"""coll/pallas_kernels — hand-rolled ring collective kernels in Pallas.

The kernel library under :mod:`ompi_tpu.coll.pallas`: ring and
bidirectional-ring reduce_scatter / allgather / allreduce, the
rank-order "linear" fold, and the two fused compute+comm kernels
(reduce_scatter fused with the ZeRO shard update, matmul-overlapped
allgather). Every function runs inside ``shard_map`` tracing with the
comm's mesh axis bound, exactly like :mod:`ompi_tpu.parallel.ring` —
and follows the *same chunk schedule*, so 'ring' results are bitwise
equal to the ppermute rings and 'linear' results are bitwise equal to
``coll/xla``'s rank-order fold.

Transport gate (``interpret=``):

- **TPU** (``interpret=False``): one monolithic ``pl.pallas_call``
  per collective that moves data with
  ``pltpu.make_async_remote_copy`` to the ring neighbor (protocol
  under "monolithic DMA kernels" below). The fused kernels consume
  the final combined chunk in VMEM (update epilogue / per-hop matmul)
  instead of round-tripping HBM. Passing a ``pltpu.InterpretParams``
  instead of ``False`` runs the SAME DMA kernels under the Pallas TPU
  interpreter, which emulates remote copies and semaphores between
  virtual CPU devices and can detect races — how tier-1 checks the
  protocol without a chip.
- **CPU** (``interpret=True``): the *hop* is a ``lax.ppermute`` while
  every *combine / fold / matmul / update* runs as a
  ``pl.pallas_call(..., interpret=True)`` kernel. The accumulation
  order is identical to the DMA schedule, which is what lets tier-1
  and the smoke lane prove ring correctness (and bit-identity vs
  ``coll/xla``) without hardware.

Which DMA kernels Mosaic compiles on a v5e, and which it refuses, is
recorded by ``chip_smoke.py`` (CHANGES.md, PR 21); no timing of this
path has been taken on a chip.
"""

from __future__ import annotations

import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ompi_tpu.util import jaxcompat

#: barrier-semaphore collective ids for the monolithic DMA kernels
#: (concurrently-live kernels must not share one)
CID_RS, CID_AG, CID_FUSED, CID_MATMUL, CID_LINEAR = 1, 2, 3, 4, 5


def _pl():
    return jaxcompat.pallas()


def _pltpu():
    return jaxcompat.pallas_tpu()


def compiled_or_raise(what: str, launch: Callable):
    """Run ``launch`` — the dispatch of a COMPILED (non-interpret)
    Pallas program. Whatever stops it — Mosaic refusing the kernel at
    lowering or compile time, or a fault on the way there — leaves as
    an ``MPIError`` that names the kernel and carries the original
    message: on the chip a kernel either runs as written or fails
    loudly, it never drops to interpret mode or to a reference
    implementation."""
    from ompi_tpu import errors

    try:
        return launch()
    except errors.MPIError:
        raise
    except Exception as exc:  # noqa: BLE001 — boundary: rename, keep
        raise errors.MPIError(
            errors.ERR_INTERN,
            f"{what}: the compiled Pallas TPU kernel did not run "
            f"({type(exc).__name__}): {exc}") from exc


def _perm(n: int, d: int):
    return [(i, (i + d) % n) for i in range(n)]


def _hop(x, axis: str, n: int, d: int):
    """One ring hop toward the +d neighbor (interpret-mode transport)."""
    return lax.ppermute(x, axis, perm=_perm(n, d))


# ---------------------------------------------------------------------------
# kernel bodies — shared verbatim between the interpret path and the
# epilogues of the monolithic DMA kernels


def _combine_body(fn: Callable):
    def kernel(a_ref, b_ref, o_ref):
        o_ref[...] = fn(a_ref[...], b_ref[...])

    return kernel


def _fold_body(n: int, fn: Callable):
    """acc = g[0]; acc = fn(acc, g[i]) for i in 1..n-1 — the exact
    statically-unrolled rank-order fold of coll/xla's 'linear' mode."""

    def kernel(g_ref, o_ref):
        acc = g_ref[0]
        for i in range(1, n):
            acc = fn(acc, g_ref[i])
        o_ref[...] = acc

    return kernel


def _roll_body(x_ref, s_ref, o_ref):
    """Rotate hop-ordered blocks into rank order (the allgather
    reassembly step; shift comes in as a (1,) scalar operand)."""
    o_ref[...] = jnp.roll(x_ref[...], s_ref[0], axis=0)


def _dot(x, w, out_dtype):
    """x @ w accumulated in f32 (Mosaic's matmul takes no narrower
    accumulator: bf16 operands with a bf16 result type are refused),
    rounded once to ``out_dtype`` — float operands only."""
    return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(
        out_dtype)


def _matmul_body(out_dtype):
    def kernel(x_ref, w_ref, o_ref):
        o_ref[...] = _dot(x_ref[...], w_ref[...], out_dtype)

    return kernel


def _apply_update(g, p, v, lr: float, mu: float, inv: Optional[float],
                  barrier: bool = True):
    """The ZeroOptimizer.step shard update, constants cast to the
    shard dtype exactly as the unfused path does. The unfused
    sequence dispatches each elementwise op as its OWN program, so
    every intermediate is correctly rounded; fused into one program
    the backend may still contract mul+add pairs into FMAs (LLVM
    contracts straight through optimization_barrier — the barriers
    only keep the op ORDER fixed), so the fused epilogue is
    equivalent to the unfused step to within one ulp, not bitwise.
    coll/pallas therefore runs this epilogue eagerly (outside the
    kernel) when ``deterministic='linear'`` demands bit-identity.
    ``barrier=False`` drops the barriers: Mosaic has no lowering for
    them, and a compiled kernel body already keeps program order."""
    ob = lax.optimization_barrier if barrier else (lambda t: t)
    if inv is not None:
        g = ob(g * jnp.asarray(inv, g.dtype))
    vn = None
    if v is not None:
        t = ob(jnp.asarray(mu, v.dtype) * v)
        vn = ob(t + g)
        g = vn
    step = ob(jnp.asarray(lr, p.dtype) * g)
    pn = p - step
    return pn, vn


def _combine_update_body(fn, lr, mu, inv, with_mom: bool):
    """Final ring combine fused with the ZeRO shard update: the
    reduced chunk is consumed in-register by the optimizer epilogue."""

    if with_mom:
        def kernel(a_ref, b_ref, p_ref, v_ref, po_ref, vo_ref):
            g = fn(a_ref[...], b_ref[...])
            pn, vn = _apply_update(g, p_ref[...], v_ref[...],
                                   lr, mu, inv)
            po_ref[...] = pn
            vo_ref[...] = vn

        return kernel

    def kernel(a_ref, b_ref, p_ref, po_ref):
        g = fn(a_ref[...], b_ref[...])
        pn, _ = _apply_update(g, p_ref[...], None, lr, mu, inv)
        po_ref[...] = pn

    return kernel


def _fold_slice_body(n: int, k: int, fn):
    """Rank-order fold + own-chunk slice (linear reduce_scatter in one
    kernel — same fold-then-slice order as C.reduce_scatter 'linear')."""

    def kernel(g_ref, r_ref, o_ref):
        full = g_ref[0]
        for i in range(1, n):
            full = fn(full, g_ref[i])
        o_ref[...] = lax.dynamic_slice_in_dim(full, r_ref[0] * k, k,
                                              axis=0)

    return kernel


def _call(body, out_shape, *args):
    """interpret-mode pallas_call over whole-array blocks."""
    pl = _pl()
    return pl.pallas_call(body, out_shape=out_shape, interpret=True)(
        *args)


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# ---------------------------------------------------------------------------
# reduce_scatter


def ring_reduce_scatter(x, axis: str, fn: Callable, *,
                        interpret: bool = True, direction: int = 1):
    """Ring reduce_scatter, chunk schedule identical to
    :func:`ompi_tpu.parallel.ring.ring_reduce_scatter` (carry starts
    at chunk r-d, step s folds ``fn(carry, own)`` with own chunk
    r-(s+2)d): dim 0 of x (size n*k) shrinks to k; rank r ends with
    chunk r reduced in ring-visit order. direction=-1 runs the
    mirror-image (counterclockwise) ring."""
    n = jaxcompat.axis_size(axis)
    if n == 1:
        return x
    assert x.shape[0] % n == 0, (
        f"ring_reduce_scatter: dim0 {x.shape[0]} not divisible by {n}")
    k = x.shape[0] // n
    if interpret is not True:
        return _dma_reduce_scatter(x, axis, n, k, fn, direction,
                                   interpret)
    chunks = x.reshape((n, k) + x.shape[1:])
    r = lax.axis_index(axis)
    carry = lax.dynamic_index_in_dim(chunks, (r - direction) % n,
                                     keepdims=False)
    for s in range(n - 1):
        carry = _hop(carry, axis, n, direction)
        own = lax.dynamic_index_in_dim(
            chunks, (r - (s + 2) * direction) % n, keepdims=False)
        carry = _call(_combine_body(fn),
                      _sds(carry.shape, carry.dtype), carry, own)
    return carry


def bidir_reduce_scatter(x, axis: str, fn: Callable, *,
                         interpret: bool = True):
    """Bidirectional ring reduce_scatter: the front half of every
    chunk's rows travels the clockwise ring, the back half the
    counterclockwise ring — both ICI link directions carry payload
    simultaneously. Deterministic (fixed schedule) but its fold order
    is its own; callers pick it only when no bit-identity mode was
    requested. Requires >= 2 rows per chunk (fall back to ring below
    that)."""
    n = jaxcompat.axis_size(axis)
    if n == 1:
        return x
    k = x.shape[0] // n
    h = k // 2
    assert h >= 1, "bidir_reduce_scatter: need >= 2 rows per chunk"
    rest = x.shape[1:]
    chunks = x.reshape((n, k) + rest)
    front = chunks[:, :h].reshape((n * h,) + rest)
    back = chunks[:, h:].reshape((n * (k - h),) + rest)
    cf = ring_reduce_scatter(front, axis, fn, interpret=interpret,
                             direction=1)
    cb = ring_reduce_scatter(back, axis, fn, interpret=interpret,
                             direction=-1)
    return jnp.concatenate([cf, cb], axis=0)


def linear_reduce_scatter(x, axis: str, fn: Callable, *,
                          interpret: bool = True):
    """'linear' reduce_scatter: gather every rank's contribution,
    fold in exact rank order, slice the own chunk — one pallas
    kernel, elementwise bit-identical to coll/xla's
    allreduce-linear + slice path."""
    n = jaxcompat.axis_size(axis)
    if n == 1:
        return x
    k = x.shape[0] // n
    g = _gather_stack(x, axis, n, interpret)
    if interpret is not True:
        # fold only the own chunk: elementwise, so slice-then-fold is
        # bitwise fold-then-slice, and Mosaic cannot slice a VALUE at
        # a dynamic offset
        own = lax.dynamic_slice_in_dim(g, lax.axis_index(axis) * k, k,
                                       axis=1)
        return _tiled_fold(own, n, fn, interpret)
    r = lax.axis_index(axis).astype(jnp.int32)[None]
    return _call(_fold_slice_body(n, k, fn),
                 _sds((k,) + x.shape[1:], x.dtype), g, r)


# ---------------------------------------------------------------------------
# allgather


def ring_allgather(x, axis: str, *, interpret: bool = True,
                   direction: int = 1):
    """Ring allgather: local [k, ...] -> [n*k, ...] with rank i's
    block at chunk i (the parallel/ring.py placement). The interpret
    path collects blocks in hop order and rotates them into rank
    order with one pallas roll kernel."""
    n = jaxcompat.axis_size(axis)
    if n == 1:
        return x
    if interpret is not True:
        return _dma_allgather(x, axis, n, direction, interpret)
    r = lax.axis_index(axis)
    blocks = [x]
    blk = x
    for _ in range(n - 1):
        blk = _hop(blk, axis, n, direction)
        blocks.append(blk)
    # hop order: block j is rank (r - j*d)'s. Rotate into rank order:
    # d=+1 -> reverse then roll by r+1; d=-1 -> roll by r.
    if direction == 1:
        arr = jnp.stack(blocks[::-1])
        shift = (r + 1).astype(jnp.int32)[None]
    else:
        arr = jnp.stack(blocks)
        shift = r.astype(jnp.int32)[None]
    out = _call(_roll_body, _sds(arr.shape, arr.dtype), arr, shift)
    return out.reshape((n * x.shape[0],) + x.shape[1:])


def bidir_allgather(x, axis: str, *, interpret: bool = True):
    """Bidirectional ring allgather: front rows clockwise, back rows
    counterclockwise; each direction moves half the payload."""
    n = jaxcompat.axis_size(axis)
    if n == 1:
        return x
    k = x.shape[0]
    h = k // 2
    assert h >= 1, "bidir_allgather: need >= 2 rows per block"
    rest = x.shape[1:]
    gf = ring_allgather(x[:h], axis, interpret=interpret, direction=1)
    gb = ring_allgather(x[h:], axis, interpret=interpret, direction=-1)
    gf = gf.reshape((n, h) + rest)
    gb = gb.reshape((n, k - h) + rest)
    return jnp.concatenate([gf, gb], axis=1).reshape((n * k,) + rest)


def _gather_stack(x, axis: str, n: int, interpret: bool):
    """[n, *x.shape] stack of every rank's block (rank i at index i) —
    the 'linear' transport. Interpret mode uses lax.all_gather (the
    very op coll/xla's linear fold gathers with, so operands are
    bitwise identical); the DMA path rings the flat payload around."""
    if interpret is True:
        return lax.all_gather(x, axis)
    full = _dma_allgather(x.reshape((1,) + x.shape), axis, n, 1,
                          interpret)
    return full.reshape((n,) + x.shape)


# ---------------------------------------------------------------------------
# allreduce


def ring_allreduce(x, axis: str, fn: Callable, *,
                   interpret: bool = True, bidir: bool = False):
    """Bandwidth-optimal allreduce = reduce_scatter + allgather over
    the flattened payload, zero-padded to a multiple of n — the exact
    pad/slice framing of parallel.ring.ring_allreduce, so the 'ring'
    result is bitwise equal to coll/xla's ring mode."""
    n = jaxcompat.axis_size(axis)
    if n == 1:
        return x
    shape = x.shape
    flat = x.reshape(-1)
    m = flat.shape[0]
    pad = (-m) % n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), x.dtype)])
    if bidir and flat.shape[0] // n >= 2:
        chunk = bidir_reduce_scatter(flat, axis, fn,
                                     interpret=interpret)
        full = bidir_allgather(chunk, axis, interpret=interpret)
    else:
        chunk = ring_reduce_scatter(flat, axis, fn,
                                    interpret=interpret)
        full = ring_allgather(chunk, axis, interpret=interpret)
    return full[:m].reshape(shape)


def linear_allreduce(x, axis: str, fn: Callable, *,
                     interpret: bool = True):
    """'linear' allreduce: gather all contributions, fold in exact
    rank order 0..n-1 inside one pallas kernel — bit-identical to
    coll/xla's ``_allreduce_linear`` (same gathered operands, same
    statically-unrolled fold)."""
    n = jaxcompat.axis_size(axis)
    if n == 1:
        return x
    g = _gather_stack(x, axis, n, interpret)
    if interpret is not True:
        return _tiled_fold(g, n, fn, interpret)
    return _call(_fold_body(n, fn), _sds(x.shape, x.dtype), g)


# ---------------------------------------------------------------------------
# fused: reduce_scatter + ZeRO shard update


def ring_reduce_scatter_update(x, axis: str, fn: Callable, p, v, *,
                               lr: float, mu: float,
                               inv: Optional[float],
                               interpret: bool = True):
    """Ring reduce_scatter whose FINAL combine step is fused with the
    ZeRO stage-1/2 shard update: the reduced gradient chunk is
    consumed in-register by ``p -= lr * (mu*v + g*inv)`` instead of
    round-tripping HBM. x is the flat padded bucket (n*k,), p/v the
    (k,) param/momentum shards (v may be None). Returns (p', v')."""
    n = jaxcompat.axis_size(axis)
    k = x.shape[0] // n
    with_mom = v is not None
    if interpret is not True:
        return _dma_reduce_scatter_update(x, axis, n, k, fn, p, v,
                                          lr=lr, mu=mu, inv=inv,
                                          interpret=interpret)
    chunks = x.reshape((n, k))
    r = lax.axis_index(axis)
    carry = lax.dynamic_index_in_dim(chunks, (r - 1) % n,
                                     keepdims=False)
    for s in range(n - 2):
        carry = _hop(carry, axis, n, 1)
        own = lax.dynamic_index_in_dim(chunks, (r - 2 - s) % n,
                                       keepdims=False)
        carry = _call(_combine_body(fn),
                      _sds(carry.shape, carry.dtype), carry, own)
    # last hop: combine + update in ONE kernel
    carry = _hop(carry, axis, n, 1)
    own = lax.dynamic_index_in_dim(chunks, (r - n) % n, keepdims=False)
    body = _combine_update_body(fn, lr, mu, inv, with_mom)
    if with_mom:
        return _call(body, (_sds(p.shape, p.dtype),
                            _sds(v.shape, v.dtype)),
                     carry, own, p, v)
    pn, = _call(body, (_sds(p.shape, p.dtype),), carry, own, p)
    return pn, None


# ---------------------------------------------------------------------------
# fused: matmul-overlapped allgather (tensor parallelism)


def allgather_matmul(x, w, axis: str, *, interpret: bool = True):
    """allgather(x) @ w with the per-block matmul overlapping the
    next ring hop (the tensor-parallel row-gather fusion): x is the
    local (m, d) block of a row-sharded activation, w the local
    (d, f) weight, both of one float dtype; returns the full
    (n*m, f) product. Each arriving
    block is multiplied while the following block is in flight —
    never materializing the gathered (n*m, d) activation."""
    n = jaxcompat.axis_size(axis)
    out_dtype = jnp.result_type(x.dtype, w.dtype)
    if n == 1:
        return _call(_matmul_body(out_dtype),
                     _sds((x.shape[0], w.shape[1]), out_dtype), x, w)
    if interpret is not True:
        return _dma_allgather_matmul(x, w, axis, n, out_dtype,
                                     interpret)
    m, f = x.shape[0], w.shape[1]
    r = lax.axis_index(axis)
    body = _matmul_body(out_dtype)
    prods = [_call(body, _sds((m, f), out_dtype), x, w)]
    blk = x
    for _ in range(n - 1):
        blk = _hop(blk, axis, n, 1)
        prods.append(_call(body, _sds((m, f), out_dtype), blk, w))
    arr = jnp.stack(prods[::-1])  # hop order -> rank order (cw ring)
    shift = (r + 1).astype(jnp.int32)[None]
    out = _call(_roll_body, _sds(arr.shape, arr.dtype), arr, shift)
    return out.reshape((n * m, f))


# ---------------------------------------------------------------------------
# monolithic DMA kernels (TPU path — ``interpret`` is False, or a
# pltpu.InterpretParams to run them under the TPU interpreter on CPU)
#
# Layout: Mosaic addresses whole (sublane, 128) tiles, so every payload
# is flattened and zero-padded into [chunk, rows, 128] with ``rows`` a
# whole number of native tiles (_to_tiles); a dynamic chunk number then
# indexes the untiled leading dimension, never a tile interior.
#
# Protocol: a barrier-semaphore handshake with both ring neighbors
# opens every kernel, so no rank DMAs into a peer that has not entered
# it. No rank can LEAVE a ring kernel before every rank has passed that
# handshake — its last hop carries data that was forwarded by all the
# others — so a neighbor's signal for the next kernel can never be
# taken for this one's. Every hop lands in a buffer (and signals a
# semaphore pair) of its own: nothing a neighbor writes is written
# twice, so a fast neighbor running steps ahead cannot overwrite data
# that is still unread. allgather needs no arithmetic and copies
# HBM -> HBM; every other kernel keeps its operands, landing slots and
# outputs whole in VMEM. The *_vmem_bytes functions below say how much
# that is; the callers hold it under coll_pallas_dma_max_bytes and send
# what does not fit one level down (coll/pallas.dma_fits).

_LANES = 128


def ring_vmem_bytes(n: int, payload_bytes: int,
                    extra_chunks: int = 1) -> int:
    """VMEM a reducing ring kernel keeps resident for an n-chunk
    payload: the payload, n-1 landing slots and the carry, plus
    ``extra_chunks`` chunk-sized buffers — the output chunk (plain
    reduce_scatter: 1) or the shard operands with an output each
    (fused ZeRO update: 2, with momentum 4)."""
    chunk = -(-payload_bytes // n)
    return payload_bytes + (n + extra_chunks) * chunk


def matmul_vmem_bytes(n: int, x, w, out_dtype) -> int:
    """VMEM allgather_matmul keeps resident: the local block and its
    n-1 landing slots, the weight, and the whole (n*m, f) product."""
    return (n * x.nbytes + w.nbytes
            + n * x.shape[0] * w.shape[1] * jnp.dtype(out_dtype).itemsize)


def _to_tiles(chunks):
    """[c, e] -> [c, rows, 128], zero-padded to whole native tiles
    (8 sublanes of 32-bit, 16 of 16-bit elements)."""
    c, e = chunks.shape
    tile = 8 * (4 // chunks.dtype.itemsize) * _LANES
    pad = (-e) % tile
    if pad:
        chunks = jnp.pad(chunks, ((0, 0), (0, pad)))
    return chunks.reshape(c, (e + pad) // _LANES, _LANES)


def _tile(x):
    """One array as a single chunk of whole tiles: -> [rows, 128]."""
    return _to_tiles(x.reshape(1, -1))[0]


def _from_tiles(tiles, shape):
    """Inverse of _to_tiles for ONE chunk: [rows, 128] -> shape."""
    return tiles.reshape(-1)[:math.prod(shape)].reshape(shape)


def _neighbor_handshake(axis: str, my, n: int, d: int):
    pltpu = _pltpu()
    nxt = (my + d) % n
    prv = (my - d) % n
    barrier = pltpu.get_barrier_semaphore()
    for peer in (nxt, prv):
        pltpu.semaphore_signal(
            barrier, 1, device_id={axis: peer},
            device_id_type=jaxcompat.pallas_device_id_type())
    pltpu.semaphore_wait(barrier, 2)
    return nxt


def _hop_dma(axis: str, src, dst, send_sem, recv_sem, nxt):
    """One ring hop: my ``src`` into the +d neighbor's ``dst``; the
    descriptor's wait() covers my send AND the matching arrival from
    the -d neighbor (every rank issues the same copy)."""
    return _pltpu().make_async_remote_copy(
        src_ref=src, dst_ref=dst, send_sem=send_sem, recv_sem=recv_sem,
        device_id={axis: nxt},
        device_id_type=jaxcompat.pallas_device_id_type())


def _ring_scratch(pltpu, n: int, shape, dtype):
    """n-1 landing slots, one carry buffer, a send and a receive DMA
    semaphore per hop."""
    return [pltpu.VMEM((n - 1,) + shape, dtype),
            pltpu.VMEM(shape, dtype),
            pltpu.SemaphoreType.DMA((n - 1,)),
            pltpu.SemaphoreType.DMA((n - 1,))]


def _ring_reduce(axis: str, n: int, d: int, fn, x_ref, land, acc,
                 send_sem, recv_sem):
    """The reduce_scatter ring over a VMEM-resident [n, rows, 128]
    payload; returns the fully reduced own chunk as a value. Carry
    starts at chunk my-d; step s folds ``fn(arrived, own)`` with own
    chunk my-(s+2)d — the parallel/ring.py schedule."""
    my = lax.axis_index(axis)
    nxt = _neighbor_handshake(axis, my, n, d)
    val = None
    for s in range(n - 1):
        src = x_ref.at[(my - d) % n] if s == 0 else acc
        rdma = _hop_dma(axis, src, land.at[s], send_sem.at[s],
                        recv_sem.at[s], nxt)
        rdma.start()
        rdma.wait()
        val = fn(land[s], x_ref[(my - (s + 2) * d) % n])
        if s < n - 2:
            acc[...] = val
    return val


def _dma_reduce_scatter(x, axis: str, n: int, k: int, fn: Callable,
                        d: int, interpret=False):
    pl, pltpu = _pl(), _pltpu()
    chunk_shape = (k,) + x.shape[1:]
    tiles = _to_tiles(x.reshape(n, -1))
    tile_shape = tiles.shape[1:]

    def kernel(x_ref, o_ref, *scratch):
        o_ref[...] = _ring_reduce(axis, n, d, fn, x_ref, *scratch)

    out = pl.pallas_call(
        kernel,
        out_shape=_sds(tile_shape, x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=_ring_scratch(pltpu, n, tile_shape, x.dtype),
        compiler_params=jaxcompat.pallas_compiler_params(CID_RS),
        interpret=interpret,
    )(tiles)
    return _from_tiles(out, chunk_shape)


def _dma_allgather(x, axis: str, n: int, d: int, interpret=False):
    pl, pltpu = _pl(), _pltpu()
    tiles = _tile(x)

    def kernel(x_ref, o_ref, copy_sem, send_sem, recv_sem):
        my = lax.axis_index(axis)
        nxt = _neighbor_handshake(axis, my, n, d)
        own = pltpu.make_async_copy(x_ref, o_ref.at[my], copy_sem)
        own.start()
        own.wait()
        for s in range(n - 1):
            # forward the block that arrived last hop (own at s=0)
            # into the SAME slot of the neighbor's output
            blk = o_ref.at[(my - s * d) % n]
            rdma = _hop_dma(axis, blk, blk, send_sem.at[s],
                            recv_sem.at[s], nxt)
            rdma.start()
            rdma.wait()

    out = pl.pallas_call(
        kernel,
        out_shape=_sds((n,) + tiles.shape, x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA((n - 1,)),
            pltpu.SemaphoreType.DMA((n - 1,)),
        ],
        compiler_params=jaxcompat.pallas_compiler_params(CID_AG),
        interpret=interpret,
    )(tiles)
    full = out.reshape(n, -1)[:, :x.size]
    return full.reshape((n * x.shape[0],) + x.shape[1:])


def _tiled_fold(g, n: int, fn: Callable, interpret=False):
    """Rank-order fold of g[0..n-1] as a row-blocked Pallas kernel
    (the 'linear' combine on TPU): [n, *shape] -> shape."""
    pl, pltpu = _pl(), _pltpu()
    shape = g.shape[1:]
    tiles = _to_tiles(g.reshape(n, -1))
    rows = tiles.shape[1]
    block = min(rows, 512)
    out = pl.pallas_call(
        _fold_body(n, fn),
        out_shape=_sds((rows, _LANES), g.dtype),
        grid=(pl.cdiv(rows, block),),
        in_specs=[pl.BlockSpec((n, block, _LANES),
                               lambda i: (0, i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((block, _LANES), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(tiles)
    return _from_tiles(out, shape)


def _dma_reduce_scatter_update(x, axis: str, n: int, k: int,
                               fn: Callable, p, v, *, lr, mu, inv,
                               interpret=False):
    pl, pltpu = _pl(), _pltpu()
    with_mom = v is not None
    tiles = _to_tiles(x.reshape(n, -1))
    tile_shape = tiles.shape[1:]
    shards = [_tile(t) for t in ((p, v) if with_mom else (p,))]

    def kernel(x_ref, *refs):
        ins, outs = refs[:len(shards)], refs[len(shards):2 * len(shards)]
        g = _ring_reduce(axis, n, 1, fn, x_ref, *refs[2 * len(shards):])
        # fused epilogue: the reduced chunk never leaves VMEM
        pn, vn = _apply_update(g, ins[0][...],
                               ins[1][...] if with_mom else None,
                               lr, mu, inv, barrier=False)
        outs[0][...] = pn
        if with_mom:
            outs[1][...] = vn

    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    outs = pl.pallas_call(
        kernel,
        out_shape=tuple(_sds(tile_shape, t.dtype) for t in shards),
        in_specs=[vmem] * (1 + len(shards)),
        out_specs=tuple([vmem] * len(shards)),
        scratch_shapes=_ring_scratch(pltpu, n, tile_shape, x.dtype),
        compiler_params=jaxcompat.pallas_compiler_params(CID_FUSED),
        interpret=interpret,
    )(tiles, *shards)
    pn = _from_tiles(outs[0], p.shape)
    return (pn, _from_tiles(outs[1], v.shape)) if with_mom \
        else (pn, None)


def _dma_allgather_matmul(x, w, axis: str, n: int, out_dtype,
                          interpret=False):
    pl, pltpu = _pl(), _pltpu()
    m, f = x.shape[0], w.shape[1]

    def kernel(x_ref, w_ref, o_ref, land, send_sem, recv_sem):
        my = lax.axis_index(axis)
        nxt = _neighbor_handshake(axis, my, n, 1)
        for s in range(n - 1):
            blk = x_ref if s == 0 else land.at[s - 1]
            rdma = _hop_dma(axis, blk, land.at[s], send_sem.at[s],
                            recv_sem.at[s], nxt)
            rdma.start()
            # overlap: multiply the block that arrived last hop (own
            # block at s=0) while this hop's DMA is in flight
            o_ref[(my - s) % n] = _dot(blk[...], w_ref[...], out_dtype)
            rdma.wait()
        o_ref[(my - (n - 1)) % n] = _dot(land[n - 2], w_ref[...],
                                         out_dtype)

    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        kernel,
        out_shape=_sds((n, m, f), out_dtype),
        in_specs=[vmem, vmem],
        out_specs=vmem,
        scratch_shapes=[
            pltpu.VMEM((n - 1,) + x.shape, x.dtype),
            pltpu.SemaphoreType.DMA((n - 1,)),
            pltpu.SemaphoreType.DMA((n - 1,)),
        ],
        compiler_params=jaxcompat.pallas_compiler_params(CID_MATMUL),
        interpret=interpret,
    )(x, w)
    return out.reshape((n * m, f))
