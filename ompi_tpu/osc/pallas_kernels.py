"""osc/pallas_kernels — device-resident RMA kernels in Pallas.

The kernel library under :mod:`ompi_tpu.osc.pallas`, following the
coll/pallas_kernels transport discipline (PR 10):

- **Apply layer** (both backends): every window mutation — Put,
  elementwise Accumulate, their strided halo variants — is one jitted
  XLA update of the flat window array at the landed payload's
  positions (:func:`apply`; displacement and stride are runtime
  operands, so one compiled program serves them all), and every
  window read an XLA slice (:func:`read`). Not Pallas: Mosaic
  addresses whole (sublane, 128) tiles and cannot touch a ref at an
  arbitrary element offset, so a kernel here could only select over
  the whole window what XLA updates in place. The window is NOT
  donated — ``PallasWindow.array`` and the active-message thread read
  the same buffer — so an update copies the window once. The layer
  is the same program on TPU and CPU, which is what lets tier-1 prove
  bit-identity against the host window without hardware.
- **Transport layer**: on TPU :func:`dma_permute` moves one
  edge-colored round's payloads with ``pltpu.make_async_remote_copy``
  into the receiver's VMEM landing scratch — semaphore-paced
  (DMA send/recv pair), opened by a barrier-semaphore handshake
  (``collective_id`` :data:`CID_RMA`; ids 1-5 belong to the
  coll/pallas ring kernels). On CPU the hop is a ``lax.ppermute``
  built by the caller — same round structure, same apply layer,
  identical results.

Which of these Mosaic compiles on a v5e is recorded by
``chip_smoke.py`` (CHANGES.md, PR 21); no timing of this path has been
taken on a chip.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
from jax import lax

from ompi_tpu.coll.pallas_kernels import (
    _from_tiles, _pl, _pltpu, _sds, _tile,
)
from ompi_tpu.util import jaxcompat

#: barrier-semaphore collective id for the RMA round kernel
#: (CID 1-5 are the coll/pallas ring kernels; concurrently-live
#: kernels must not share one)
CID_RMA = 6

#: accumulate kind -> combine(current_window_slice, payload).
#: "put"/"replace" overwrite; the rest are the elementwise MPI ops the
#: fence program can fuse (the device_epoch._APPLY set — everything
#: else is the caller's staged-fallthrough problem).
_COMBINE = {
    "put": lambda cur, p: p,
    "replace": lambda cur, p: p,
    "sum": lambda cur, p: cur + p,
    "min": jnp.minimum,
    "max": jnp.maximum,
    "prod": lambda cur, p: cur * p,
}

ELEMENTWISE = frozenset(_COMBINE)


# ---------------------------------------------------------------------------
# apply layer — window mutation and read (XLA, both backends)


@lru_cache(maxsize=512)
def _apply_fn(k: int, kind: str, strided: bool):
    """window' = window with _COMBINE[kind](window[i], payload[j]) at
    i = d + j*s for j < k (s = 1 unless ``strided``)."""
    fn = _COMBINE[kind]

    def run(w, p, d, s):
        if strided:
            idx = d + s * jnp.arange(k, dtype=jnp.int32)
            return w.at[idx].set(fn(w[idx], p))
        cur = lax.dynamic_slice(w, (d,), (k,))
        return lax.dynamic_update_slice(w, fn(cur, p), (d,))

    return jax.jit(run)


def apply(window, payload, disp: int, kind: str, stride: int = 1):
    """Apply one RMA descriptor to the flat window array; returns the
    new window. ``kind`` is an :data:`ELEMENTWISE` name."""
    fn = _apply_fn(int(payload.shape[0]), kind, stride != 1)
    return fn(window, payload, jnp.int32(disp), jnp.int32(stride))


@lru_cache(maxsize=512)
def _read_fn(k: int, strided: bool):
    if strided:
        return jax.jit(lambda w, d, s: jnp.take(
            w, d + s * jnp.arange(k, dtype=jnp.int32), axis=0))
    return jax.jit(lambda w, d, s: lax.dynamic_slice(w, (d,), (k,)))


def read(window, disp: int, nelems: int, stride: int = 1):
    """``nelems`` window elements from ``disp`` (element stride
    ``stride``) as a device payload — the Get-side read."""
    return _read_fn(int(nelems), stride != 1)(
        window, jnp.int32(disp), jnp.int32(stride))


# ---------------------------------------------------------------------------
# transport layer — the TPU DMA round kernel


def round_vmem_bytes(payload_bytes: int) -> int:
    """VMEM :func:`dma_permute` keeps resident: the payload, its
    landing scratch and the landed output."""
    return 3 * payload_bytes


def dma_permute(payload, tgt, src, axis: str, n: int, interpret=False):
    """One edge-colored RMA round on TPU: DMA my (k,) ``payload`` into
    rank ``tgt``'s VMEM landing scratch, receive my own landing from
    rank ``src``; returns the landed payload (zeros when ``src`` is
    the -1 no-partner sentinel). ``tgt``/``src`` are (1,) int32 mesh
    coordinates — runtime operands, so ONE compiled kernel serves
    every round's pairing. Runs inside ``shard_map`` with the window
    comm's ``axis`` (size ``n``) bound, like every coll/pallas DMA
    kernel.

    Protocol: an all-ranks barrier-semaphore handshake opens the
    round. A round's pairs do not depend on each other, so a
    partners-only handshake is not enough: a rank that finished early
    could signal its NEXT round's partner, be counted for this one,
    and let that partner send to a rank that has not entered yet.
    With every rank signalling every other, any signal from a later
    round proves that all ranks passed this one's barrier. Then one
    ``make_async_remote_copy`` per edge, paced by a DMA send/recv
    semaphore pair — the sender waits its send, the receiver blocks
    on the arrival before reading the landing scratch, giving
    per-edge completion exactly where the reference's osc/rdma waits
    its BTL RDMA completions."""
    pl, pltpu = _pl(), _pltpu()
    did = jaxcompat.pallas_device_id_type()
    k = int(payload.shape[0])
    tiles = _tile(payload)

    def kernel(p_ref, t_ref, s_ref, o_ref, land, send_sem, recv_sem):
        my = lax.axis_index(axis)
        barrier = pltpu.get_barrier_semaphore()
        for j in range(1, n):
            pltpu.semaphore_signal(barrier, 1,
                                   device_id={axis: (my + j) % n},
                                   device_id_type=did)
        pltpu.semaphore_wait(barrier, n - 1)

        def edge(peer):
            return pltpu.make_async_remote_copy(
                src_ref=p_ref, dst_ref=land, send_sem=send_sem,
                recv_sem=recv_sem, device_id={axis: peer},
                device_id_type=did)

        @pl.when(t_ref[0] >= 0)
        def _send():
            rdma = edge(t_ref[0])
            rdma.start()
            rdma.wait_send()

        o_ref[...] = jnp.zeros_like(o_ref)

        @pl.when(s_ref[0] >= 0)
        def _recv():
            edge(s_ref[0]).wait_recv()
            o_ref[...] = land[...]

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        kernel,
        out_shape=_sds(tiles.shape, payload.dtype),
        in_specs=[vmem, smem, smem],
        out_specs=vmem,
        scratch_shapes=[
            pltpu.VMEM(tiles.shape, payload.dtype),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA(()),
        ],
        compiler_params=jaxcompat.pallas_compiler_params(CID_RMA),
        interpret=interpret,
    )(tiles,
      jnp.asarray(tgt, jnp.int32).reshape((1,)),
      jnp.asarray(src, jnp.int32).reshape((1,)))
    return _from_tiles(out, (k,))
