"""The Pallas TPU kernels of the short causal depthwise convolution:
``silu(b + sum_j w[:, j] * x[t - (K - 1) + j])`` with zeros before the
sequence, in ONE pass over its bytes (``ops/ssm.py`` owns the entry,
``causal_conv``, the rule that says when the kernels run, ``conv_tile``,
and their oracle, the ``jax.numpy`` form of K shifted sums).

Two kernels behind one ``custom_vjp`` (:func:`conv`) that keeps the
OPERAND and the small leaves and nothing else:

* :func:`forward` (``causal_conv_fwd``): a grid over (batch, channel
  tiles, time tiles), every step independent. A step reads one block of
  the operand and, as a second small block on the same array, the tile
  of rows in front of it (its index clamped at the sequence's start and
  the rows then masked to zeros: the zeros before the sequence); it
  forms the K shifted products and their sum in the oracle's order, adds
  the bias where there is one, applies SiLU — all in float32 — and
  rounds ONCE to the operand's type.
* :func:`backward` (``causal_conv_bwd``): a grid over (channel tiles,
  batch, time tiles). A step makes the pre-activation again from the
  block it reads anyway (and, of the tile BEHIND the block, for the
  K - 1 rows the block's ``dx`` reaches into), forms ``d pre = dy *
  silu'(pre)`` in float32, writes ``dx`` — the transposed taps: ``d
  pre`` shifted the other way — in the operand's type and adds ``dw``
  and ``db`` in float32 into output blocks that stay in VMEM while the
  grid walks a channel tile's batch rows and time tiles. One read of x
  and dy, one write of dx.

**Two layouts, one arithmetic.** A caller's arrays are read and written
as they lie: ``[B, T, C]`` with the channels in the lanes and time in
the sublanes (``ops/kda._heads``: the products wrote q, k, v so and
``kda_delta_fwd`` reads them so), or ``[B, C, T]`` with TIME in the
lanes (``ops/ssm.mixer``: XLA lays ``in_proj``'s product out so by
itself and ``ssm_scan_fwd`` reads the convolved ``xBC`` so) — `time`
below is the block's time axis, 0 or 1. A shift along time is a
rotation (``pltpu.roll``) of the block's rows or lanes with the
neighbouring tile in front of (behind) it; the taps are a row ``[1,
C]`` over the sublanes or a column ``[C, 1]`` over the lanes.

A block is worked a CHUNK at a time — sixteen float32 registers'
worth, ``[128, 128]`` or ``[32, 512]`` (scripts/conv_probe.py reads
others on the chip) — so that a chunk's dozen intermediate values live
in registers and not in VMEM: the lanes' extent in a static
loop, the sublanes' in a ``fori_loop`` (time in the sublanes carries the
chunk's last rows forward, or ``d pre``'s first rows backward, as the
loop's value).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ompi_tpu.ops.grouped_matmul import VMEM_LIMIT_BYTES
from ompi_tpu.ops.ssm import LANES
from ompi_tpu.ops.ssm_scan import _spread

F32 = jnp.float32
#: the entries of the neighbouring tile a chunk takes along time: one
#: float32 tile of rows, one tile of lanes. K - 1 may not pass the
#: first (`ssm.conv_tile` refuses such taps for both layouts)
HALO = (8, LANES)
#: a chunk's extent along (sublanes, lanes)
_CHUNK = ((128, LANES), (32, 512))


class Tile(NamedTuple):
    """A grid step's block: `time` entries of `channels` channels."""
    time: int
    channels: int


def _before(cur, prev, s: int, time: int):
    """`cur` `s` entries LATER along `time`: ``out[t] = cur[t - s]``,
    the last `s` entries of `prev` (one :data:`HALO`) in front."""
    if not s:
        return cur
    h = prev.shape[time]
    rolled = pltpu.roll(jnp.concatenate([prev, cur], axis=time), s, time)
    return lax.slice_in_dim(rolled, h, h + cur.shape[time], axis=time)


def _after(cur, nxt, s: int, time: int):
    """``out[t] = cur[t + s]``, the first `s` entries of `nxt` behind."""
    if not s:
        return cur
    both = jnp.concatenate([cur, nxt], axis=time)
    rolled = pltpu.roll(both, both.shape[time] - s, time)
    return lax.slice_in_dim(rolled, 0, cur.shape[time], axis=time)


def _pre(cur, prev, w, b, time: int):
    """The pre-activation of a chunk and its K shifted operands: the
    oracle's sum, tap 0 (the oldest entry's) first, the bias added to
    the finished sum. cur, prev float32; w: the K taps, each
    broadcastable against `cur`; b likewise or None."""
    k = len(w)
    shifted = [_before(cur, prev, k - 1 - j, time) for j in range(k)]
    acc = shifted[0] * w[0]
    for j in range(1, k):
        acc = acc + shifted[j] * w[j]
    return (acc if b is None else b + acc), shifted


def _dsilu(pre):
    """The derivative of ``x sigmoid(x)``."""
    s = jax.nn.sigmoid(pre)
    return s * (1.0 + pre * (1.0 - s))


def _folded(x, time: int):
    """`x` summed along `time` down to one :data:`HALO`-wide tile
    (whole registers added, nothing crosses a sublane or a lane)."""
    h = HALO[time]
    return sum(lax.slice_in_dim(x, a, a + h, axis=time)
               for a in range(0, x.shape[time], h))


def _taps(w_ref, cs, k: int, time: int):
    """The K taps of channels `cs` of a block's `w_ref` — ``[K, C]``
    under time in the sublanes, ``[C, K]`` under time in the lanes."""
    if time == 0:
        return [w_ref[j:j + 1, cs] for j in range(k)]
    block = w_ref[cs, :]
    return [block[:, j:j + 1] for j in range(k)]


def _of(ref, time: int, ts, cs):
    """A block's entries `ts` along time of channels `cs`."""
    return ref[(ts, cs) if time == 0 else (cs, ts)]


def _last(x, n: int, time: int):
    return lax.slice_in_dim(x, x.shape[time] - n, x.shape[time], axis=time)


def _first(x, n: int, time: int):
    return lax.slice_in_dim(x, 0, n, axis=time)


def _walk(shape, time: int, start, chunk, done=None, *,
          reverse: bool = False):
    """Work a block of `shape` a chunk at a time. For every run of
    channels `cs` (a ``pl.ds``): ``leaves, state = start(cs)`` (the
    run's taps and bias, read once), then ``state = chunk(t0, size,
    cs, leaves, *state)`` for every chunk of `size` entries at `t0`
    along time — ascending, or descending under `reverse` — and last
    ``done(cs, *state)``. The sublanes' extent is a ``fori_loop`` (its
    offsets traced), the lanes' a static loop (its offsets ints)."""
    sub, lane = (math.gcd(c, n) for c, n in zip(_CHUNK[time], shape))
    n_sub, n_lane = shape[0] // sub, shape[1] // lane
    if time == 0:     # channels in the lanes: static; time: the loop
        for c in range(n_lane):
            cs = pl.ds(c * lane, lane)

            leaves, state = start(cs)

            def body(i, state, cs=cs, leaves=leaves):
                i = n_sub - 1 - i if reverse else i
                return chunk(pl.multiple_of(i * sub, sub), sub, cs, leaves,
                             *state)

            state = lax.fori_loop(0, n_sub, body, state)
            if done is not None:
                done(cs, *state)
    else:             # channels in the sublanes: the loop; time: static
        def body(c, _):
            cs = pl.ds(pl.multiple_of(c * sub, sub), sub)
            leaves, state = start(cs)
            for i in (range(n_lane - 1, -1, -1) if reverse
                      else range(n_lane)):
                state = chunk(i * lane, lane, cs, leaves, *state)
            if done is not None:
                done(cs, *state)
            return 0

        lax.fori_loop(0, n_sub, body, 0)


def _put(ref, time: int, t0, size: int, cs, value):
    ref[(pl.ds(t0, size), cs) if time == 0 else (cs, pl.ds(t0, size))] = \
        value.astype(ref.dtype)


def _leaves_of(w_ref, b_ref, cs, k: int, time: int):
    """(the K taps, the bias or None) of channels `cs`, each
    broadcastable against a chunk."""
    b = None if b_ref is None else _of(b_ref, time, slice(None), cs)
    return _taps(w_ref, cs, k, time), b


def _fwd_kernel(*refs, k: int, time: int, bias: bool):
    x_ref, before_ref, w_ref = refs[:3]
    b_ref = refs[3] if bias else None
    o_ref = refs[-1]
    opens = pl.program_id(2) == 0           # zeros before the sequence
    h = HALO[time]

    def start(cs):
        front = _of(before_ref, time, slice(None), cs).astype(F32)
        return (_leaves_of(w_ref, b_ref, cs, k, time),
                (jnp.where(opens, 0.0, _last(front, h, time)),))

    def chunk(t0, size, cs, leaves, prev):
        cur = _of(x_ref, time, pl.ds(t0, size), cs).astype(F32)
        pre, _ = _pre(cur, prev, *leaves, time)
        _put(o_ref, time, t0, size, cs, jax.nn.silu(pre))
        return (_last(cur, h, time),)

    _walk(x_ref.shape, time, start, chunk)


def _bwd_kernel(*refs, k: int, time: int, bias: bool):
    x_ref, before_ref, behind_ref, dy_ref, dy_behind_ref, w_ref = refs[:6]
    b_ref = refs[6] if bias else None
    dx_ref, dw_ref = refs[6 + bias:8 + bias]
    db_ref = refs[-1] if bias else None
    opens = pl.program_id(2) == 0
    closes = pl.program_id(2) == pl.num_programs(2) - 1
    h, wide = HALO[time], before_ref.shape[time]
    n_time = x_ref.shape[time]

    @pl.when(jnp.logical_and(pl.program_id(1) == 0, opens))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        if bias:
            db_ref[...] = jnp.zeros_like(db_ref)

    def tile(ref, cs, at=slice(None)):
        return _of(ref, time, at, cs).astype(F32)

    def start(cs):
        # d pre of the K - 1 entries behind the block, from the tile
        # behind it and the block's last entries (nothing lies behind
        # the sequence's end: its dy is taken for zero)
        leaves = _leaves_of(w_ref, b_ref, cs, k, time)
        behind = _first(tile(behind_ref, cs), h, time)
        tail = _last(tile(x_ref, cs, pl.ds(n_time - wide, wide)), h, time)
        pre, _ = _pre(behind, tail, *leaves, time)
        dy = _first(tile(dy_behind_ref, cs), h, time)
        return leaves, (jnp.where(closes, 0.0, dy) * _dsilu(pre),
                        *[jnp.zeros_like(behind)] * (k + bias))

    def front(cs):           # of the block: zeros before the sequence
        return jnp.where(opens, 0.0, tile(before_ref, cs))

    def chunk(t0, size, cs, leaves, after, *sums):
        w, _ = leaves
        cur = tile(x_ref, cs, pl.ds(t0, size))
        # the entries in front of the chunk: the block's own, or at the
        # block's first chunk the tile in front of the block
        if isinstance(t0, int):
            prev = tile(x_ref, cs, pl.ds(t0 - wide, wide)) if t0 \
                else front(cs)
        else:
            own = tile(x_ref, cs, pl.ds(pl.multiple_of(
                jnp.maximum(t0 - wide, 0), wide), wide))
            prev = jnp.where(t0 == 0, front(cs), own)
        pre, shifted = _pre(cur, _last(prev, h, time), *leaves, time)
        dpre = tile(dy_ref, cs, pl.ds(t0, size)) * _dsilu(pre)
        dx = _after(dpre, after, k - 1, time) * w[0]
        for j in range(1, k):
            dx = dx + _after(dpre, after, k - 1 - j, time) * w[j]
        _put(dx_ref, time, t0, size, cs, dx)
        sums = [s + _folded(dpre * v, time)
                for s, v in zip(sums, shifted + [1.0] * bias)]
        return (_first(dpre, h, time), *sums)

    def done(cs, after, *sums):
        sums = [s.sum(time, keepdims=True) for s in sums]
        at = (slice(None), cs) if time == 0 else (cs, slice(None))
        shape = (k, sums[0].shape[1]) if time == 0 else (sums[0].shape[0], k)
        dw_ref[at] += _spread(sums[:k], shape, time)
        if bias:
            db_ref[at] += sums[k]

    _walk(x_ref.shape, time, start, chunk, done, reverse=True)


def _specs(shape, tile: Tile, time_last: bool, halo: int, order,
           first: int = 0):
    """The block specs of one call: `order` turns the grid's indices
    into (batch, channel tile, time tile). `x`: a block of an array of
    `shape`'s time extent; `before` / `behind`: the tile of `halo`
    entries in front of it / behind it; each a function of the channel
    tile the array's own channels start at (`first` for the operand,
    whose convolved channels may lie inside a wider array; 0 for what
    is as wide as the taps); `leaf`: the small leaves' blocks (taps,
    bias or a gradient of them)."""
    t = shape[2 if time_last else 1]
    per, tiles = tile.time // halo, t // halo

    def at(time_index, block):
        def spec(first):
            def index(*grid):
                b, c, ti = order(*grid)
                return (b, c + first, time_index(ti)) if time_last else (
                    b, time_index(ti), c + first)
            return pl.BlockSpec((None, *block), index)
        return spec

    def small(block):
        def index(*grid):
            c = order(*grid)[1]
            return (c, 0) if time_last else (0, c)
        return lambda n: pl.BlockSpec(block(n), index)

    if time_last:
        whole, edge = (tile.channels, tile.time), (tile.channels, halo)
        leaf = small(lambda n: (tile.channels, n))
    else:
        whole, edge = (tile.time, tile.channels), (halo, tile.channels)
        leaf = small(lambda n: (n, tile.channels))
    x = at(lambda ti: ti, whole)
    before = at(lambda ti: jnp.maximum(ti * per - 1, 0), edge)
    behind = at(lambda ti: jnp.minimum((ti + 1) * per, tiles - 1), edge)
    return dict(x=x(first), before=before(first), behind=behind(first),
                y=x(0), y_behind=behind(0), leaf=leaf)


def _check(x, w, tile: Tile, time_last: bool, first: int):
    """(the convolved channels, the sequence, the operand's first
    channel in tiles) of x with the taps w, or a ValueError."""
    wide, t = (x.shape[1], x.shape[2]) if time_last else (
        x.shape[2], x.shape[1])
    c = w.shape[0]
    if (t % tile.time or c % tile.channels or first % tile.channels
            or first + c > wide or w.shape[1] - 1 > HALO[0]
            or tile.time % _halo(x.dtype, time_last)):
        raise ValueError(f"{tile} does not tile channels {first} .. "
                         f"{first + c} of x {x.shape} (time last: "
                         f"{time_last}) with taps {w.shape}")
    return c, t, first // tile.channels


def _like(x, c: int, time_last: bool):
    """x's shape with `c` channels."""
    return (x.shape[0], c, x.shape[2]) if time_last else (*x.shape[:2], c)


def _halo(dtype, time_last: bool) -> int:
    """The neighbouring tile a step reads along time: a tile of lanes,
    or the operand type's tile of sublanes."""
    return LANES if time_last else 32 // jnp.dtype(dtype).itemsize


def _leaves(w, b, time_last: bool):
    """The taps and the bias as the kernels read them, float32."""
    w = w.astype(F32)
    if time_last:
        return w, None if b is None else b.astype(F32)[:, None]
    return w.T, None if b is None else b.astype(F32)[None, :]


def _params(semantics, elements: int, k: int, size: int, passes: int):
    return dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics,
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=passes * (2 * k + 6) * elements,
            transcendentals=passes * elements,
            bytes_accessed=(passes + 1) * elements * size))


# Each pass is a jitted function of its operands: a delta-rule layer
# calls the backward one 12 times and jax traces (and lowers) it once —
# written out, 36 kernel bodies took 2 s of the cell's tracing.
_STATIC = ("tile", "time_last", "interpret", "first")


@functools.partial(jax.jit, static_argnames=_STATIC)
def forward(x, w, b, tile: Tile, time_last: bool, interpret: bool = False,
            first: int = 0):
    """The convolution of channels `first` .. `first` + C of x — [B, T,
    wide], or time last [B, wide, T] — with the taps w [C, K] and the
    bias b [C] or None -> [B, T, C] ([B, C, T]) in x's type. The
    channels are read where they lie: `first` rides the block index."""
    c, t, at = _check(x, w, tile, time_last, first)
    k, time = w.shape[1], int(time_last)
    spec = _specs(x.shape, tile, time_last, _halo(x.dtype, time_last),
                  lambda b, c, t: (b, c, t), at)
    w, b = _leaves(w, b, time_last)
    small = [w] + ([] if b is None else [b])
    shape = _like(x, c, time_last)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, k=k, time=time, bias=b is not None),
        name="causal_conv_fwd",
        grid=(x.shape[0], c // tile.channels, t // tile.time),
        in_specs=[spec["x"], spec["before"], spec["leaf"](k)]
        + ([] if b is None else [spec["leaf"](1)]),
        out_specs=spec["y"],
        out_shape=jax.ShapeDtypeStruct(shape, x.dtype),
        interpret=interpret,
        **_params(("parallel", "parallel", "parallel"), math.prod(shape), k,
                  x.dtype.itemsize, 1))(x, x, *small)


@functools.partial(jax.jit, static_argnames=_STATIC)
def backward(x, dy, w, b, tile: Tile, time_last: bool,
             interpret: bool = False, first: int = 0):
    """The cotangents of :func:`forward`'s (x's channels `first` ..
    `first` + C, w, b) under dy: (dx in dy's shape and x's type, dw [C,
    K] float32, db [C] float32 or None)."""
    c, t, at = _check(x, w, tile, time_last, first)
    k, time = w.shape[1], int(time_last)
    spec = _specs(x.shape, tile, time_last, _halo(x.dtype, time_last),
                  lambda c, b, t: (b, c, t), at)
    w, b = _leaves(w, b, time_last)
    bias = b is not None
    small = [w] + ([b] if bias else [])
    like = lambda a: jax.ShapeDtypeStruct(a.shape, F32)  # noqa: E731
    dx, dw, *db = pl.pallas_call(
        functools.partial(_bwd_kernel, k=k, time=time, bias=bias),
        name="causal_conv_bwd",
        grid=(c // tile.channels, x.shape[0], t // tile.time),
        in_specs=[spec["x"], spec["before"], spec["behind"], spec["y"],
                  spec["y_behind"], spec["leaf"](k)]
        + ([spec["leaf"](1)] if bias else []),
        out_specs=[spec["y"], spec["leaf"](k)]
        + ([spec["leaf"](1)] if bias else []),
        out_shape=[jax.ShapeDtypeStruct(dy.shape, x.dtype), like(w)]
        + ([like(b)] if bias else []),
        interpret=interpret,
        **_params(("parallel", "arbitrary", "arbitrary"), dy.size, k,
                  x.dtype.itemsize, 2))(x, x, x, dy, dy, *small)
    if time_last:
        return dx, dw, db[0][:, 0] if bias else None
    return dx, dw.T, db[0][0] if bias else None


@functools.lru_cache(maxsize=None)
def _conv(tile: Tile, time_last: bool, bias: bool, interpret: bool,
          first: int = 0):
    """:func:`forward` behind a ``custom_vjp`` whose residuals are its
    operands and whose backward pass is :func:`backward` (the operand's
    cotangent zero outside the convolved channels)."""
    on = dict(tile=tile, time_last=time_last, interpret=interpret,
              first=first)

    def run(x, w, *b):
        return forward(x, w, b[0] if bias else None, **on)

    def bwd(res, dy):
        x, w, *b = res
        dx, dw, db = backward(x, dy, w, b[0] if bias else None, **on)
        if dx.shape != x.shape:
            axis = 1 if time_last else 2
            pads = [(0, 0)] * 3
            pads[axis] = (first, x.shape[axis] - first - dx.shape[axis])
            dx = jnp.pad(dx, pads)
        return (dx, dw.astype(w.dtype)) + (
            (db.astype(b[0].dtype),) if bias else ())

    conv = jax.custom_vjp(run)
    conv.defvjp(lambda *a: (run(*a), a), bwd)
    return conv


def conv(x, w, b, tile: Tile, time_last: bool = False,
         interpret: bool = False, first: int = 0):
    """``ops/ssm.causal_conv`` on the kernels: of x [B, T, wide] the
    channels `first` .. `first` + C, w [C, K], b [C] or None -> [B, T,
    C], or under `time_last` [B, C, T], the layout the result is read
    in (x is then read through its transposed view: the layout XLA
    gives a product whose reader wants time in the lanes). `tile`:
    ``ssm.conv_tile``'s."""
    if time_last:
        x = jnp.swapaxes(x, 1, 2)
    return _conv(Tile(*tile), time_last, b is not None, interpret, first)(
        x, w, *(() if b is None else (b,)))
