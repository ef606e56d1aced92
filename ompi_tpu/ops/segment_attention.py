"""The Pallas TPU kernels of two-way SEGMENT attention: softmax
self-attention of ONE packed row in which a position sees the positions
of its own segment id and no others, both ways, the same mask for every
head (a vision tower's images back to back; ``ops/attention.py`` owns
the rule, ``segment_tile``, that says when they run, and ``att.mha`` with
``segments=`` is their oracle).

Two kernels, blockwise over (query tile, key tile) pairs with a pair's
scores alive in VMEM only — float32 scores, statistics and
accumulators, the operands' type (bfloat16) on the MXU:

* :func:`forward` (``seg_fwd``): the online softmax. Grid (head group,
  pair), the pairs of a query tile one after the other; per head a
  running maximum, sum and numerator; at a query tile's last pair ``o``
  and the per-row log-sum-exp are written.
* :func:`backward` (``seg_bwd``): dq, dk, dv in one kernel. Grid (head
  group, pair), the pairs of a KEY tile one after the other; each
  pair's probabilities are made again from ``lse``; dk and dv accumulate
  over a key tile's pairs, dq over the whole group in a ``[heads of the
  group, T, D]`` float32 scratch (what bounds the group), written at
  the group's last step. dS is rounded to the operands' type before its
  two products, as the library's kernels and XLA's default precision do.

**The pairs are data.** Which tiles hold a pair of one segment depends
on the ids, an input of the step: :func:`pair_table` orders the VISITED
pairs first (``visit``: ``ops.attention.segment_tiles``) and the
kernels read (query tile, key tile, flags) per grid step from SMEM. The
grid's pair axis has the static length of ALL pairs; a step past the
visited ones repeats the last visited pair's blocks (nothing is
fetched) and does nothing. Another packing of the same length runs the
same executable.

**Ids are compared only where a tile straddles a segment's edge.** A
pair whose two tiles lie inside one segment (``interior``) takes the
plain softmax; every other visited pair compares the ids pair by pair
(a large negative FINITE score where they differ: a row whose first
tiles are all masked stays finite, and its first real key wipes what
they added). Exact for any ids: `visit` may be a superset.

**Orientation and layout.** `forward` works ``S = Q K^T`` ([rows,
keys]: P feeds the PV product as it lies), `backward` ``S^T = K Q^T``
([keys, rows]: the per-row ``lse`` and ``di`` broadcast over sublanes as
``[1, rows]`` rows, which is how `forward` writes ``lse``). Operands
are ``[H, T, width]``, any width: a head narrower than the 128 lanes is
padded by the layout in VMEM, not by a copy in HBM, and ``o`` is
written at its own width. The ids come as ONE ``[1, T]`` int32 row; the
column an edge pair needs is made from it in the kernel. Operands
``[H, width, T]`` — the sequence in the lanes, as XLA lays the tower's
other arrays out, so that no transposition stands around the kernels —
were built and measured (PERF.md section 6, PR 38): the kernels run the
same, the copies under `attn_core` fall by a third, and XLA's
re-laid-out RoPE around them costs more than that.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ompi_tpu.ops.sparse_attention import (LANES, MASKED, _NT, _check,
                                           _params, _wide)

#: a grid step's flags: the first / last visited pair of its query tile
#: (`forward`) or key tile (`backward`), a pair whose ids are compared,
#: a pair that is visited at all
FIRST, LAST, EDGE, VISITED = 1, 2, 4, 8

#: keys (`forward`) or rows (`backward`) of a pair scored in one piece
_PIECE = 512


class Tiles(NamedTuple):
    """Rows and keys of a pair, and the heads worked per grid step by
    the forward and by the backward (whose group keeps its dq in
    VMEM)."""
    rows: int
    keys: int
    heads: int
    heads_bwd: int


def pair_table(visit, interior, by_key: bool = False):
    """(query tile [P], key tile [P], flags [P]) int32, P the number of
    ALL pairs, from `visit` and `interior`, bool [query tiles, key
    tiles] (data): the visited pairs first, a query tile's after one
    another in key order (`by_key`: a key tile's, in row order), each
    with VISITED, FIRST / LAST of its tile's run and EDGE where it is
    not interior; every later entry is the last visited pair with no
    flag."""
    if by_key:
        key_of, row_of, flags = pair_table(visit.T, interior.T)
        return row_of, key_of, flags
    minor = visit.shape[1]
    seen, plain = visit.reshape(-1), interior.reshape(-1)
    slot = jnp.arange(seen.size, dtype=jnp.int32)
    count = seen.sum(dtype=jnp.int32)
    # the visited pair of rank p, without a sort or a gather
    hit = seen[None, :] & ((jnp.cumsum(seen, dtype=jnp.int32) - 1)[None, :]
                           == slot[:, None])
    visited = slot < count
    flat = jnp.where(visited, jnp.where(hit, slot[None, :], 0).sum(1),
                     jnp.where(seen, slot, 0).max())
    major = flat // minor
    first = (slot == 0) | (major != jnp.roll(major, 1))
    last = (slot == count - 1) | (major != jnp.roll(major, -1))
    edge = (hit & ~plain[None, :]).any(1)
    flags = visited * (VISITED + FIRST * first + LAST * last + EDGE * edge)
    return major, flat % minor, flags.astype(jnp.int32)


def _column(row):
    """A [1, n] row of 32-bit values as [n, LANES], each lane of a row
    holding the row's value."""
    return jnp.broadcast_to(row, (LANES, row.shape[1])).T


def _lanes(stat, width: int):
    """A lane-broadcast [n, LANES] statistic as [n, width], any
    width."""
    return stat[:, :width] if width < LANES else _wide(stat, width)


# -- forward --------------------------------------------------------------------

def _fwd_kernel(row_of, key_of, flags, q, k, v, id_q, id_k, o, lse, m_s, l_s,
                acc_s, *, heads: int, piece: int):
    flag = flags[pl.program_id(1)]
    keys, width = k.shape[1], acc_s.shape[-1]

    @pl.when((flag & FIRST) != 0)
    def _():
        m_s[...] = jnp.full_like(m_s, MASKED)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    def visit(edge: bool):
        mine = _column(id_q[...]) if edge else None

        def head(h, _):
            qh = q[h]
            for at in range(0, keys, piece):
                part = pl.ds(at, piece)
                s = lax.dot_general(qh, k[h, part, :], _NT,
                                    preferred_element_type=jnp.float32)
                if edge:
                    s = jnp.where(_wide(mine, piece) == id_k[:, part], s,
                                  MASKED)
                m_prev = m_s[h]
                m_next = jnp.maximum(m_prev, s.max(axis=-1)[:, None])
                p = jnp.exp(s - _wide(m_next, piece))
                alpha = jnp.exp(m_prev - m_next)
                l_s[h] = alpha * l_s[h] + p.sum(axis=-1)[:, None]
                m_s[h] = m_next
                acc_s[h] = _lanes(alpha, width) * acc_s[h] + jnp.dot(
                    p.astype(v.dtype), v[h, part, :],
                    preferred_element_type=jnp.float32)

        lax.fori_loop(0, heads, head, None)

    pl.when((flag & (VISITED | EDGE)) == VISITED)(lambda: visit(False))
    pl.when((flag & EDGE) != 0)(lambda: visit(True))

    @pl.when((flag & LAST) != 0)
    def _():
        def head(h, _):
            l = l_s[h]
            o[h] = (acc_s[h] * _lanes(1.0 / l, width)).astype(o.dtype)
            lse[h] = (m_s[h] + jnp.log(l)).T[:1]

        lax.fori_loop(0, heads, head, None)


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def forward(q, k, v, ids, table, tiles: Tiles, interpret: bool = False):
    """q, k: [H, T, D] (q already scaled); v: [H, T, Dv]; ids: [T]
    int32; table: `pair_table`'s for these tiles. Returns (o [H, T, Dv]
    in q's type, lse [H, 1, T] float32)."""
    h, t, d = q.shape
    dv = v.shape[-1]
    rows, keys, heads = tiles.rows, tiles.keys, tiles.heads
    _check("seg_fwd", t, h, tiles, heads)
    pairs = (t // rows) * (t // keys)
    size = q.dtype.itemsize
    by_row = lambda g, n, r, c, f: (g, r[n], 0)  # noqa: E731
    by_key = lambda g, n, r, c, f: (g, c[n], 0)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads,
                          piece=min(keys, _PIECE)),
        name="seg_fwd",
        out_shape=(jax.ShapeDtypeStruct((h, t, dv), q.dtype),
                   jax.ShapeDtypeStruct((h, 1, t), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(h // heads, pairs),
            in_specs=[
                pl.BlockSpec((heads, rows, d), by_row),
                pl.BlockSpec((heads, keys, d), by_key),
                pl.BlockSpec((heads, keys, dv), by_key),
                pl.BlockSpec((1, rows), lambda g, n, r, c, f: (0, r[n])),
                pl.BlockSpec((1, keys), lambda g, n, r, c, f: (0, c[n]))],
            out_specs=(
                pl.BlockSpec((heads, rows, dv), by_row),
                pl.BlockSpec((heads, 1, rows), lambda g, n, r, c, f:
                             (g, 0, r[n]))),
            scratch_shapes=[pltpu.VMEM((heads, rows, LANES), jnp.float32),
                            pltpu.VMEM((heads, rows, LANES), jnp.float32),
                            pltpu.VMEM((heads, rows, dv), jnp.float32)]),
        interpret=interpret,
        # what a row of ONE segment would cost: the most there can be
        **_params(flops=2 * h * t * t * (d + dv), exps=h * t * t,
                  nbytes=h * t * (t // rows + 1) * (d + dv) * size),
    )(*table, q, k, v, ids[None], ids[None])


# -- backward --------------------------------------------------------------------

def _bwd_kernel(row_of, key_of, flags, q, k, v, do, lse, di, id_q, id_k, dq,
                dk, dv, dq_s, dk_s, dv_s, *, heads: int, piece: int):
    n = pl.program_id(1)
    flag = flags[n]
    rows = q.shape[1]

    @pl.when(n == 0)
    def _():
        dq_s[...] = jnp.zeros_like(dq_s)

    @pl.when((flag & FIRST) != 0)
    def _():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    first_row = row_of[n] * rows

    def visit(edge: bool):
        mine = _column(id_k[...]) if edge else None

        def head(h, _):
            kh, vh = k[h], v[h]
            for at in range(0, rows, piece):
                part = pl.ds(at, piece)
                qh, doh = q[h, part, :], do[h, part, :]
                s_t = lax.dot_general(kh, qh, _NT,
                                      preferred_element_type=jnp.float32)
                if edge:
                    s_t = jnp.where(_wide(mine, piece) == id_q[:, part], s_t,
                                    MASKED)
                p_t = jnp.exp(s_t - lse[h, :, part])
                dv_s[h] += jnp.dot(p_t.astype(doh.dtype), doh,
                                   preferred_element_type=jnp.float32)
                dp_t = lax.dot_general(vh, doh, _NT,
                                       preferred_element_type=jnp.float32)
                ds_t = (dp_t - di[h, :, part]) * p_t
                dk_s[h] += jnp.dot(ds_t.astype(qh.dtype), qh,
                                   preferred_element_type=jnp.float32)
                mine_q = pl.ds(pl.multiple_of(first_row + at, piece), piece)
                dq_s[h, mine_q, :] += jnp.dot(
                    ds_t.T.astype(kh.dtype), kh,
                    preferred_element_type=jnp.float32)

        lax.fori_loop(0, heads, head, None)

    pl.when((flag & (VISITED | EDGE)) == VISITED)(lambda: visit(False))
    pl.when((flag & EDGE) != 0)(lambda: visit(True))

    @pl.when((flag & LAST) != 0)
    def _():
        dk[...] = dk_s[...].astype(dk.dtype)
        dv[...] = dv_s[...].astype(dv.dtype)

    @pl.when(n == pl.num_programs(1) - 1)
    def _():
        dq[...] = dq_s[...].astype(dq.dtype)


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def backward(q, k, v, do, lse, di, ids, table, tiles: Tiles,
             interpret: bool = False):
    """(dq, dk, dv) of `forward`'s `o` for the cotangent `do` [H, T,
    Dv]; lse, di ``= rowsum(do * o)``: [H, 1, T] float32; table:
    `pair_table`'s with `by_key`."""
    h, t, d = q.shape
    dvw = v.shape[-1]
    rows, keys, heads = tiles.rows, tiles.keys, tiles.heads_bwd
    _check("seg_bwd", t, h, tiles, heads)
    pairs = (t // rows) * (t // keys)
    size = q.dtype.itemsize
    by_row = lambda g, n, r, c, f: (g, r[n], 0)  # noqa: E731
    by_key = lambda g, n, r, c, f: (g, c[n], 0)  # noqa: E731
    stat = pl.BlockSpec((heads, 1, rows), lambda g, n, r, c, f:
                        (g, 0, r[n]))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads,
                          piece=min(rows, _PIECE)),
        name="seg_bwd",
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(h // heads, pairs),
            in_specs=[
                pl.BlockSpec((heads, rows, d), by_row),
                pl.BlockSpec((heads, keys, d), by_key),
                pl.BlockSpec((heads, keys, dvw), by_key),
                pl.BlockSpec((heads, rows, dvw), by_row),
                stat, stat,
                pl.BlockSpec((1, rows), lambda g, n, r, c, f: (0, r[n])),
                pl.BlockSpec((1, keys), lambda g, n, r, c, f: (0, c[n]))],
            out_specs=(
                pl.BlockSpec((heads, t, d), lambda g, n, r, c, f:
                             (g, 0, 0)),
                pl.BlockSpec((heads, keys, d), by_key),
                pl.BlockSpec((heads, keys, dvw), by_key)),
            scratch_shapes=[pltpu.VMEM((heads, t, d), jnp.float32),
                            pltpu.VMEM((heads, keys, d), jnp.float32),
                            pltpu.VMEM((heads, keys, dvw), jnp.float32)]),
        interpret=interpret,
        **_params(flops=2 * h * t * t * (3 * d + 2 * dvw), exps=h * t * t,
                  nbytes=(h * t * (t // keys + 2) * (d + dvw) * size
                          + 2 * h * t * (d + dvw) * size)),
    )(*table, q, k, v, do, lse, di, ids[None], ids[None])
