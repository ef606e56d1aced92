"""The Pallas TPU kernels of learned sparse attention: softmax attention
of ONE sequence under a mask that is data and the same for every head
(``ops/attention.py::dsa_attend`` is the one way in and owns the rule,
``dsa_tile``, that says when they run; its plain path is their oracle).

Three kernels, all blockwise over (row block, key block) pairs with the
scores of a pair alive in VMEM only — float32 scores, statistics and
accumulators, bfloat16 (the operands' type) on the MXU:

* :func:`forward` (``dsa_fwd``): the online softmax. Grid (head group,
  pair), the pairs of a row block in key order; per head a running
  maximum, sum and numerator; at a row block's last pair ``o`` and the
  per-row log-sum-exp are written.
* :func:`head_sum` (``dsa_head_sum``): the heads' probabilities summed,
  ``sum_h exp(s_h - lse_h)`` — it needs the FINISHED ``lse``, so it is a
  second pass over QK^T alone. Grid (pair, head group) with the heads
  innermost and the ``[rows, keys]`` float32 sum resident, written once.
  It reads no mask: the caller's select keeps the kept pairs.
* :func:`backward` (``dsa_bwd``): dq, dk, dv in one kernel. Grid (head
  group, pair), the pairs of a KEY block in row order; each pair's
  probabilities are made again from ``lse``; dk and dv accumulate over a
  key block's pairs, dq over the whole group in a ``[heads of the group,
  T, D]`` float32 scratch (what bounds the group), written at the
  group's last pair. dS is rounded to the operands' type before its two
  products, as XLA's default precision does to a float32 cotangent.

**The pairs.** `keep` already holds the causal mask, so the pairs above
the diagonal are never visited: the grid's pair axis runs over a static
table of the pairs with ``first key <= last row`` (row block, key block,
in SMEM). Inside a visited pair the mask tile decides; it is read ONCE
per (group, pair) as int8, turned into a float32 bias (0 or a large
negative FINITE value: a row whose first blocks are all masked stays
finite, and the first real key wipes what they added) and used for every
head of the group.

**Orientation.** `forward` works ``S = Q K^T`` ([rows, keys]: P feeds
the PV product as it lies). `head_sum` and `backward` work ``S^T = K
Q^T`` ([keys, rows]) — the per-row ``lse`` and ``di`` then broadcast
over sublanes as ``[1, rows]`` rows, no per-tile transposition of the
statistics — and `backward` reads the mask transposed.

**Layout.** Operands are ``[H, T, width]``: the caller pays one layout
change each way. Reading the model's ``[T, H, width]`` as ``[T, H *
width]`` blocks whose heads are runs of lanes was tried (PERF.md
section 6, PR 31): the kernels run the same, but on the TPU's tiled
layouts that reshape is itself a copy as dear as the transposition.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ompi_tpu.ops.grouped_matmul import VMEM_LIMIT_BYTES

#: the score of a masked pair: large, negative and finite
MASKED = -0.7 * float(np.finfo(np.float32).max)
LANES = 128

_NT = (((1,), (1,)), ((), ()))  # A B^T


class Tiles(NamedTuple):
    """Rows and keys of a pair, and the heads worked per grid step:
    by the forward and the head sum, and by the backward (whose group
    keeps its dq in VMEM)."""
    rows: int
    keys: int
    heads: int
    heads_bwd: int


@functools.lru_cache(maxsize=None)
def _pairs(t: int, rows: int, keys: int, by_key: bool):
    """(row block [P], key block [P]) int32: the pairs with a key at or
    under a row, in row-major order, or key-major with `by_key`."""
    pairs = [(i, j) for i in range(t // rows) for j in range(t // keys)
             if j * keys <= i * rows + rows - 1]
    if by_key:
        pairs.sort(key=lambda p: (p[1], p[0]))
    return (np.array([p[0] for p in pairs], np.int32),
            np.array([p[1] for p in pairs], np.int32))


def _bias(keep_ref):
    """The mask tile (int8, 0 or 1) as what is added to a score: 0.0
    where kept, MASKED where not (arithmetic: Mosaic takes no select
    between two scalars)."""
    kept = keep_ref[...].astype(jnp.int32).astype(jnp.float32)
    return (1.0 - kept) * MASKED


def _wide(stat, width: int):
    """A lane-broadcast [rows, LANES] statistic as [rows, width]."""
    return stat if width == LANES else jnp.tile(stat, (1, width // LANES))


def _params(flops: int, exps: int, nbytes: int):
    return dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(flops=flops, transcendentals=exps,
                                      bytes_accessed=nbytes))


def _check(what: str, t: int, h: int, tiles: Tiles, heads: int):
    if t % tiles.rows or t % tiles.keys or h % heads:
        raise ValueError(f"{what}: tiles {tiles} do not divide T {t}, "
                         f"heads {h}")


# -- forward --------------------------------------------------------------------

def _fwd_kernel(row_of, key_of, q, k, v, keep, o, lse, m_s, l_s, acc_s, *,
                heads: int, rows: int, keys: int):
    n = pl.program_id(1)
    i, j = row_of[n], key_of[n]

    @pl.when(j == 0)
    def _():
        m_s[...] = jnp.full_like(m_s, MASKED)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    bias = _bias(keep)

    def head(h, _):
        s = lax.dot_general(q[h], k[h], _NT,
                            preferred_element_type=jnp.float32) + bias
        m_prev = m_s[h]
        m_next = jnp.maximum(m_prev, s.max(axis=-1)[:, None])
        p = jnp.exp(s - _wide(m_next, keys))
        alpha = jnp.exp(m_prev - m_next)
        l_s[h] = alpha * l_s[h] + p.sum(axis=-1)[:, None]
        m_s[h] = m_next
        acc_s[h] = _wide(alpha, acc_s.shape[-1]) * acc_s[h] + jnp.dot(
            p.astype(v.dtype), v[h], preferred_element_type=jnp.float32)

    lax.fori_loop(0, heads, head, None)

    @pl.when(j == (i * rows + rows - 1) // keys)
    def _():
        def head(h, _):
            l = l_s[h]
            o[h] = (acc_s[h] * _wide(1.0 / l, acc_s.shape[-1])).astype(
                o.dtype)
            lse[h] = (m_s[h] + jnp.log(l)).T[:1]

        lax.fori_loop(0, heads, head, None)


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def forward(q, k, v, keep, tiles: Tiles, interpret: bool = False):
    """q, k: [H, T, D] (q already scaled); v: [H, T, Dv]; keep: [T, T]
    int8, nonzero where row t sees key s (causal included). Returns
    (o [H, T, Dv] in q's type, lse [H, 1, T] float32)."""
    h, t, d = q.shape
    dv = v.shape[-1]
    rows, keys, heads = tiles.rows, tiles.keys, tiles.heads
    _check("dsa_fwd", t, h, tiles, heads)
    row_of, key_of = _pairs(t, rows, keys, False)
    size = q.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads, rows=rows, keys=keys),
        name="dsa_fwd",
        out_shape=(jax.ShapeDtypeStruct((h, t, dv), q.dtype),
                   jax.ShapeDtypeStruct((h, 1, t), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(h // heads, len(row_of)),
            in_specs=[
                pl.BlockSpec((heads, rows, d), lambda g, n, r, c:
                             (g, r[n], 0)),
                pl.BlockSpec((heads, keys, d), lambda g, n, r, c:
                             (g, c[n], 0)),
                pl.BlockSpec((heads, keys, dv), lambda g, n, r, c:
                             (g, c[n], 0)),
                pl.BlockSpec((rows, keys), lambda g, n, r, c:
                             (r[n], c[n]))],
            out_specs=(
                pl.BlockSpec((heads, rows, dv), lambda g, n, r, c:
                             (g, r[n], 0)),
                pl.BlockSpec((heads, 1, rows), lambda g, n, r, c:
                             (g, 0, r[n]))),
            scratch_shapes=[pltpu.VMEM((heads, rows, LANES), jnp.float32),
                            pltpu.VMEM((heads, rows, LANES), jnp.float32),
                            pltpu.VMEM((heads, rows, dv), jnp.float32)]),
        interpret=interpret,
        **_params(flops=2 * h * len(row_of) * rows * keys * (d + dv),
                  exps=h * len(row_of) * rows * keys,
                  nbytes=(h * len(row_of) * keys * (d + dv) * size
                          + h * t * (d + dv) * size
                          + h // heads * len(row_of) * rows * keys)),
    )(row_of, key_of, q, k, v, keep)


# -- the heads' summed probabilities -------------------------------------------------

def _head_sum_kernel(row_of, key_of, q, k, lse, out, acc, *, heads: int):
    g = pl.program_id(1)

    @pl.when(g == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    def head(h, total):
        s_t = lax.dot_general(k[h], q[h], _NT,
                              preferred_element_type=jnp.float32)
        return total + jnp.exp(s_t - lse[h])

    acc[...] += lax.fori_loop(0, heads, head, jnp.zeros_like(acc))

    @pl.when(g == pl.num_programs(1) - 1)
    def _():
        out[...] = acc[...].T


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def head_sum(q, k, lse, tiles: Tiles, interpret: bool = False):
    """``sum_h exp(q_h . k_h - lse_h)`` over the visited pairs: q, k
    [H, T, D], lse [H, 1, T] (`forward`'s). Returns [T, T] float32. NO
    mask is read: where a pair is kept this is the heads' summed
    softmax, where it is not it is whatever the scores give (infinity
    included), and the pairs above the diagonal are not written — the
    caller keeps the kept pairs by a select."""
    h, t, d = q.shape
    rows, keys, heads = tiles.rows, tiles.keys, tiles.heads
    _check("dsa_head_sum", t, h, tiles, heads)
    row_of, key_of = _pairs(t, rows, keys, False)
    size = q.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_head_sum_kernel, heads=heads),
        name="dsa_head_sum",
        out_shape=jax.ShapeDtypeStruct((t, t), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(len(row_of), h // heads),
            in_specs=[
                pl.BlockSpec((heads, rows, d), lambda n, g, r, c:
                             (g, r[n], 0)),
                pl.BlockSpec((heads, keys, d), lambda n, g, r, c:
                             (g, c[n], 0)),
                pl.BlockSpec((heads, 1, rows), lambda n, g, r, c:
                             (g, 0, r[n]))],
            out_specs=pl.BlockSpec((rows, keys), lambda n, g, r, c:
                                   (r[n], c[n])),
            scratch_shapes=[pltpu.VMEM((keys, rows), jnp.float32)]),
        interpret=interpret,
        **_params(flops=2 * h * len(row_of) * rows * keys * d,
                  exps=h * len(row_of) * rows * keys,
                  nbytes=(h * len(row_of) * (rows + keys) * d * size
                          + len(row_of) * rows * keys * 4)),
    )(row_of, key_of, q, k, lse)


# -- backward --------------------------------------------------------------------

def _bwd_kernel(row_of, key_of, q, k, v, do, lse, di, keep_t, dq, dk, dv,
                dq_s, dk_s, dv_s, *, heads: int, rows: int, keys: int):
    n = pl.program_id(1)
    i, j = row_of[n], key_of[n]

    @pl.when(n == 0)
    def _():
        dq_s[...] = jnp.zeros_like(dq_s)

    @pl.when(i == (j * keys) // rows)
    def _():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    bias = _bias(keep_t)
    mine = pl.ds(pl.multiple_of(i * rows, rows), rows)

    def head(h, _):
        qh, kh, doh = q[h], k[h], do[h]
        s_t = lax.dot_general(kh, qh, _NT,
                              preferred_element_type=jnp.float32)
        p_t = jnp.exp(s_t + bias - lse[h])
        dv_s[h] += jnp.dot(p_t.astype(doh.dtype), doh,
                           preferred_element_type=jnp.float32)
        dp_t = lax.dot_general(v[h], doh, _NT,
                               preferred_element_type=jnp.float32)
        ds_t = (dp_t - di[h]) * p_t
        dk_s[h] += jnp.dot(ds_t.astype(qh.dtype), qh,
                           preferred_element_type=jnp.float32)
        dq_s[h, mine, :] += jnp.dot(ds_t.T.astype(kh.dtype), kh,
                                    preferred_element_type=jnp.float32)

    lax.fori_loop(0, heads, head, None)

    @pl.when(i == dq.shape[1] // rows - 1)
    def _():
        dk[...] = dk_s[...].astype(dk.dtype)
        dv[...] = dv_s[...].astype(dv.dtype)

    @pl.when(n == pl.num_programs(1) - 1)
    def _():
        dq[...] = dq_s[...].astype(dq.dtype)


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def backward(q, k, v, do, lse, di, keep_t, tiles: Tiles,
             interpret: bool = False):
    """(dq, dk, dv) of `forward`'s `o` for the cotangent `do` [H, T,
    Dv]; lse, di ``= rowsum(do * o)``: [H, 1, T] float32; keep_t: the
    mask transposed, int8."""
    h, t, d = q.shape
    dvw = v.shape[-1]
    rows, keys, heads = tiles.rows, tiles.keys, tiles.heads_bwd
    _check("dsa_bwd", t, h, tiles, heads)
    row_of, key_of = _pairs(t, rows, keys, True)
    size = q.dtype.itemsize
    by_row = lambda g, n, r, c: (g, r[n], 0)  # noqa: E731
    by_key = lambda g, n, r, c: (g, c[n], 0)  # noqa: E731
    stat = pl.BlockSpec((heads, 1, rows), lambda g, n, r, c: (g, 0, r[n]))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads, rows=rows, keys=keys),
        name="dsa_bwd",
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(h // heads, len(row_of)),
            in_specs=[
                pl.BlockSpec((heads, rows, d), by_row),
                pl.BlockSpec((heads, keys, d), by_key),
                pl.BlockSpec((heads, keys, dvw), by_key),
                pl.BlockSpec((heads, rows, dvw), by_row),
                stat, stat,
                pl.BlockSpec((keys, rows), lambda g, n, r, c:
                             (c[n], r[n]))],
            out_specs=(
                pl.BlockSpec((heads, t, d), lambda g, n, r, c: (g, 0, 0)),
                pl.BlockSpec((heads, keys, d), by_key),
                pl.BlockSpec((heads, keys, dvw), by_key)),
            scratch_shapes=[pltpu.VMEM((heads, t, d), jnp.float32),
                            pltpu.VMEM((heads, keys, d), jnp.float32),
                            pltpu.VMEM((heads, keys, dvw), jnp.float32)]),
        interpret=interpret,
        **_params(flops=2 * h * len(row_of) * rows * keys * (3 * d + 2 * dvw),
                  exps=h * len(row_of) * rows * keys,
                  nbytes=(h * len(row_of) * rows * (d + dvw) * size
                          + 2 * h * t * (2 * d + dvw) * size
                          + h // heads * len(row_of) * rows * keys)),
    )(row_of, key_of, q, k, v, do, lse, di, keep_t)
