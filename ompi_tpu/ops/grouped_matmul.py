"""The Pallas TPU kernels of the drop-free expert path: a grouped
matmul over ragged, contiguous groups of rows (modelled on jax's
``pallas.ops.tpu.megablox``; ``ops/moe.py::grouped_matmul`` is the one
way in and owns the rule that says when they run).

:func:`gmm` is ``lax.ragged_dot``: rows ``[M, K]``, one ``[K, N]``
matrix a group, groups given by their sizes; with ``transpose_rhs`` the
matrices are read as ``[N, K]`` (the product with respect to the rows,
no transposed copy of the weights). :func:`tgmm` is its transpose with
respect to the matrices: ``rows_g^T @ cols_g`` for every group, both
operands read as they lie (no transposed copy of the rows), zeros for
a group without rows. Operands in their own type, float32
accumulation, the result in the type asked for.

**How the groups meet the tiles.** The rows are cut into tiles of
``tm``; a VISIT is one (group, tile) pair whose rows intersect, in row
order, so a tile that holds a group edge is visited once by each group
in it: at most ``M / tm + G - 1`` visits. Three small int32 arrays
(group offsets, and per visit the group and the tile) ride in SMEM and
drive the block index maps; the grid's visit axis has the traced
number of visits as its bound. A tile wholly inside its group is one
product over ``tm`` rows. A tile at an edge is worked in blocks of
``sub`` rows, only those the group reaches into, and of those only the
group's rows are kept (``gmm``) or count (``tgmm``): large tiles for
the MXU and the DMA, small ones for what an edge wastes. Rows past the
last group (``sum(sizes) < M``) come out zero and count for no group,
as with ``lax.ragged_dot``.

**Blocks.** ``gmm`` holds the whole of K: a ``[tm, K]`` tile of rows
against a ``[K, tn]`` block of the group's matrix, whose index does
not change between consecutive visits of one group, so Pallas does not
fetch it again — a group's matrix crosses HBM once, however many tiles
its rows fill. ``tgmm`` sums a ``[tk, tn]`` block of a group's result
in float32 over the group's visits.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: what a kernel may ask of VMEM (v5e: 128 MiB a core; the compiler's
#: default of 16 does not hold one [2048, 1024] block twice)
VMEM_LIMIT_BYTES = 96 * 1024 * 1024
#: Columns of a whole tile's product that are computed in one piece. A
#: kernel's code is NOT shared between its calls in the step's
#: executable (36 a step in olmoe-train-t4096), and one unrolled
#: [512, 2048] x [2048, 1024] product is 1.2 MB of it, which a run pays
#: when it loads the executable; in runs of 512 columns the products
#: take 1% longer and the kernels a third less room (PERF.md 6, PR 29).
RUN = 512


@functools.partial(jax.jit, static_argnames=("m", "tm", "visit_empty"))
def visits(sizes, m: int, tm: int, visit_empty: bool):
    """The (group, tile) pairs in row order. sizes: [E] int32 rows a
    group. Returns (offsets [E + 2], group [V], tile [V], count []):
    group E is the tail, the rows past the last group; `visit_empty`
    gives a group without rows one visit (so that `tgmm` writes its
    zeros) and the tail none."""
    e = sizes.shape[0]
    tiles_m = m // tm
    sizes = sizes.astype(jnp.int32)
    sizes = jnp.concatenate([sizes, (m - sizes.sum())[None]])
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    n = jnp.where(sizes > 0, (ends - 1) // tm - starts // tm + 1,
                  int(visit_empty))
    if visit_empty:
        n = n.at[e].set(0)
    upto = jnp.cumsum(n)
    v = jnp.arange(tiles_m + e, dtype=jnp.int32)
    group = jnp.minimum((upto[None, :] <= v[:, None]).sum(1), e)
    tile = (starts // tm)[group] + v - (upto - n)[group]
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return (offsets.astype(jnp.int32), group.astype(jnp.int32),
            jnp.clip(tile, 0, tiles_m - 1).astype(jnp.int32), upto[-1])


def _edges(offsets, group, tile, v, tm: int):
    """(first row of visit v's group, the row past its last, the
    tile's first row, is the tile wholly the group's)."""
    g = group[v]
    start, end, row0 = offsets[g], offsets[g + 1], tile[v] * tm
    return start, end, row0, (start <= row0) & (row0 + tm <= end)


def _mine(start, end, row0, shape):
    """[rows, width] mask: which rows from row0 on are the group's."""
    rows = row0 + lax.broadcasted_iota(jnp.int32, shape, 0)
    return (rows >= start) & (rows < end)


def _runs(width: int, run: int, body) -> None:
    """body(slice) over `width` columns in runs of `run`: a loop, not
    one unrolled product (see RUN)."""
    if run >= width:
        body(slice(None))
        return
    # the widest run of whole lanes that divides the width
    run = next(d for d in range(run, 0, -128) if width % d == 0)

    def step(j, _):
        body(pl.ds(pl.multiple_of(j * run, run), run))

    lax.fori_loop(0, width // run, step, None)


def _edge_blocks(tm: int, sub: int, start, end, row0, body) -> None:
    """body(rows of the block, its first row) for each block of `sub`
    rows of the tile that the group [start, end) reaches into."""
    def step(s, _):
        first = row0 + s * sub

        @pl.when((first < end) & (first + sub > start))
        def _():
            body(pl.ds(pl.multiple_of(s * sub, sub), sub), first)

    lax.fori_loop(0, tm // sub, step, None)


def _gmm_kernel(offsets, group, tile, lhs, rhs, out, *, tm: int, sub: int,
                run: int, n_groups: int, transpose_rhs: bool):
    v = pl.program_id(1)
    start, end, row0, whole = _edges(offsets, group, tile, v, tm)
    real = group[v] < n_groups
    whole &= real
    dims = (((1,), (1 if transpose_rhs else 0,)), ((), ()))

    def product(rows, cols):
        return lax.dot_general(
            lhs[rows, :], rhs[cols, :] if transpose_rhs else rhs[:, cols],
            dims, preferred_element_type=jnp.float32)

    @pl.when(whole)
    def _():
        def columns(cols):
            out[:, cols] = product(slice(None), cols).astype(out.dtype)
        _runs(out.shape[1], run, columns)

    # a tile with a group edge in it: only the blocks of `sub` rows the
    # group reaches into, and of those only the group's rows — every
    # row is written by the one visit of its own group, so what the
    # other rows hold meanwhile is nobody's
    @pl.when(jnp.logical_not(whole) & real)
    def _():
        def block(rows, first):
            val = product(rows, slice(None))
            out[rows, :] = jnp.where(
                _mine(start, end, first, val.shape), val,
                out[rows, :].astype(jnp.float32)).astype(out.dtype)
        _edge_blocks(tm, sub, start, end, row0, block)

    # the tail (the rows past the last group: a layer that holds a
    # share of the experts bounds its rows at a few times the share,
    # ops/moe.held_rows_bound, so a few tiles of it; most of the rows
    # only on that layer's full path): zeros, and no product
    @pl.when(jnp.logical_not(real))
    def _():
        def block(rows, first):
            old = out[rows, :]
            out[rows, :] = jnp.where(_mine(start, end, first, old.shape),
                                     jnp.zeros_like(old), old)
        _edge_blocks(tm, sub, start, end, row0, block)


def _check(what: str, tiles: Tuple[int, ...], *divides):
    """Each (x, t): t must divide x."""
    if any(x % t for x, t in divides):
        raise ValueError(f"{what}: tiles {tiles} do not divide "
                         f"{[x for x, _ in divides]}")


@functools.partial(jax.jit, static_argnames=(
    "tiles", "transpose_rhs", "out_dtype", "interpret", "run"))
def gmm(lhs, rhs, sizes, tiles: Tuple[int, int, int],
        transpose_rhs: bool = False, out_dtype=None,
        interpret: bool = False, run: int = RUN):
    """``lax.ragged_dot(lhs, rhs, sizes)``. lhs: [M, K]; rhs:
    [E, K, N] ([E, N, K] with `transpose_rhs`); sizes: [E] int32;
    tiles: (tm, sub, tn). Returns [M, N]; the rows past the last
    group come out zero, and no product is made for them."""
    m, k = lhs.shape
    e = rhs.shape[0]
    n = rhs.shape[1 if transpose_rhs else 2]
    tm, sub, tn = tiles
    _check("gmm (rows, rows of a tile, columns)", tiles, (m, tm), (tm, sub),
           (n, tn))
    out_dtype = out_dtype or lhs.dtype
    offsets, group, tile, count = visits(sizes, m, tm, visit_empty=False)

    def rhs_index(n_i, v, offsets, group, tile):
        g = jnp.minimum(group[v], e - 1)
        return (g, n_i, 0) if transpose_rhs else (g, 0, n_i)

    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, sub=sub, run=run, n_groups=e,
                          transpose_rhs=transpose_rhs),
        name="moe_gmm_nt" if transpose_rhs else "moe_gmm",
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, count),
            in_specs=[
                pl.BlockSpec((tm, k), lambda n_i, v, o, g, t: (t[v], 0)),
                pl.BlockSpec((None, tn, k) if transpose_rhs
                             else (None, k, tn), rhs_index)],
            out_specs=pl.BlockSpec((tm, tn), lambda n_i, v, o, g, t:
                                   (t[v], n_i))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(lhs.size * lhs.dtype.itemsize * (n // tn)
                            + rhs.size * rhs.dtype.itemsize
                            + m * n * jnp.dtype(out_dtype).itemsize)),
        interpret=interpret,
    )(offsets, group, tile, lhs, rhs)


def _tgmm_kernel(offsets, group, tile, lhs, rhs, out, acc, *, tm: int,
                 sub: int, run: int):
    v, last_v = pl.program_id(2), pl.num_programs(2) - 1
    g = group[v]
    start, end, row0, whole = _edges(offsets, group, tile, v, tm)
    dims = (((0,), (0,)), ((), ()))

    @pl.when((v == 0) | (group[jnp.maximum(v - 1, 0)] != g))
    def _():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(whole)
    def _():
        def add(cols):
            acc[:, cols] += lax.dot_general(
                lhs[...], rhs[:, cols], dims,
                preferred_element_type=jnp.float32)
        _runs(acc.shape[1], run, add)

    # a tile with a group edge in it: only the blocks of `sub` rows the
    # group reaches into, the others' rows in them counted as zeros (in
    # both operands: 0 x inf is not 0); a group without rows: none
    @pl.when(jnp.logical_not(whole))
    def _():
        def block(rows, first):
            def mine(x):
                x = x[rows, :]
                return jnp.where(_mine(start, end, first, x.shape),
                                 x.astype(jnp.float32), 0.0).astype(x.dtype)
            acc[...] += lax.dot_general(mine(lhs), mine(rhs), dims,
                                        preferred_element_type=jnp.float32)
        _edge_blocks(tm, sub, start, end, row0, block)

    @pl.when((v == last_v) | (group[jnp.minimum(v + 1, last_v)] != g))
    def _():
        out[...] = acc[...].astype(out.dtype)


@functools.partial(jax.jit, static_argnames=(
    "tiles", "out_dtype", "interpret", "run"))
def tgmm(lhs, rhs, sizes, tiles: Tuple[int, int, int, int], out_dtype=None,
         interpret: bool = False, run: int = RUN):
    """``lhs_g^T @ rhs_g`` for every group g of rows. lhs: [M, K];
    rhs: [M, N]; sizes: [E] int32; tiles: (tm, sub, tk, tn). Returns
    [E, K, N], zeros for a group without rows."""
    m, k = lhs.shape
    n = rhs.shape[1]
    e = sizes.shape[0]
    tm, sub, tk, tn = tiles
    _check("tgmm (rows, rows of a tile, K, N)", tiles, (m, tm), (tm, sub),
           (k, tk), (n, tn))
    out_dtype = out_dtype or lhs.dtype
    offsets, group, tile, count = visits(sizes, m, tm, visit_empty=True)
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm, sub=sub, run=run),
        name="moe_tgmm",
        out_shape=jax.ShapeDtypeStruct((e, k, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, k // tk, count),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n_i, k_i, v, o, g, t:
                             (t[v], k_i)),
                pl.BlockSpec((tm, tn), lambda n_i, k_i, v, o, g, t:
                             (t[v], n_i))],
            out_specs=pl.BlockSpec((None, tk, tn),
                                   lambda n_i, k_i, v, o, g, t:
                                   (g[v], k_i, n_i)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(lhs.size * lhs.dtype.itemsize * (n // tn)
                            + rhs.size * rhs.dtype.itemsize * (k // tk)
                            + e * k * n * jnp.dtype(out_dtype).itemsize)),
        interpret=interpret,
    )(offsets, group, tile, lhs, rhs)
