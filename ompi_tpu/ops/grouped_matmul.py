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
in float32 over the group's visits. **The packed out block**
(``gmm(packed=True)``; :func:`packed_shape`): where the product's rows
are next fetched ONE BY ONE — each token's k rows, by the sort's
inverse — the tile is written as uint32 words, two bfloat16 rows a word
(one float32 row), ``[tm / pack * lanes, 128]``: a row's columns are
`lanes` whole sublanes one after another and not one sublane of `lanes`
tiles, so a row (a pair) is one contiguous run of bytes that a single
DMA moves. Mosaic (jax 0.9.0) slices no one row out of a 2-D array in
HBM — a slice's rows must be whole tiles of 8 —, and XLA making this
layout from the plain one cost more than the fetch it serves (PERF.md 7
"From PR 44"): the products whose rows are fetched so write it
themselves, a strided store a block of 128 columns where the plain
block takes a dense one, and may sum a second pair's product in the
float32 tile first (the rows' gradient of a gated expert: ``d h1 w1^T +
d h3 w3^T``, one rounding and no pass of XLA's to add them).

:func:`row_reduce` is the reader: ``y[i] = sum_j w[i, j] row(place[i,
j])``, a DMA a held row into VMEM, the float32 sum there, the token's
``[lanes, 128]`` slab turned back into one row of the plain ``[T, D]``
result by strided loads.
"""

from __future__ import annotations

import functools
import operator
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
    """body(slice, its first column) over `width` columns in runs of
    `run`: a loop, not one unrolled product (see RUN)."""
    if run >= width:
        body(slice(None), 0)
        return
    # the widest run of whole lanes that divides the width
    run = next(d for d in range(run, 0, -128) if width % d == 0)

    def step(j, _):
        first = pl.multiple_of(j * run, run)
        body(pl.ds(first, run), first)

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


def packed_rows(dtype) -> int:
    """Rows a uint32 word of the packed layout holds: 2 of bfloat16
    (row 2i the low half, row 2i + 1 the high one, as the chip packs a
    pair of sublanes), 1 of float32."""
    return 4 // jnp.dtype(dtype).itemsize


def packed_lanes(n: int) -> int:
    """Sublanes a row's `n` columns take in the packed layout, 128
    columns each: whole tiles of 8 (18 -> 24, 21 -> 24)."""
    return -(-n // 1024) * 8


def packed_shape(m: int, n: int, tn: int, dtype) -> Tuple[int, int]:
    """The packed layout of ``[m, n]`` rows of `dtype` made in blocks of
    `tn` columns: uint32 words ``[n / tn, m / pack, lanes, 128]`` as
    ONE 2-D array — a row's columns of a block are `lanes` whole
    sublanes one after another, ``lanes * 512`` contiguous bytes that a
    single DMA fetches; the blocks of columns lead (one block where the
    kernel holds the whole of N)."""
    return (n // tn * (m // packed_rows(dtype)) * packed_lanes(tn), 128)


def _words(val, dtype):
    """[rows, 128 c] float32 rounded to `dtype` -> its uint32 words,
    [rows / packed_rows, 128 c]."""
    return pltpu.bitcast(val.astype(dtype), jnp.uint32)


def _gmm_kernel(offsets, group, tile, *refs, tm: int, sub: int, run: int,
                n_groups: int, transpose_rhs: bool, packed):
    *operands, out = refs
    v = pl.program_id(1)
    start, end, row0, whole = _edges(offsets, group, tile, v, tm)
    real = group[v] < n_groups
    whole &= real
    dims = (((1,), (1 if transpose_rhs else 0,)), ((), ()))

    def product(rows, cols):
        # the pairs' products summed in the float32 tile
        return functools.reduce(operator.add, (lax.dot_general(
            lhs[rows, :], rhs[cols, :] if transpose_rhs else rhs[:, cols],
            dims, preferred_element_type=jnp.float32)
            for lhs, rhs in zip(operands[::2], operands[1::2])))

    if packed is not None:
        width = operands[1].shape[0 if transpose_rhs else 1]
        _gmm_packed_tile(out, product, width, packed, whole, real, start,
                         end, row0, tm=tm, sub=sub, run=run)
        return

    @pl.when(whole)
    def _():
        def columns(cols, _):
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


def _gmm_packed_tile(out, product, width: int, dtype, whole, real, start,
                     end, row0, *, tm: int, sub: int, run: int):
    """`_gmm_kernel`'s three cases with the PACKED out block
    (:func:`packed_shape`): ``[tm / pack * lanes, 128]`` uint32 words,
    a row's columns `lanes` sublanes one after another. A ``[rows,
    128]`` piece of the float32 tile becomes ``rows / pack`` words, a
    sublane apart in the tile and `lanes` apart in the block: one
    strided store a block of 128 columns."""
    pack = packed_rows(dtype)
    lanes = packed_lanes(width)

    def at(word0, words, lane):
        return pl.ds(word0 * lanes + lane, words, stride=lanes)

    @pl.when(whole)
    def _():
        def columns(cols, first):
            words = _words(product(slice(None), cols), dtype)
            for q in range(words.shape[1] // 128):
                out[at(0, tm // pack, first // 128 + q), :] = (
                    words[:, q * 128:(q + 1) * 128])
        _runs(width, run, columns)

    def edge(value):
        # as the plain block's: the whole block's product once, then
        # block by block of 128 columns the words there, the group's
        # rows of them replaced
        def block(rows, first):
            new = value(rows)
            mine = _mine(start, end, first, (sub, 128))
            for j in range(width // 128):
                where = at((first - row0) // pack, sub // pack, j)
                old = pltpu.bitcast(out[where, :], dtype)
                out[where, :] = pltpu.bitcast(jnp.where(
                    mine, new[:, j * 128:(j + 1) * 128], old), jnp.uint32)
        _edge_blocks(tm, sub, start, end, row0, block)

    @pl.when(jnp.logical_not(whole) & real)
    def _():
        edge(lambda rows: product(rows, slice(None)).astype(dtype))

    @pl.when(jnp.logical_not(real))
    def _():
        edge(lambda rows: jnp.zeros((sub, width), dtype))


def _check(what: str, tiles: Tuple[int, ...], *divides):
    """Each (x, t): t must divide x."""
    if any(x % t for x, t in divides):
        raise ValueError(f"{what}: tiles {tiles} do not divide "
                         f"{[x for x, _ in divides]}")


@functools.partial(jax.jit, static_argnames=(
    "tiles", "transpose_rhs", "out_dtype", "interpret", "run", "packed"))
def gmm(lhs, rhs, sizes, tiles: Tuple[int, int, int],
        transpose_rhs: bool = False, out_dtype=None,
        interpret: bool = False, run: int = RUN, packed: bool = False,
        lhs2=None, rhs2=None):
    """``lax.ragged_dot(lhs, rhs, sizes)``. lhs: [M, K]; rhs:
    [E, K, N] ([E, N, K] with `transpose_rhs`); sizes: [E] int32;
    tiles: (tm, sub, tn). Returns [M, N]; the rows past the last
    group come out zero, and no product is made for them. A second
    pair `lhs2`, `rhs2` of the same shapes is multiplied likewise and
    the two products are summed in the float32 tile, before the one
    rounding. `packed`: the result in the layout :func:`row_reduce`
    reads (:func:`packed_shape`; the sublanes past a row's columns are
    nobody's)."""
    m, k = lhs.shape
    e = rhs.shape[0]
    n = rhs.shape[1 if transpose_rhs else 2]
    tm, sub, tn = tiles
    _check("gmm (rows, rows of a tile, columns)", tiles, (m, tm), (tm, sub),
           (n, tn))
    out_dtype = jnp.dtype(out_dtype or lhs.dtype)
    offsets, group, tile, count = visits(sizes, m, tm, visit_empty=False)
    pairs = [lhs, rhs] + ([] if lhs2 is None else [lhs2, rhs2])

    def rhs_index(n_i, v, offsets, group, tile):
        g = jnp.minimum(group[v], e - 1)
        return (g, n_i, 0) if transpose_rhs else (g, 0, n_i)

    out_shape, out_block = (m, n), (tm, tn)
    if packed:
        pack = packed_rows(out_dtype)
        _check("gmm (packed: a block's columns are whole sublane tiles, "
               "an edge block's rows whole tiles of words)", tiles,
               (tn, 1024 if tn < n else 128), (sub, 8 * pack))
        out_shape = packed_shape(m, n, tn, out_dtype)
        out_block = (tm // pack * packed_lanes(tn), 128)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, sub=sub, run=run, n_groups=e,
                          transpose_rhs=transpose_rhs,
                          packed=out_dtype if packed else None),
        name="moe_gmm_nt" if transpose_rhs else "moe_gmm",
        out_shape=jax.ShapeDtypeStruct(
            out_shape, jnp.uint32 if packed else out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, count),
            in_specs=[
                pl.BlockSpec((tm, k), lambda n_i, v, o, g, t: (t[v], 0)),
                pl.BlockSpec((None, tn, k) if transpose_rhs
                             else (None, k, tn), rhs_index)]
            * (len(pairs) // 2),
            out_specs=pl.BlockSpec(
                out_block, (lambda n_i, v, o, g, t:
                            (n_i * (m // tm) + t[v], 0)) if packed
                else lambda n_i, v, o, g, t: (t[v], n_i))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=m * k * n * len(pairs), transcendentals=0,
            bytes_accessed=(len(pairs) // 2 * (
                lhs.size * lhs.dtype.itemsize * (n // tn)
                + rhs.size * rhs.dtype.itemsize)
                + m * n * out_dtype.itemsize)),
        interpret=interpret,
    )(offsets, group, tile, *pairs)


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
        def add(cols, _):
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


#: Tokens a grid step of `row_reduce` sums: their k x 128 places are
#: one SMEM block and as many row copies in flight on one semaphore.
REDUCE_TOKENS = 128
#: Copies a pass of the first tile's start loop and of a wait loop
#: where every place has its row (no branch between them; on the
#: chip, PR 51, 2 / 8 / 16 a pass: 7.58 / 6.33 / 6.12 ms a reduce at
#: mellum2-train-t16384's shape before the starts moved into the
#: summing loop, 5.59 / 5.55 at 8 / 16 since).
REDUCE_UNROLL = 16


def _row_reduce_kernel(counts, rows_at, rows_next, *refs, pack: int,
                       width: int, block: int, weighted: bool,
                       compact: bool):
    weights = refs[0] if weighted else None
    ends, src, out, rows, total, sems = (
        (None,) * (not compact) + refs[weighted:])
    k, tokens = rows_at.shape
    part = packed_lanes(block)
    lanes = width // block * part
    words = src.shape[0] // (width // block) // part
    tile, last_tile = pl.program_id(0), pl.num_programs(0) - 1
    here = tile % 2  # the buffer (and semaphore) of this tile's rows

    def at(n):
        return n // tokens, n % tokens

    def copies(places, buf, n):
        """The copies of entry n's row into buffer `buf`: one a block
        of columns."""
        word = places[at(n)] // pack
        return [pltpu.make_async_copy(
            src.at[pl.ds(pl.multiple_of((b * words + word) * part, 8), part)],
            rows.at[buf, pl.ds(pl.multiple_of(n * lanes + b * part, 8),
                               part)], sems.at[buf])
            for b in range(width // block)]

    def each(count, body):
        """body(n) for n under `count`; where that is static,
        `REDUCE_UNROLL` (which divides it) a pass."""
        unroll = 1 if compact else REDUCE_UNROLL

        def some(s, _):
            for u in range(unroll):
                body(s * unroll + u)
        lax.fori_loop(0, count // unroll, some, None)

    def start(places, buf, count):
        each(count, lambda n: [dma.start() for dma in copies(places, buf, n)])

    def entries(t):
        """How many entries tile t has: its held places, or all."""
        return counts[t] if compact else k * tokens

    # this tile's rows were asked for a grid step ago (the first tile's:
    # now), into the buffer of its parity, so that they cross HBM while
    # the tile before is summed; under a bound the next tile's few are
    # asked for here, in a loop of their own
    pl.when(tile == 0)(lambda: start(rows_at, 0, entries(0)))
    if compact:
        pl.when(tile < last_tile)(lambda: start(
            rows_next, 1 - here, counts[jnp.minimum(tile + 1, last_tile)]))
    # (a wait reads its copy's size and semaphore alone: every copy is
    # one block's sublanes, so one descriptor stands for them all)
    def wait(buf, count):
        one = pltpu.make_async_copy(src.at[pl.ds(0, part)],
                                    rows.at[buf, pl.ds(0, part)],
                                    sems.at[buf])
        each(count, lambda n: [one.wait() for _ in range(width // block)])

    wait(here, entries(tile))

    def value(n):
        """Entry n's row as float32 ``[lanes, 128]``, weighed."""
        val = rows[here, pl.ds(pl.multiple_of(n * lanes, 8), lanes), :]
        if pack == 2:
            # the row's half of each word, in the high half: the
            # float32 its bfloat16 is
            half = (16 * (rows_at[at(n)] % 2)).astype(jnp.uint32)
            val = (val >> half) << 16
        val = pltpu.bitcast(val, jnp.float32)
        return val * weights[at(n)] if weighted else val

    def put(i, acc):
        total[pl.ds(pl.multiple_of(i * lanes, 8), lanes), :] = acc

    if compact:
        # the tile's held places alone, token by token
        def token(i, first):
            last = ends[0, i]
            put(i, lax.fori_loop(
                first, last, lambda n, acc: acc + value(n),
                jnp.zeros((lanes, 128), jnp.float32)))
            return last

        lax.fori_loop(0, tokens, token, 0)
    else:
        # every place has its row: entry j * tokens + i is token i's
        # place j; the NEXT tile's copies are asked for between this
        # tile's sums, the scalar core's work beside the vector unit's
        # (5.55 ms for 6.33 at mellum2-train-t16384's shape, PR 51); the
        # last tile asks for its own rows again, and waits for them
        def token(i, _):
            for j in range(k):
                for dma in copies(rows_next, 1 - here, j * tokens + i):
                    dma.start()
            put(i, functools.reduce(operator.add, (
                value(j * tokens + i) for j in range(k))))

        lax.fori_loop(0, tokens, token, None)
        pl.when(tile == last_tile)(lambda: wait(1 - here, k * tokens))
    # a token's sum is a [lanes, 128] slab; the result wants it as one
    # row: block by block of 128 columns, the tokens' sublanes `lanes`
    # apart
    for j in range(width // 128):
        out[:, j * 128:(j + 1) * 128] = total[
            pl.ds(j, tokens, stride=lanes), :].astype(out.dtype)


def _held_places(places, weights, held, tokens: int):
    """The places under `held` of each tile of `tokens` tokens, token
    by token and a token's in the order they have, in front of the
    tile's others: (rows [tiles, k, tokens] — entry n of a tile at
    ``[n // tokens, n % tokens]`` —, the weights likewise or None,
    [tiles, 1, tokens] the entries up to and with each token's, [tiles]
    a tile's held places). ONE sort of each tile's ``k * tokens``
    scalars."""
    k, t = places.shape
    tiles = t // tokens

    def by_tile(x):  # [k, t] -> [tiles, tokens * k], the tokens leading
        return x.reshape(k, tiles, tokens).transpose(1, 2, 0).reshape(
            tiles, tokens * k)

    live = by_tile(places) < held
    key = jnp.where(live, 0, tokens * k) + lax.broadcasted_iota(
        jnp.int32, live.shape, 1)
    operands = (key, by_tile(places)) + (
        () if weights is None else (by_tile(weights),))
    _, rows, *rest = lax.sort(operands, dimension=1, num_keys=1)
    ends = jnp.cumsum(live.reshape(tiles, tokens, k).sum(2, dtype=jnp.int32),
                      axis=1, dtype=jnp.int32)
    shape = (tiles, k, tokens)
    return (rows.reshape(shape), rest[0].reshape(shape) if rest else None,
            ends[:, None, :], ends[:, -1])


@functools.partial(jax.jit, static_argnames=(
    "width", "block", "dtype", "compact", "interpret"))
def row_reduce(src, places, weights, held, width: int, block: int, dtype,
               compact: bool = True, interpret: bool = False):
    """``y[i] = sum_j weights[j, i] * row(places[j, i])`` over the
    places under `held`: the products and the sum in float32, ONE
    rounding to `dtype`. src: rows of `width` columns of `dtype` in the
    packed layout, made in blocks of `block` columns (:func:`gmm`'s
    `packed`, :func:`packed_shape`); places: [k, T] int32, each token's
    k rows, place by place; weights: [k, T] float32, or None for the
    plain sum; held: int32 scalar — a place at or past it adds nothing.
    Returns [T, width] of `dtype`. Every row is fetched by a DMA of its
    own (one descriptor a place and block of columns), a tile of
    `REDUCE_TOKENS` tokens' copies in flight at once and asked for a
    grid step ahead, into the second of two buffers. `compact`: only
    the places under `held` are walked at all — found by one sort of
    scalars a tile —, their rows the only ones fetched: the form of a
    layer under a bound, most of whose places are nobody's. Without it
    every place is fetched and one past `held` WEIGHS zero, so its row
    must be finite (a product's rows past its last group are zeros):
    the form of a layer with all its rows."""
    k, t = places.shape
    lanes = width // block * packed_lanes(block)
    pack = packed_rows(dtype)
    tokens = REDUCE_TOKENS
    _check("row_reduce (tokens, columns, columns of a block)",
           (tokens, block, 128), (t, tokens), (width, block), (block, 128))
    weighted = weights is not None
    if weighted:
        weights = weights.astype(jnp.float32)
    tiles = t // tokens
    if compact:
        rows_at, weights, ends, counts = _held_places(places, weights, held,
                                                      tokens)
    else:
        def by_tile(x):
            return x.reshape(k, tiles, tokens).transpose(1, 0, 2)
        rows_at, counts = by_tile(places), jnp.zeros((tiles,), jnp.int32)
        if weighted:
            weights = by_tile(jnp.where(places < held, weights, 0.0))

    def scalars(rows, ahead=0):
        return pl.BlockSpec(
            (None, rows, tokens), lambda i, counts: (
                jnp.minimum(i + ahead, tiles - 1), 0, 0),
            memory_space=pltpu.SMEM)

    return pl.pallas_call(
        functools.partial(_row_reduce_kernel, pack=pack, width=width,
                          block=block, weighted=weighted, compact=compact),
        name="moe_row_reduce",
        out_shape=jax.ShapeDtypeStruct((t, width), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(tiles,),
            in_specs=[scalars(k), scalars(k, ahead=1)]
            + [scalars(k)] * weighted + [scalars(1)] * compact
            + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tokens, width), lambda i, counts: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, k * tokens * lanes, 128), jnp.uint32),
                pltpu.VMEM((tokens * lanes, 128), jnp.float32),
                pltpu.SemaphoreType.DMA((2,))]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * k * t * width, transcendentals=0,
            bytes_accessed=(k * t * lanes * 512 // pack
                            + t * width * jnp.dtype(dtype).itemsize)),
        interpret=interpret,
    )(counts, rows_at, rows_at, *([weights] if weighted else []),
      *([ends] if compact else []), src)
