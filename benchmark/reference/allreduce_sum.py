"""MPI_Allreduce(MPI_SUM) by the book, and the seeded inputs it sums.

Nothing here imports the program (the seed's key is the benchmark's own). Every rank's input is a function of
(--seed, index of the size, rank), drawn on the device by jax.random —
threefry gives the same bits on every chip, so each rank can draw the
others' inputs again by itself and needs nothing the program moved.

The plain answer is the float64 sum of the ranks' inputs, rounded to
the buffer's type. MPI leaves the order of a floating-point reduction
open, so a result is held to it within a few roundings of the stated
type: the gap is |got - want| / sum_r |x_r| per element (a bound that
does not blow up where the inputs cancel), the largest over a sample
of elements drawn from the seed (blocks of them, where the buffer is
large). `deterministic="linear"` promises
more — the rank-order fold ((x0 + x1) + x2) + x3 in the buffer's type,
bit for bit — and is held to exactly that, computed here with numpy.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark.weights import seed_key


def _key(seed: int, size_index: int, rank: int):
    import jax

    return jax.random.fold_in(jax.random.fold_in(
        seed_key(seed), 1000 + size_index), rank)


@functools.lru_cache(maxsize=None)
def _draw(n_elems: int, dtype: str):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda k: jax.random.normal(
        k, (n_elems,), jnp.float32).astype(dtype))


def rank_input(seed: int, size_index: int, rank: int, n_elems: int,
               dtype: str):
    """Rank `rank`'s send buffer for this size, on the local device."""
    return _draw(n_elems, dtype)(_key(seed, size_index, rank))


BLOCKS = 16


@functools.lru_cache(maxsize=None)
def _pick(n_elems: int, sample: int):
    """The elements that are compared: all of them where the buffer
    is no larger than `sample`, else BLOCKS contiguous blocks at
    offsets drawn from the seed (a random gather of a million single
    elements out of a GiB costs the chip seconds; slices cost
    nothing)."""
    import jax
    import jax.numpy as jnp

    if n_elems <= sample:
        return jax.jit(lambda k, x: x)
    size = sample // BLOCKS

    def blocks(k, x):
        starts = jax.random.randint(k, (BLOCKS,), 0, n_elems - size,
                                    jnp.int32)
        return jnp.concatenate([
            jax.lax.dynamic_slice(x, (starts[i],), (size,))
            for i in range(BLOCKS)])

    return jax.jit(blocks)


def result_gap(result, seed: int, size_index: int, ranks: int,
               n_elems: int, dtype: str, sample: int,
               control_dtype=None) -> float:
    """Largest gap of `result` from the plain sum over the sampled
    elements (all of them where the buffer is no larger than the
    sample). With `control_dtype` the result is not looked at: the
    control — the same sum carried in that lower type — stands in
    its place."""
    import jax

    pick = _pick(n_elems, sample)
    k = _key(seed, size_index, ranks)  # no rank's key
    xs = [np.asarray(pick(k, rank_input(seed, size_index, r, n_elems,
                                        dtype)), np.float64)
          for r in range(ranks)]
    if control_dtype is not None:
        acc = None
        for r in range(ranks):
            x = rank_input(seed, size_index, r, n_elems,
                           dtype).astype(control_dtype)
            acc = x if acc is None else acc + x
        result = acc.astype(dtype)
    got = np.asarray(jax.device_get(pick(k, result)), np.float64)
    want = np.sum(xs, axis=0).astype(dtype).astype(np.float64)
    scale = np.maximum(np.sum(np.abs(xs), axis=0),
                       np.finfo(np.dtype(dtype)).tiny)
    return float(np.max(np.abs(got - want) / scale))


def linear_fold_mismatches(result, seed: int, size_index: int, ranks: int,
                           n_elems: int, dtype: str) -> int:
    """Elements of `result` that are not, bit for bit, the rank-order
    fold of the ranks' inputs in the buffer's own type."""
    acc = None
    for r in range(ranks):
        x = np.asarray(rank_input(seed, size_index, r, n_elems, dtype))
        acc = x.copy() if acc is None else (acc + x).astype(x.dtype)
    got = np.asarray(result)
    view = np.dtype(f"uint{8 * got.dtype.itemsize}")
    return int(np.count_nonzero(got.view(view) != acc.view(view)))
