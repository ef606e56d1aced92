"""The TPU DMA kernels' protocol, checked without a chip.

``interpret=True`` never reaches ``make_async_remote_copy`` (the hop is
a ppermute), so until PR 21 no test executed the DMA kernels at all.
The Pallas TPU interpreter emulates remote copies and semaphores
between virtual CPU devices and carries a happens-before race detector:
these tests run the SAME kernels a chip compiles, against plain numpy,
and fail on any race it reports. Whether Mosaic compiles them is
chip_smoke.py's business.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from ompi_tpu.coll import pallas_kernels as K
from ompi_tpu.osc import pallas_kernels as OK
from ompi_tpu.util import jaxcompat

AXIS = "x"


def _add(a, b):
    return a + b


@pytest.fixture
def dma():
    """InterpretParams with race detection; asserts none was found."""
    from jax._src.pallas.mosaic.interpret import (
        interpret_pallas_call as ipc)

    pltpu.reset_tpu_interpret_mode_state()
    yield pltpu.InterpretParams(detect_races=True)
    assert ipc.races is not None and not ipc.races.races_found


def _smap(n, fn, nin=1):
    mesh = Mesh(np.array(jax.devices()[:n]), (AXIS,))
    return jax.jit(jaxcompat.shard_map(
        fn, mesh=mesh, in_specs=(P(AXIS),) * nin, out_specs=P(AXIS),
        check_vma=False))


@pytest.mark.parametrize("n,m", [(4, 4 * 300), (3, 3 * 8 * 128)])
def test_ring_kernels_dma_protocol(dma, n, m):
    """reduce_scatter (both directions), allgather and the bidir
    allreduce over real DMA hops: exact, race-free, at a tile-aligned
    and an unaligned length."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(-50, 50, (n, m)).astype(np.float32))
    total = np.asarray(x).sum(0)
    for d in (1, -1):
        got = _smap(n, lambda a: K.ring_reduce_scatter(
            a[0], AXIS, _add, interpret=dma, direction=d)[None])(x)
        np.testing.assert_array_equal(got, total.reshape(n, m // n))
    xs = x[:, :m // n]
    got = _smap(n, lambda a: K.ring_allgather(
        a[0], AXIS, interpret=dma)[None])(xs)
    np.testing.assert_array_equal(
        got, np.broadcast_to(np.asarray(xs).reshape(-1), (n, m)))
    got = _smap(n, lambda a: K.ring_allreduce(
        a[0], AXIS, _add, interpret=dma, bidir=True)[None])(x)
    np.testing.assert_array_equal(got, np.broadcast_to(total, (n, m)))
    got = _smap(n, lambda a: K.linear_reduce_scatter(
        a[0], AXIS, _add, interpret=dma)[None])(x)
    np.testing.assert_array_equal(got, total.reshape(n, m // n))


def test_fused_kernels_dma_protocol(dma):
    """The fused update and allgather-matmul kernels on DMA hops
    equal their ppermute-schedule twins."""
    n, k = 4, 333
    rng = np.random.default_rng(1)
    g = jnp.asarray(rng.integers(-9, 9, (n, n * k)).astype(np.float32))
    p = jnp.asarray(rng.integers(-9, 9, (n, k)).astype(np.float32))
    v = jnp.asarray(rng.integers(-9, 9, (n, k)).astype(np.float32))

    def fused(interp):
        def body(a, p, v):
            pn, vn = K.ring_reduce_scatter_update(
                a[0], AXIS, _add, p[0], v[0], lr=0.5, mu=0.25,
                inv=1.0 / n, interpret=interp)
            return jnp.stack([pn, vn])[None]

        return _smap(n, body, nin=3)(g, p, v)

    np.testing.assert_array_equal(fused(dma), fused(True))
    x = jnp.asarray(rng.integers(-3, 3, (n, 16, 64)).astype(np.float32))
    w = jnp.asarray(rng.integers(-3, 3, (n, 64, 128)).astype(np.float32))
    got = _smap(n, lambda a, w: K.allgather_matmul(
        a[0], w[0], AXIS, interpret=dma)[None], nin=2)(x, w)
    full = np.asarray(x).reshape(n * 16, 64)
    for r in range(n):
        np.testing.assert_array_equal(got[r], full @ np.asarray(w[r]))


def test_osc_dma_permute_protocol(dma):
    """One colored RMA round: full ring, a lone edge, a chain whose
    middle rank both sends and receives."""
    n, k = 4, 1000
    rng = np.random.default_rng(2)
    run = _smap(n, lambda p, t, s: OK.dma_permute(
        p[0], t[0], s[0], AXIS, n, interpret=dma)[None], nin=3)
    for perm in ([(0, 1), (1, 2), (2, 3), (3, 0)], [(0, 2)],
                 [(3, 1), (1, 2)]):
        pay = rng.standard_normal((n, k)).astype(np.float32)
        tgt = np.full((n, 1), -1, np.int32)
        src = np.full((n, 1), -1, np.int32)
        want = np.zeros((n, k), np.float32)
        for s, d in perm:
            tgt[s, 0], src[d, 0], want[d] = d, s, pay[s]
        got = run(jnp.asarray(pay), jnp.asarray(tgt), jnp.asarray(src))
        np.testing.assert_array_equal(got, want)
