"""coll/pallas — hand-rolled ring collective backend (priority 60,
opt-in) over the device plane.

Interpret-mode kernels + ppermute hops on the CI CPU ranks — the same
chunk schedule the TPU DMA kernels run, so ring correctness and the
bit-identity contracts are proven without hardware. The component is
opt-in (``coll_pallas on``): every test here stacks it explicitly.
"""

import pytest

from tests.harness import run_ranks

MCA = {"device_plane": "on", "coll_pallas": "on"}


@pytest.mark.parametrize("n", [2, 3, 4])
def test_allreduce_bit_identical_to_xla(n):
    """Deterministic modes must match coll/xla bit for bit on pow2 and
    non-pow2 meshes (odd chunk remainders); the default ring is
    allclose (different add order is the point)."""
    run_ranks("""
    import jax.numpy as jnp
    from ompi_tpu.coll import xla as cx
    assert comm.coll.providers["allreduce_dev"] == "pallas"
    rng = np.random.default_rng(11)
    h = (rng.standard_normal(257) * (10.0 ** rng.integers(-3, 4, 257))
         ).astype(np.float32)
    h = np.roll(h, rank * 7)
    for dt, u in ((jnp.float32, np.uint32), (jnp.bfloat16, np.uint16)):
        x = jnp.asarray(h).astype(dt)
        for det in ("linear", "ring"):
            p = np.asarray(comm.coll.allreduce_dev(
                comm, x, deterministic=det))
            r = np.asarray(cx.allreduce_dev(
                comm, x, deterministic=det))
            assert (p.view(u) == r.view(u)).all(), (det, str(dt))
        p = np.asarray(comm.coll.allreduce_dev(comm, x))
        r = np.asarray(cx.allreduce_dev(comm, x))
        np.testing.assert_allclose(
            p.astype(np.float32), r.astype(np.float32),
            rtol=2e-2 if dt == jnp.bfloat16 else 1e-5, atol=1e-5)
    """, n, mca=MCA)


@pytest.mark.parametrize("n", [2, 3, 4])
def test_reduce_scatter_allgather_vs_xla(n):
    run_ranks("""
    import jax.numpy as jnp
    from ompi_tpu.core import pvar
    from ompi_tpu.coll import xla as cx
    assert comm.coll.providers["reduce_scatter_block_dev"] == "pallas"
    assert comm.coll.providers["allgather_dev"] == "pallas"
    rng = np.random.default_rng(rank)
    s = pvar.session()
    x = jnp.asarray(rng.standard_normal((3 * size, 5)
                                        ).astype(np.float32))
    p = np.asarray(comm.coll.reduce_scatter_block_dev(
        comm, x, deterministic="linear"))
    r = np.asarray(cx.reduce_scatter_block_dev(
        comm, x, deterministic="linear"))
    assert (p.view(np.uint32) == r.view(np.uint32)).all()
    # allgather moves data unchanged -> exact on any mesh size
    y = jnp.asarray(rng.standard_normal((7, 3)).astype(np.float32))
    pg = np.asarray(comm.coll.allgather_dev(comm, y))
    rg = np.asarray(cx.allgather_dev(comm, y))
    assert pg.shape == (size, 7, 3)
    np.testing.assert_array_equal(pg, rg)
    assert s.read("pallas_launches") >= 2
    """, n, mca=MCA)


def test_unsupported_dtype_falls_through():
    """int16 is outside the support matrix: the slot must delegate to
    coll/xla with identical arguments (same result, provider stays
    pallas, pallas_fallthrough counts the delegation)."""
    run_ranks("""
    import jax.numpy as jnp
    from ompi_tpu.core import pvar
    assert comm.coll.providers["allreduce_dev"] == "pallas"
    s = pvar.session()
    x = (jnp.arange(32) % 7 + rank).astype(jnp.int16)
    r = np.asarray(comm.coll.allreduce_dev(comm, x))
    exp = sum((np.arange(32) % 7 + rr).astype(np.int16)
              for rr in range(size))
    np.testing.assert_array_equal(r, exp)
    assert s.read("pallas_fallthrough") >= 1
    assert s.read("pallas_launches") == 0
    """, 2, mca=MCA)


def test_indivisible_reduce_scatter_raises():
    """An indivisible dim 0 is a caller error, not a fallthrough case
    — the delegated coll/xla slot raises the same MPIError."""
    run_ranks("""
    import jax.numpy as jnp
    from ompi_tpu import errors
    x = jnp.ones((3 * size + 1, 2), jnp.float32)
    try:
        comm.coll.reduce_scatter_block_dev(comm, x)
    except errors.MPIError as e:
        assert e.error_class == errors.ERR_COUNT, e
    else:
        raise AssertionError("indivisible dim0 did not raise")
    """, 2, mca=MCA)


def test_forced_algorithm_cvar():
    """coll_pallas_allreduce_algorithm pins the variant (the
    coll_tuned_*_algorithm analog); 'xla' always falls through."""
    run_ranks("""
    import jax.numpy as jnp
    from ompi_tpu.core import cvar, pvar
    x = jnp.arange(64, dtype=jnp.float32) + rank
    try:
        cvar.set("coll_pallas_allreduce_algorithm", "linear")
        s = pvar.session()
        comm.coll.allreduce_dev(comm, x)
        assert s.read("pallas_linear_bytes") == 64 * 4
        cvar.set("coll_pallas_allreduce_algorithm", "bidir")
        s = pvar.session()
        comm.coll.allreduce_dev(comm, x)
        assert s.read("pallas_bidir_bytes") == 64 * 4
        cvar.set("coll_pallas_allreduce_algorithm", "xla")
        s = pvar.session()
        comm.coll.allreduce_dev(comm, x)
        assert s.read("pallas_fallthrough") == 1
        assert s.read("pallas_launches") == 0
    finally:
        cvar.set("coll_pallas_allreduce_algorithm", "")
    """, 2, mca=MCA)


def test_switchpoint_table():
    """A measured switchpoint table (the tune report's candidate
    JSON) selects per (op, log2-size, dtype, mesh): the largest log2 <= the
    payload bucket wins, and 'xla' entries fall through."""
    run_ranks("""
    import json, jax.numpy as jnp
    from ompi_tpu.core import cvar, pvar
    path = "/tmp/ompi_tpu_pallas_sw_%d.json" % rank
    with open(path, "w") as f:
        json.dump([
            {"op": "allreduce", "dtype": "float32", "mesh": [size],
             "log2": 0, "algorithm": "linear"},
            {"op": "allreduce", "dtype": "float32", "mesh": [size],
             "log2": 12, "algorithm": "xla"},
        ], f)
    try:
        cvar.set("coll_pallas_switchpoints", path)
        small = jnp.arange(64, dtype=jnp.float32) + rank   # 256 B
        big = jnp.arange(2048, dtype=jnp.float32) + rank   # 8 KiB
        s = pvar.session()
        comm.coll.allreduce_dev(comm, small)
        assert s.read("pallas_linear_bytes") == 64 * 4
        s = pvar.session()
        comm.coll.allreduce_dev(comm, big)
        assert s.read("pallas_fallthrough") == 1
        assert s.read("pallas_launches") == 0
    finally:
        cvar.set("coll_pallas_switchpoints", "")
    """, 2, mca=MCA)


@pytest.mark.parametrize("n", [2, 3])
def test_fused_zero_linear_bit_identical(n):
    """fused=True under deterministic='linear' must reproduce the
    unfused ZeRO cycle bitwise across momentum-carrying steps (n=3
    exercises the padded odd-remainder shard)."""
    run_ranks("""
    import jax.numpy as jnp
    from ompi_tpu.core import pvar
    from ompi_tpu.zero.optimizer import ZeroOptimizer
    rng = np.random.default_rng(7)
    params = {"w": jnp.asarray(rng.standard_normal((3, 5)
                                                   ).astype(np.float32)),
              "b": jnp.asarray(rng.standard_normal((7,)
                                                   ).astype(np.float32))}
    gs = [{"w": jnp.asarray((rng.standard_normal((3, 5)) * 0.3
                             ).astype(np.float32)),
           "b": jnp.asarray((rng.standard_normal((7,)) * 0.3
                             ).astype(np.float32))} for _ in range(2)]
    base = ZeroOptimizer(comm, params, lr=0.1, momentum=0.9,
                         deterministic="linear")
    fused = ZeroOptimizer(comm, params, lr=0.1, momentum=0.9,
                          deterministic="linear", fused=True)
    s = pvar.session()
    for g in gs:
        ref, out = base.step(g), fused.step(g)
        for k in ref:
            assert (np.asarray(ref[k]).view(np.uint32)
                    == np.asarray(out[k]).view(np.uint32)).all(), k
    assert s.read("pallas_fused_launches") >= 2
    mb = np.asarray(base.state.slots["momentum"].shards[0])
    mf = np.asarray(fused.state.slots["momentum"].shards[0])
    assert (mb.view(np.uint32) == mf.view(np.uint32)).all()
    """, n, mca=MCA)


def test_fused_zero_default_equivalent():
    """Default (ring) mode keeps the in-kernel fused epilogue: the
    acceptance bar is numerical equivalence, not bitwise (the single
    fused program may contract multiply-add)."""
    run_ranks("""
    import jax.numpy as jnp
    from ompi_tpu.zero.optimizer import ZeroOptimizer
    rng = np.random.default_rng(3)
    params = {"w": jnp.asarray(rng.standard_normal((4, 4)
                                                   ).astype(np.float32))}
    g = {"w": jnp.asarray((rng.standard_normal((4, 4)) * 0.2
                           ).astype(np.float32))}
    base = ZeroOptimizer(comm, params, lr=0.1, momentum=0.9)
    fused = ZeroOptimizer(comm, params, lr=0.1, momentum=0.9,
                          fused=True)
    for _ in range(2):
        ref, out = base.step(g), fused.step(g)
        np.testing.assert_allclose(np.asarray(ref["w"]),
                                   np.asarray(out["w"]),
                                   rtol=1e-6, atol=1e-6)
    """, 2, mca=MCA)


def test_allgather_matmul():
    run_ranks("""
    import jax.numpy as jnp
    from ompi_tpu.core import pvar
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((4, 6)).astype(np.float32)) \\
        + rank
    w = jnp.asarray(rng.standard_normal((6, 3)).astype(np.float32))
    s = pvar.session()
    out = np.asarray(comm.coll.allgather_matmul_dev(comm, x, w))
    assert out.shape == (4 * size, 3)
    full = np.concatenate(
        [np.asarray(x) - rank + rr for rr in range(size)], axis=0)
    np.testing.assert_allclose(out, full @ np.asarray(w),
                               rtol=1e-5, atol=1e-5)
    assert s.read("pallas_fused_launches") == 1
    # unsupported dtype composes allgather + local matmul (fallback
    # still returns the product, never None)
    xi = jnp.ones((2, 3), jnp.int16)
    wi = jnp.ones((3, 2), jnp.int16)
    s = pvar.session()
    got = np.asarray(comm.coll.allgather_matmul_dev(comm, xi, wi))
    np.testing.assert_array_equal(
        got, np.full((2 * size, 2), 3, np.int16))
    assert s.read("pallas_fallthrough") >= 1
    """, 2, mca=MCA)


def test_trace_span_presence():
    """Launches must show up as coll_pallas spans (with the chosen
    algorithm) in the trace plane's exported timeline."""
    run_ranks("""
    import jax, jax.numpy as jnp
    from ompi_tpu.trace import export as trace_export
    from ompi_tpu.trace import recorder as trace_rec
    x = jnp.arange(128, dtype=jnp.float32) + rank
    comm.coll.allreduce_dev(comm, x)  # compile outside the recording
    trace_rec.enable()
    try:
        jax.block_until_ready(comm.coll.allreduce_dev(comm, x))
    finally:
        rec = trace_rec.disable()
    path = "/tmp/ompi_tpu_pallas_trace_%d.json" % rank
    doc = trace_export.write(path, rec)
    spans = [ev for ev in doc["traceEvents"]
             if ev.get("ph") == "X" and ev.get("cat") == "coll_pallas"]
    assert spans, "no coll_pallas span in the exported timeline"
    assert any(ev.get("args", {}).get("algorithm") in
               ("ring", "bidir", "linear") for ev in spans), spans
    """, 2, mca=MCA)


def test_off_by_default():
    """Without the opt-in the xla providers must be untouched (the
    stacking contract existing provider-asserting tests rely on)."""
    run_ranks("""
    assert comm.coll.providers["allreduce_dev"] == "xla"
    assert "fused_rs_update_dev" not in comm.coll.fns
    """, 2, mca={"device_plane": "on"})


def test_tpu_path_sends_what_vmem_cannot_hold_one_level_down():
    """The DMA kernels keep their operands whole in VMEM. With the TPU
    transport forced and a 4 KiB bound, every entry point that reaches
    one must decline BEFORE it builds a kernel (none compiles on CPU),
    count the fallthrough and still return the right answer."""
    run_ranks("""
    import jax.numpy as jnp
    from ompi_tpu import osc
    from ompi_tpu.coll import pallas as cp, pallas_kernels as K
    from ompi_tpu.coll import xla as cx
    from ompi_tpu.core import pvar
    from ompi_tpu.osc.pallas import PallasWindow
    from ompi_tpu.zero.optimizer import ZeroOptimizer
    # a ring reduce_scatter on four chips holds 2.25x its payload
    assert K.ring_vmem_bytes(4, 4 << 20) == 9 << 20
    assert cp.dma_fits(4096) and not cp.dma_fits(4097)
    s = pvar.session()
    x = jnp.arange(1 << 14, dtype=jnp.float32) + rank
    np.testing.assert_array_equal(
        np.asarray(comm.Allreduce(x, deterministic="ring")),
        np.asarray(cx.allreduce_dev(comm, x, deterministic="ring")))
    assert s.read("pallas_fallthrough") == 1
    params = {"w": jnp.ones((64, 64), jnp.float32)}
    g = {"w": jnp.full((64, 64), rank + 1.0, jnp.float32)}
    outs = []
    for fused in (True, False):
        opt = ZeroOptimizer(comm, params, lr=0.5, momentum=0.5,
                            fused=fused)
        outs.append(np.asarray(opt.step(g)["w"]))
        opt.free()
    np.testing.assert_array_equal(*outs)
    assert s.read("pallas_fallthrough") == 2
    xm = jnp.ones((32, 64), jnp.float32) + rank
    w = jnp.ones((64, 32), jnp.float32)
    got = np.asarray(comm.coll.allgather_matmul_dev(comm, xm, w))
    full = np.concatenate([np.ones((32, 64), np.float32) + r
                           for r in range(size)])
    np.testing.assert_array_equal(got, full @ np.asarray(w))
    assert s.read("pallas_fallthrough") == 3
    assert s.read("pallas_launches") == 0
    # an osc fence round past the bound rides XLA's collective permute
    base = np.zeros(4096, np.float32)
    put = np.arange(2000, dtype=np.float32) + rank
    wd = osc.win_create(comm, jnp.asarray(base), disp_unit=4)
    assert isinstance(wd, PallasWindow) and not wd._interp
    wd.Fence()
    wd.Put(jnp.asarray(put), (rank + 1) % size, disp=7)
    wd.Fence()
    want = base.copy()
    want[7:2007] = np.arange(2000, dtype=np.float32) + (rank - 1) % size
    np.testing.assert_array_equal(np.asarray(wd.array), want)
    assert s.read("osc_pallas_fallthrough") == 1
    wd.Free()
    """, 2, mca={**MCA, "osc_pallas": "on",
                "coll_pallas_interpret": "off",
                "osc_pallas_interpret": "off",
                "coll_pallas_dma_max_bytes": "4096"})
