"""serve/ — production-skew MoE serving plane.

The dispatch-policy contracts (ISSUE 17 acceptance bar): ``drop`` is
bit-identical to the training ``moe_ffn`` path, ``reroute`` conserves
tokens (nothing lost, nothing duplicated), ``dcn_overflow`` bytes are
budget-bounded and attributed to the DCN level, the Zipf generator is
deterministic under a fixed seed, and a bad policy name surfaces as
``MPIError(ERR_ARG)`` at the first dispatch — every dispatch, never
cached.
"""

import numpy as np
import pytest

from ompi_tpu import errors
from ompi_tpu.monitoring import matrix as _matrix, merge as _merge
from ompi_tpu.monitoring import report as _report
from ompi_tpu.serve import ZipfTraffic, run_decode
from tests.harness import run_ranks

_MCA = {"device_plane": "on"}


# ---------------------------------------------------------------------------
# traffic generator (in-process)


def test_zipf_deterministic_under_seed():
    a = ZipfTraffic(8, 32, hotness=1.3, seed=11)
    b = ZipfTraffic(8, 32, hotness=1.3, seed=11)
    for _ in range(3):
        ia, xa = a.request(64)
        ib, xb = b.request(64)
        np.testing.assert_array_equal(ia, ib)
        assert (xa.view(np.uint32) == xb.view(np.uint32)).all()
    c = ZipfTraffic(8, 32, hotness=1.3, seed=12)
    assert not np.array_equal(c.expert_ids(64), a.expert_ids(64))


def test_zipf_routes_to_drawn_expert_and_hotness_dial():
    tr = ZipfTraffic(8, 32, hotness=1.2, seed=5)
    ids, x = tr.request(256)
    np.testing.assert_array_equal(np.argmax(x @ tr.wg, -1), ids)
    # the dial: hotter alpha concentrates load on the hot expert
    share = []
    for alpha in (0.0, 1.0, 2.0):
        t = ZipfTraffic(8, 32, hotness=alpha, seed=9)
        ids = t.expert_ids(4096)
        share.append(np.mean(ids == t.hot_expert))
    assert share[0] < share[1] < share[2]
    assert share[2] > 0.5  # alpha=2 is a genuinely hot expert


def test_zipf_bad_config_err_arg():
    with pytest.raises(errors.MPIError) as ei:
        ZipfTraffic(16, 8)  # more experts than router dims
    assert ei.value.error_class == errors.ERR_ARG


# ---------------------------------------------------------------------------
# dispatch policies (multi-rank device plane)


def test_drop_bitwise_equal_to_moe_ffn():
    """policy='drop' through the Dispatcher must reproduce the
    training moe_ffn program bit for bit — same op sequence, the
    stats tail must not perturb the output graph."""
    run_ranks("""
    import jax, jax.numpy as jnp
    from ompi_tpu.coll import xla as cx
    from ompi_tpu.core import pvar
    from ompi_tpu.ops import moe
    from ompi_tpu.serve import Dispatcher, ZipfTraffic
    e_local, d, f = 2, 32, 16
    tr = ZipfTraffic(e_local * size, d, hotness=1.2, seed=3)
    rng = np.random.default_rng(100 + rank)
    w1 = rng.standard_normal((e_local, d, f)).astype(np.float32)
    w2 = rng.standard_normal((e_local, f, d)).astype(np.float32)
    ids, x = tr.request(32)

    ctx = cx._ctx(comm)
    def body(xb, wgb, w1b, w2b):
        return moe.moe_ffn(xb[0], wgb[0], w1b[0], w2b[0], cx.AXIS)
    fn = ctx.smap(body, out_varying=True)
    ref = np.asarray(ctx.my_shard(fn(
        ctx.to_global(jnp.asarray(x)),
        ctx.to_global(jnp.asarray(tr.wg)),
        ctx.to_global(jnp.asarray(w1)),
        ctx.to_global(jnp.asarray(w2)))))

    disp = Dispatcher(comm, tr.wg, w1, w2)
    s = pvar.session()
    out, info = disp(x)
    out = np.asarray(out)
    assert (out.view(np.uint32) == ref.view(np.uint32)).all()
    assert info["policy"] == "drop"
    assert info["tokens"] == 32
    assert info["kept"] + info["dropped"] == 32
    assert info["rerouted"] == 0 and info["multi_assigned"] == 0
    assert info["dropped"] > 0  # skewed traffic must overflow
    assert s.read("serve_tokens") == 32
    assert s.read("serve_dropped_tokens") == info["dropped"]
    # second dispatch reuses the compiled program (one _Ctx cache
    # entry per (policy, mesh, capacity) — the tentpole contract)
    s2 = pvar.session()
    disp(x)
    assert s2.read("coll_xla_cache_hits") >= 1
    assert s2.read("coll_xla_cache_misses") == 0
    """, 4, mca=_MCA)


def test_reroute_conserves_tokens():
    """reroute: every overflow token lands on exactly one free slot
    of a least-loaded expert or stays dropped — kept + rerouted +
    dropped == tokens, and no token is ever double-assigned."""
    run_ranks("""
    from ompi_tpu.core import pvar
    from ompi_tpu.serve import Dispatcher, ZipfTraffic
    e_local, d, f = 2, 32, 16
    tr = ZipfTraffic(e_local * size, d, hotness=1.5, seed=4)
    rng = np.random.default_rng(100 + rank)
    w1 = rng.standard_normal((e_local, d, f)).astype(np.float32)
    w2 = rng.standard_normal((e_local, f, d)).astype(np.float32)
    disp = Dispatcher(comm, tr.wg, w1, w2, policy="reroute")
    drop = Dispatcher(comm, tr.wg, w1, w2, policy="drop")
    s = pvar.session()
    total_rr = 0
    for i in range(3):
        ids, x = tr.request(32)
        out, info = disp(x)
        assert info["kept"] + info["rerouted"] + info["dropped"] \\
            == info["tokens"] == 32, info
        assert info["multi_assigned"] == 0, info
        _, dinfo = drop(x)
        # reroute can only serve MORE tokens than drop, via overflow
        assert info["kept"] == dinfo["kept"]
        assert info["rerouted"] + info["kept"] >= dinfo["kept"]
        total_rr += info["rerouted"]
    assert total_rr > 0  # the hot expert must overflow into reroutes
    assert s.read("serve_rerouted_tokens") == total_rr
    """, 4, mca=_MCA)


def test_dcn_overflow_bounded_and_attributed():
    """dcn_overflow on a 2x2 grid: slices are expert replicas;
    overflow ships over the DCN level, byte-metered into the hier
    table, and the serve_dcn_budget_bytes cvar bounds the shipped
    bytes (overflow past it drops — the link-cost-aware decision)."""
    run_ranks("""
    from ompi_tpu.core import cvar, pvar
    from ompi_tpu.monitoring import matrix as _matrix
    from ompi_tpu.serve import Dispatcher, ZipfTraffic
    e_local, d, f, t = 2, 16, 8, 32
    n_ici = 2
    # replica weights: same experts at the same ICI position of
    # every slice (rank 0 pairs with 2, 1 with 3 on the 2x2 grid)
    tr = ZipfTraffic(e_local * n_ici, d, hotness=1.5, seed=6)
    rng = np.random.default_rng(200 + rank % n_ici)
    w1 = rng.standard_normal((e_local, d, f)).astype(np.float32)
    w2 = rng.standard_normal((e_local, f, d)).astype(np.float32)
    disp = Dispatcher(comm, tr.wg, w1, w2, policy="dcn_overflow")
    ids, x = tr.request(t)
    s = pvar.session()
    out, info = disp(x)
    out = np.asarray(out)
    assert info["kept"] + info["dropped"] + info["dcn_tokens"] == t
    assert info["dcn_tokens"] > 0  # skew must overflow to the replica
    assert info["dropped"] == 0    # unbounded budget serves them all
    assert s.read("serve_dcn_overflow_tokens") == info["dcn_tokens"]
    assert s.read("serve_dcn_overflow_bytes") == info["dcn_bytes"]
    # attribution: the DCN level of the hier table carries the bytes
    tm = _matrix.TRAFFIC
    assert tm is not None
    rec = tm.hier_levels["serve_overflow"]
    assert rec[2] == info["dcn_bytes"] and rec[1] == 0.0
    # every token served: the output IS its picked expert's FFN
    gates = np.exp((x @ tr.wg) - (x @ tr.wg).max(-1, keepdims=True))
    gates = gates / gates.sum(-1, keepdims=True)
    oracle = np.zeros_like(x)
    for i in range(t):
        e = int(ids[i])
        r2 = np.random.default_rng(200 + e // e_local)
        w1e = r2.standard_normal((e_local, d, f)).astype(np.float32)
        w2e = r2.standard_normal((e_local, f, d)).astype(np.float32)
        h = np.maximum(x[i] @ w1e[e % e_local], 0.0)
        oracle[i] = gates[i, e] * (h @ w2e[e % e_local])
    np.testing.assert_allclose(out, oracle, rtol=1e-4, atol=1e-5)
    # budget: bound the remote leg to ~half the overflow
    cost = (d + 2 + d) * 4
    budget = max((info["dcn_tokens"] // 2), 1) * cost
    try:
        cvar.set("serve_dcn_budget_bytes", budget)
        s2 = pvar.session()
        _, binfo = disp(x)
        assert binfo["dcn_bytes"] <= budget
        assert binfo["dcn_tokens"] < info["dcn_tokens"]
        assert binfo["dropped"] > 0  # past-budget overflow drops
        assert binfo["kept"] + binfo["dropped"] \\
            + binfo["dcn_tokens"] == t
    finally:
        cvar.set("serve_dcn_budget_bytes", 0)
    """, 4, mca={"device_plane": "on", "coll_hier_split": "2x2",
                 "monitoring_level": "1"})


def test_bad_policy_err_arg_at_first_dispatch_uncached():
    run_ranks("""
    from ompi_tpu import errors
    from ompi_tpu.serve import Dispatcher, ZipfTraffic
    tr = ZipfTraffic(2 * size, 16, seed=1)
    rng = np.random.default_rng(0)
    w1 = rng.standard_normal((2, 16, 8)).astype(np.float32)
    w2 = rng.standard_normal((2, 8, 16)).astype(np.float32)
    disp = Dispatcher(comm, tr.wg, w1, w2, policy="drp")  # typo
    ids, x = tr.request(8)
    for _ in range(2):  # raises EVERY dispatch — never cached
        try:
            disp(x)
        except errors.MPIError as e:
            assert e.error_class == errors.ERR_ARG
            assert "drp" in str(e)
        else:
            raise AssertionError("bad policy accepted")
    disp.policy = "drop"  # config fixed at runtime -> serves
    out, info = disp(x)
    assert info["tokens"] == 8
    """, 4, mca=_MCA)


def test_router_width_mismatch_err_arg():
    # flat policies expect e_local * size router columns; dcn_overflow
    # expects e_local * n_ici (slices are replicas). Either mismatch
    # must be a named ERR_ARG, not a traced reshape error.
    run_ranks("""
    from ompi_tpu import errors
    from ompi_tpu.serve import Dispatcher, ZipfTraffic
    rng = np.random.default_rng(0)
    w1 = rng.standard_normal((2, 16, 8)).astype(np.float32)
    w2 = rng.standard_normal((2, 8, 16)).astype(np.float32)
    tr_small = ZipfTraffic(2, 16, seed=1)       # 2 != 2 * size
    ids, x = tr_small.request(8)
    try:
        Dispatcher(comm, tr_small.wg, w1, w2, policy="drop")(x)
    except errors.MPIError as e:
        assert e.error_class == errors.ERR_ARG
        assert "router" in str(e) and "comm.size" in str(e)
    else:
        raise AssertionError("narrow router accepted by drop")
    tr_flat = ZipfTraffic(2 * size, 16, seed=1)  # flat width, not n_ici
    ids, x = tr_flat.request(8)
    try:
        Dispatcher(comm, tr_flat.wg, w1, w2, policy="dcn_overflow")(x)
    except errors.MPIError as e:
        assert e.error_class == errors.ERR_ARG
        assert "n_ici" in str(e)
    else:
        raise AssertionError("flat-width router accepted by dcn")
    """, 4, mca=dict(_MCA, coll_hier_split="2x2"))


def test_dcn_overflow_without_grid_err_arg():
    run_ranks("""
    from ompi_tpu import errors
    from ompi_tpu.serve import Dispatcher, ZipfTraffic
    tr = ZipfTraffic(2 * size, 16, seed=1)
    rng = np.random.default_rng(0)
    w1 = rng.standard_normal((2, 16, 8)).astype(np.float32)
    w2 = rng.standard_normal((2, 8, 16)).astype(np.float32)
    disp = Dispatcher(comm, tr.wg, w1, w2, policy="dcn_overflow")
    ids, x = tr.request(8)
    try:
        disp(x)
    except errors.MPIError as e:
        assert e.error_class == errors.ERR_ARG
    else:
        raise AssertionError("dcn_overflow served without a grid")
    """, 4, mca=_MCA)


# ---------------------------------------------------------------------------
# decode loop + [serve] report section (in-process)


class _FakeDispatcher:
    policy = "drop"

    def __call__(self, x):
        t = len(x)
        drop = t // 4
        return np.zeros_like(x), {
            "policy": self.policy, "tokens": t, "kept": t - drop,
            "rerouted": 0, "dropped": drop, "multi_assigned": 0,
            "dcn_tokens": 0, "dcn_bytes": 0,
            "counts": [3 * t // 4, t // 8, t // 8]}


def test_run_decode_tail_latency_summary():
    tr = ZipfTraffic(3, 8, hotness=1.1, seed=2)
    res = run_decode(_FakeDispatcher(), tr, n_requests=16,
                     tokens_per_request=8, warmup=1)
    assert res["requests"] == 16 and res["tokens"] == 128
    assert res["dropped"] == 32 and res["drop_rate"] == 0.25
    # the tail is ordered and distinct from throughput
    assert 0 < res["p50_ms"] <= res["p95_ms"] <= res["p99_ms"]
    assert res["tokens_per_s"] > 0
    assert res["hot_expert"] == 0 and res["hot_share"] >= 0.5


def test_serve_report_section_names_hot_expert():
    tm = _matrix.TrafficMatrix(rank=0, level=1, nranks=1)
    tm.serve_event("reroute", tokens=256, kept=200, rerouted=40,
                   dropped=16, dcn_tokens=0, dcn_bytes=0)
    tm.serve_event("reroute", requests=8, lat_ns=2_000_000)
    tm.serve_event("reroute", requests=8, lat_ns=9_000_000)
    tm.expert_tokens([200, 16, 24, 16])
    merged = _merge.merge([_merge.snapshot_doc(tm)])
    assert merged["serve"]["reroute"]["tokens"] == 256
    assert merged["serve"]["reroute"]["requests"] == 16
    text = _report.render(merged)
    assert "[serve] policy reroute" in text
    assert "rerouted 40" in text
    assert "~p99" in text and "~p50" in text
    assert "hot expert: e0" in text  # named, with its share
    assert "78.1% of routed tokens" in text
    assert "HOT" in text
    # round-trips through JSON (the dump/report CLI path)
    import json
    merged2 = _merge.merge([json.loads(json.dumps(
        _merge.snapshot_doc(tm)))])
    assert _report.render(merged2) == text
