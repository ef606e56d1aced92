"""The `solar2-train-t8192` cell off the chip: the plain reference's
token-by-token delta rule against a third, dense writing of it; whole
rehearsal runs through the one command (and the same with the timed
path broken underneath, which must come out not correct); the
reference's fp8 control at toy size under the cell's rehearsal limits;
the operation and byte counts against counts by hand; the cell and its
configuration as ISSUE 46 names them; the new readers on a recorded
trace of the cell's step and on a trace that has none of their
names."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from benchmark import check_manifest as cm
from benchmark import flops_solar2, manifest as mf
from benchmark import run as bench_run
from benchmark.common import RESULT_TAG

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "solar2-train-t8192"
CONFIG = "solar-open2-250b"
ONE_STEP = os.path.join(HERE, "data", "solar2_t8192_one_step.xplane.pb")


# -- the reference against a third writing ------------------------------------

def test_the_recurrence_is_the_dense_triangular_system():
    """One head, float64 numpy: with G the cumulative log-decay over
    the WHOLE sequence, A[t, s] = beta_t sum_c k_t[c] k_s[c] exp(G_t[c]
    - G_s[c]) for s < t, U = (I + A)^-1 (beta v) and o = P U with P
    the same sum of q_t and k_s over s <= t — the [T, T] form no
    program here may hold, which a toy can."""
    pytest.importorskip("jax")
    import jax
    import numpy as np

    from benchmark.reference import solar2_decoder as ref

    t, width = 24, 6
    ks = jax.random.split(jax.random.key(4), 5)
    q, k = (jax.random.normal(a, (t, 1, width)) for a in ks[:2])
    k = k / np.sqrt((np.asarray(k) ** 2).sum(-1, keepdims=True))
    v = jax.random.normal(ks[2], (t, 1, width))
    g = -jax.random.uniform(ks[3], (t, 1, width), minval=0.01, maxval=2.0)
    beta = 2 * jax.nn.sigmoid(2 * jax.random.normal(ks[4], (t, 1)))
    with jax.default_matmul_precision("highest"):
        o, last = ref.delta_recurrence(q, k, v, g, beta, block=5)
    qn, kn, vn, gn, bn = (np.asarray(a, np.float64)[:, 0]
                          for a in (q, k, v, g, beta[..., None]))
    cum = np.cumsum(gn, 0)
    e = np.exp(cum[:, None, :] - cum[None, :, :])           # [t, s, c]
    low = np.tril(np.ones((t, t)))
    a = np.tril((kn[:, None, :] * kn[None, :, :] * e).sum(-1), -1) * bn
    p = (qn[:, None, :] * kn[None, :, :] * e).sum(-1) * low
    u = np.linalg.solve(np.eye(t) + a, bn * vn)
    assert np.abs(np.asarray(o[:, 0]) - p @ u).max() < 1e-4
    state = ((kn * np.exp(cum[-1] - cum)).T) @ u
    assert np.abs(np.asarray(last[0]) - state).max() < 1e-4
    # the convolution: tap j reads the token 3 - j places earlier
    import jax.numpy as jnp

    w = jnp.array([[1.0, 10.0, 100.0, 1000.0]])
    seq = jnp.arange(1.0, 6.0)[:, None]
    want = jax.nn.silu(jnp.array([1000.0, 2100.0, 3210.0, 4321.0, 5432.0]))
    assert np.allclose(np.asarray(ref.short_conv(seq, w)[:, 0]),
                       np.asarray(want))


# -- whole rehearsal runs ------------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_run_is_correct_and_claims_no_device_number(trace):
    p = subprocess.run(
        [sys.executable, os.path.join(mf.HERE, "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 17), "--seconds", "2", "--trace", str(trace),
         "--rehearsal", "1"], capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    last = p.stdout.strip().splitlines()[-1]
    res = json.loads(last)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert res["device"]["platform"] == "cpu"
    assert res["metrics"] == {}  # never a rate under a device name
    assert "REHEARSAL counts" in p.stdout
    for name in ("moe_dropped_assignments", "route_counts_short",
                 "nonfinite_window_losses", "seed_tree_remade_gap"):
        assert f"check {name}: 0" in p.stdout
    for name in ("kda_out_gap", "kda_state_gap", "gqa_out_gap",
                 "route_disagreement"):
        assert f"check {name}:" in p.stdout
    for said in ('"kda_layers": 3', '"kda_chunks": 12',
                 '"kda_carry_scan_layers": 3', '"attn_gated_layers": 1'):
        assert said in p.stdout
    errs = cm.check_line(mf.load(), CELL, trace, last)
    assert errs and all("missing" in e or "device lacks" in e
                        for e in errs), errs


@pytest.mark.parametrize("fault, failing", [
    ("decay_ignored", "kda_out_gap"),
    ("correction_left_out", "kda_out_gap"),
    ("kda_gate_left_out", "kda_out_gap"),
    ("carried_state_dropped", "kda_state_gap"),
    ("gqa_gate_left_out", "gqa_out_gap")])
def test_broken_timed_path_comes_out_not_correct(fault, failing, tmp_path):
    class Ns:
        workload, seed, seconds, trace, rehearsal = CELL, 5, 1.0, 0, 1

    argv = bench_run.launcher_argv(Ns, mf.workload_file(CELL), str(tmp_path))
    i = argv.index(os.path.join(mf.HERE, "rank_main.py"))
    argv[i:i + 1] = [os.path.join(HERE, "broken_solar2_rank.py"), fault]
    p = subprocess.run(argv, env=bench_run.child_env(), capture_output=True,
                       text=True, timeout=900, cwd=mf.ROOT)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    line = next(ln for ln in p.stdout.splitlines()
                if ln.startswith(RESULT_TAG))
    assert json.loads(line[len(RESULT_TAG):])["correct"] is False
    assert "NOT CORRECT" in next(
        ln for ln in p.stdout.splitlines()
        if ln.startswith(f"check {failing}:"))


def test_the_cell_is_the_one_the_issue_names():
    manifest = mf.load()
    assert cm.check(manifest) == []
    cell, wl, traffic, config, limits = mf.cell_inputs(manifest, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "b1-t8192", 1)
    assert traffic == {"batch": 1, "seq": 8192, "n_batches": 8, "lr": 0.01,
                       "check_steps": 3, "trace_steps": 5}
    assert wl["runner"] == "solar2_train" and wl["ranks"] == 1
    assert set(limits) == set(wl["rehearsal_limits"]) == {
        "loss_gap", "first_grad_norm_gap", "first_grad_norm_rms_gap",
        "param_change_norm_gap", "router_grad_norm_gap",
        "route_disagreement", "kda_out_gap", "kda_state_gap", "gqa_out_gap"}
    # every key of the catalog's row as it is there, but the cuts
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Solar-Open2-250B")
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"] == config["source"]
    differs = {k for k, v in row["config"].items()
               if config.get(k, "-") != v}
    assert differs == set(entry["reduced"]) == set(config["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert config["published"] == {k: row["config"][k] for k in differs}
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["router_experts"], config["vocab_size"]) == (
        4, 40, 320, 196608 // 8)
    assert config["gqa_layers"] == list(range(0, 48, 4))
    assert set(config["assumed"]["sizes"]) >= {"kda_chunk", "kda_gate_rank"}
    # ten cells or more (a pinned count fails at the next cell: PR 43),
    # one of them on four chips
    assert len(manifest["workloads"]) >= 10
    assert [w["name"] for w in manifest["workloads"] if w["chips"] == 4] \
        == ["osu-allreduce-4rank"]
    # the cell reports every `.train` metric, its own, and the `.moe`
    # ones whose readers read it truthfully (no `qk_rope` scope here)
    names = set(mf.metrics_for(manifest, CELL, 1))
    assert {m["name"] for m in manifest["per_layer"]
            if m["name"].endswith((".train", ".solar", ".moe"))} \
        - {"qk_rope_ms.moe"} | {"init_s", "compile_s"} == names
    assert set(mf.metrics_for(manifest, CELL, 0)) == {"setup_s",
                                                      "tokens_per_s"}


def test_the_seeded_tree_counts_what_the_file_says():
    """3,308,353,344 parameters at the published widths (ISSUE 46's
    table), from the plan's shapes alone."""
    import math

    from benchmark import weights_solar2
    from benchmark.runners import solar2_train as st

    config = mf.load_json("configs", CONFIG + ".json")
    plan = weights_solar2.plan(st.model_sizes(config))

    def count(tree):
        if isinstance(tree, tuple):
            return math.prod(tree[0])
        return sum(count(v) for v in (
            tree.values() if isinstance(tree, dict) else tree))

    gqa, kda = plan["layers"][0], plan["layers"][1]
    experts = sum(count(gqa[n]) for n in (
        "wg", "wg_bias", "w1", "w3", "w2", "ws1", "ws3", "ws2"))
    assert experts == 646_185_280
    assert count(gqa) == 755_245_376 and count(kda) == 783_925_760
    assert count(kda) - experts - 2 * 4096 == 137_732_288
    assert count(plan) == config["parameters"]["total"] == 3_308_353_344


# -- the reference and its control ---------------------------------------------

@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_fp8_control_fails_where_the_program_passes(seed):
    """The comparison of a run at toy size under the cell's rehearsal
    limits: the bfloat16 program passes, the reference with fp8 matmul
    operands does not — by both mixers' outputs, the delta rule's final
    state and the routing (`rehearsal_limits_why`)."""
    pytest.importorskip("jax")
    import jax.numpy as jnp

    from benchmark import compare, weights, weights_solar2
    from benchmark.runners import solar2_train as st

    _, _, traffic, config, limits = mf.cell_inputs(mf.load(), CELL,
                                                   rehearsal=True)
    sizes = st.model_sizes(config)
    n, lr = traffic["check_steps"], traffic["lr"]
    toks, labs = weights.batches(sizes["vocab"], traffic["n_batches"],
                                 traffic["batch"], traffic["seq"], seed)
    params = weights_solar2.device_init(sizes, seed)
    probe = st.probes(sizes, params, toks, n)
    _, program = st.first_steps(st.build_step(sizes, lr), params, toks, labs,
                                sizes, seed, n)
    reference = st.reference_steps(sizes, toks, labs, seed, lr, n)
    first = st.reference_first_batch(sizes, toks, seed)
    said = []
    assert compare.verdict(
        st.checks_against(program, reference, limits, sizes)
        + st.first_batch_checks(probe, first, limits), said.append), said
    fp8 = jnp.float8_e4m3fn
    control = st.reference_steps(sizes, toks, labs, seed, lr, n,
                                 quantize=fp8)
    chosen, (out, last), attn = st.reference_first_batch(sizes, toks, seed,
                                                         fp8)
    broken = {c[0] for c in st.checks_against(control, reference, limits,
                                              sizes)
              + st.first_batch_checks(
                  {"experts": st.chosen_numbers(chosen, sizes["top_k"]),
                   "kda_out": out, "kda_state": last, "gqa_out": attn},
                  first, limits)
              if not compare.holds(c)}
    assert {"kda_out_gap", "kda_state_gap", "gqa_out_gap",
            "route_disagreement"} <= broken


# -- counts ----------------------------------------------------------------------

TOY = {"d_model": 8, "vocab": 100, "n_layers": 4, "gqa_layers": (0,),
       "n_heads": 4, "n_kv_heads": 2, "head_dim": 3, "kda_heads": 2,
       "kda_head_dim": 3, "kda_conv": 4, "kda_chunk": 4, "kda_rank": 3,
       "moe_d_ff": 5, "n_shared_experts": 1, "n_experts": 16}


def test_flops_and_bytes_against_a_hand_count():
    fl = flops_solar2
    assert fl.layer_counts(TOY) == {"G": 1, "K": 3}
    assert fl.kda_width(TOY) == 6
    # four of 8 x 6, two bottlenecks 3 x (8 + 6), w_b 8 x 2
    assert fl.kda_proj_params(TOY) == 4 * 48 + 2 * 42 + 16
    # wq, wa, wo 8 x 12 each; wk, wv 8 x 6 each
    assert fl.attention_params(TOY) == 3 * 96 + 2 * 48
    # 8 tokens in 2 chunks of 4: 8 x 5 / 2 = 20 causal pairs a head;
    # the pair sums 2 x 2 x 3, the system 2 x (3 + 3), the pairs applied
    # 2 x 3 a pair; the carry's three products 3 x 2 x 3 x 3 a token
    assert fl.core_flops_forward(TOY, 8) == 2 * (20 * (12 + 12 + 6)
                                                 + 8 * 54)
    assert fl.kda_core_flops_per_step(TOY, 3, 8) == 3 * 2064 * 3 * 3
    # a token: q, k, v, g = 24 and beta = 2 numbers and o = 6 forward;
    # those, do and five cotangents backward
    assert fl.kda_core_bytes_per_step(TOY, 3, 8) == (
        (26 + 6) + (26 + 6 + 26)) * 2 * 24 * 3
    assert fl.kda_conv_flops_per_step(TOY, 3, 8) == 3 * 2 * 4 * 18 * 24 * 3
    # 36 causal pairs x (QK^T + PV) x 4 heads x 3 wide x 2
    assert fl.gqa_attn_flops_per_step(TOY, 3, 8) == 3 * 48 * 36 * 3
    assert fl.expert_flops_per_step(TOY, 7) == 6 * 3 * 8 * 5 * 7
    # 3 x 292 + 384 + 4 x (router 128 + shared 120) + head 800
    assert fl.dense_params_per_token(TOY) == 876 + 384 + 992 + 800
    assert fl.train_flops_per_step(TOY, 3, 8, 7) == (
        6 * 3052 * 24 + 55728 + 31104 + 15552 + 5040)


def test_flops_of_the_committed_cell():
    from benchmark.runners import solar2_train as st

    sizes = st.model_sizes(mf.load_json("configs", CONFIG + ".json"))
    fl = flops_solar2
    assert fl.kda_proj_params(sizes) == 4 * 33_554_432 + 3_145_728 + 262_144
    assert fl.attention_params(sizes) == 109_051_904
    per_token = fl.core_flops_forward(sizes, 8192) / 8192
    assert 8.8e6 < per_token < 9.0e6
    held = 8192 * 8 * 40 // 320 * 4  # an eighth of the assignments
    step = fl.train_flops_per_step(sizes, 1, 8192, held)
    assert 40.5e12 < step < 41.5e12
    forward = step / 3 / 8192
    assert 1.66e9 < forward < 1.70e9  # ISSUE 46: 1.68 GFLOP a token
    kda = 3 * (6 * fl.kda_proj_params(sizes) * 8192) \
        + fl.kda_core_flops_per_step(sizes, 1, 8192) \
        + fl.kda_conv_flops_per_step(sizes, 1, 8192)
    assert 0.50 < kda / step < 0.52
    assert 0.20 < (6 * fl.attention_params(sizes) * 8192
                   + fl.gqa_attn_flops_per_step(sizes, 1, 8192)) / step < 0.22
    assert 0.075 < fl.gqa_attn_flops_per_step(sizes, 1, 8192) / step < 0.085
    # the core is bound by memory: its bytes at 819 GB/s outlast its
    # operations at 197 TFLOP/s
    assert fl.kda_core_bytes_per_step(sizes, 1, 8192) / 819e9 \
        > fl.kda_core_flops_per_step(sizes, 1, 8192) / 197e12


# -- the readers -----------------------------------------------------------------

def test_every_new_reader_gives_nothing_without_a_trace():
    """As on a parent commit whose run has no such scope and no probe."""
    solar = [m for m in mf.load()["per_layer"]
             if m["name"].endswith(".solar")]
    assert len(solar) == 11
    for m in solar:
        assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
        reader = importlib.import_module(
            "benchmark.layer_metrics." + mf.reader_name(m["name"]))
        assert reader.read({"spans": {}, "counters": {}, "facts": {},
                            "trace": None, "peaks": None,
                            "ranks": 1}) is None, m["name"]


def test_solar_parts_of_a_trace_without_them_are_nothing():
    """OLMoE's step (a program without the configuration) has none of
    the names."""
    from benchmark.layer_metrics import _program, _solar

    old = os.path.join(HERE, "data", "olmoe_t4096_one_step.xplane.pb")
    assert _solar.busy_ms(_program.load(old)) is None


def test_the_readers_on_the_cells_recorded_step():
    """One traced step of the cell on the chip (PERF.md 5, PR 46; cut
    by tools/cut_program_trace.py at `train:3`, then thinned to the
    launch and to layer 1's ops of 20 us and more — the whole step is
    34,000 ops and 10 MB —, which hold 192.9 of that layer's 197.6 ms
    under `kda`): the four parts lie inside `kda`, add up to it but for
    what overlaps, and the carry's kernels are under `kda_core`."""
    from benchmark.layer_metrics import _program, _solar

    events = _program.load(ONE_STEP)
    parts = _solar.busy_ms(events)
    assert parts is not None and set(parts) == set(_solar.PARTS)
    inner = sum(parts[p] for p in _solar.PARTS if p != "kda")
    assert 0 < parts["kda_core"] < parts["kda"]
    assert parts["kda"] == pytest.approx(192.85, rel=1e-3)
    assert parts["kda_core"] == pytest.approx(117.97, rel=1e-3)
    assert 0.98 * parts["kda"] <= inner <= 1.02 * parts["kda"]
    names = {e.name for lines in events["chips"].values()
             for evs in lines.values() for e in evs}
    assert any("kda_carry_fwd" in n for n in names)
    assert any("kda_carry_bwd" in n for n in names)
