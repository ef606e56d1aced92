"""The `mellum2-train-t16384` cell off the chip: the plain reference
against a third, naive writing (a Python loop over the queries for the
window, YaRN by the formula pair by pair); whole rehearsal runs through
the one command (and the same with the timed path broken underneath,
which must come out not correct); the reference's fp8 control at toy
size under the cell's rehearsal limits; the operation counts against
counts by enumeration; the cell and its configuration as ISSUE 43 names
them; the new readers without a trace, on a trace of another model and
on one step cut from the cell's own."""

import importlib
import json
import math
import os
import subprocess
import sys

import pytest

from benchmark import check_manifest as cm
from benchmark import flops_mellum2, manifest as mf
from benchmark import run as bench_run
from benchmark.common import RESULT_TAG

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "mellum2-train-t16384"
CONFIG = "mellum2-12b-a2.5b"
SLIDING, FULL = "sliding_attention", "full_attention"


# -- the reference against a third writing ------------------------------------

def _naive_attention(lp, x, kind, spec):
    """One sequence x [T, d] in float64, every (head, query) in a loop of
    its own: the keys a query sees listed one by one, each pair of a
    head's dimensions turned by its own angle."""
    import numpy as np

    t, h, kv = x.shape[0], spec.n_heads, spec.n_kv_heads
    dh = lp["wq"].shape[1] // h
    half = dh // 2
    q = (x @ lp["wq"]).reshape(t, h, dh)
    k = (x @ lp["wk"]).reshape(t, kv, dh)
    v = (x @ lp["wv"]).reshape(t, kv, dh)
    theta, factor, original, fast, slow, attention_factor = spec.yarn

    def angle(pair: int) -> float:
        e = theta ** (-pair / half) if kind == FULL \
            else spec.sliding_theta ** (-pair / half)
        if kind == SLIDING:
            return e

        def corr(n):
            return dh * math.log(original / (2 * math.pi * n)) \
                / (2 * math.log(theta))

        low = max(math.floor(corr(fast)), 0)
        high = min(math.ceil(corr(slow)), dh - 1)
        r = min(max((pair - low) / (high - low), 0.0), 1.0)
        return e * (1 - r) + e / factor * r

    scale = attention_factor if kind == FULL else 1.0

    def turned(vec, pos):
        out = np.empty(dh)
        for i in range(half):
            c, s = (scale * f(pos * angle(i)) for f in (math.cos, math.sin))
            out[i] = vec[i] * c - vec[i + half] * s
            out[i + half] = vec[i + half] * c + vec[i] * s
        return out

    o = np.zeros((t, h, dh))
    for i in range(h):
        mine = i // (h // kv)
        for at in range(t):
            first = max(0, at - spec.window + 1) if kind == SLIDING else 0
            keys = list(range(first, at + 1))
            s = np.array([turned(q[at, i], at) @ turned(k[j, mine], j)
                          for j in keys]) / math.sqrt(dh)
            p = np.exp(s - s.max())
            o[at, i] = (p / p.sum()) @ v[keys, mine]
    return o.reshape(t, h * dh) @ lp["wo"]


@pytest.mark.parametrize("kind", [SLIDING, FULL])
def test_the_reference_is_the_naive_writing(kind):
    pytest.importorskip("jax")
    import jax
    import numpy as np

    from benchmark import weights_mellum2
    from benchmark.reference import mellum2_decoder as ref
    from benchmark.runners import mellum2_train as mt

    config = mf.load_json("configs", CONFIG + ".rehearsal.json")
    sizes = mt.model_sizes(dict(config, param_dtype="float32"))
    spec = mt.reference_spec(sizes)._replace(q_rows=8)
    lp = weights_mellum2.device_init(sizes, 3)["layers"][0]
    x = jax.random.normal(jax.random.key(9), (1, 40, sizes["d_model"]))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.attention(lp, x, kind, spec)[0], np.float64)
    want = _naive_attention(
        {k: np.asarray(v, np.float64) for k, v in lp.items()
         if k in ("wq", "wk", "wv", "wo")}, np.asarray(x[0], np.float64),
        kind, spec)
    assert np.abs(got - want).max() <= 2e-5 * np.abs(want).max()
    # and the two kinds differ: the window is 16 of 40 keys, YaRN slows
    other = SLIDING if kind == FULL else FULL
    with jax.default_matmul_precision("highest"):
        far = np.asarray(ref.attention(lp, x, other, spec)[0])
    assert np.abs(far - want).max() > 0.05 * np.abs(want).max()


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
                 "nonfinite_window_losses", "seed_tree_remade_gap",
                 "window_leak_rows", "window_edge_missed"):
        assert f"check {name}: 0" in p.stdout
    for name in ("swa_out_gap", "full_out_gap", "route_disagreement"):
        assert f"check {name}:" in p.stdout
    assert "'window_edge_seen': 3, 'window_edge_wanted': 3" in p.stdout
    assert '"attn_window_layers": 3, "attn_full_layers": 1' in p.stdout
    errs = cm.check_line(mf.load(), CELL, trace, last)
    assert errs and all("missing" in e or "device lacks" in e
                        for e in errs), errs


@pytest.mark.parametrize("fault, failing", [
    ("window_ignored", "swa_out_gap"),
    ("yarn_left_out", "full_out_gap"),
    ("factor_left_out", "full_out_gap")])
def test_broken_timed_path_comes_out_not_correct(fault, failing, tmp_path):
    class Ns:
        workload, seed, seconds, trace, rehearsal = CELL, 5, 1.0, 0, 1

    argv = bench_run.launcher_argv(Ns, mf.workload_file(CELL), str(tmp_path))
    i = argv.index(os.path.join(mf.HERE, "rank_main.py"))
    argv[i:i + 1] = [os.path.join(HERE, "broken_mellum2_rank.py"), fault]
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
        CONFIG, "b1-t16384", 1)
    assert traffic == {"batch": 1, "seq": 16384, "n_batches": 8, "lr": 0.01,
                       "check_steps": 3, "trace_steps": 5}
    assert wl["runner"] == "mellum2_train" and wl["ranks"] == 1
    assert set(limits) == set(wl["rehearsal_limits"]) == {
        "loss_gap", "first_grad_norm_gap", "first_grad_norm_rms_gap",
        "param_change_norm_gap", "router_grad_norm_gap",
        "route_disagreement", "swa_out_gap", "full_out_gap"}
    # every key of the catalog's row as it is there, but the cuts
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Mellum2-12B-A2.5B-Instruct")
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"] == config["source"]
    differs = {k for k, v in row["config"].items()
               if config.get(k, "-") != v}
    # the two lists of layer kinds are kept whole; the depth says how
    # many of their entries the cell holds
    assert differs == set(entry["reduced"]) == set(config["reduced"]) \
        == {"num_hidden_layers", "vocab_size"}
    from benchmark.runners import mellum2_train as mt

    assert mt.model_sizes(config)["layer_types"] == (SLIDING,) * 3 + (FULL,)
    assert config["mlp_layer_types"] == ["sparse"] * 28
    assert (config["published"]["num_hidden_layers"],
            config["published"]["vocab_size"]) == (28, 98304)
    assert (config["num_hidden_layers"], config["vocab_size"],
            config["num_experts"], config["router_experts"]) == (
                4, 98304 // 4, 64, 64)
    assert config["rope_parameters"] == row["config"]["rope_parameters"]
    for reason in ("deployment", "depth", "optimizer", "precision", "qk_norm",
                   "mtp_head", "router_losses", "sequence"):
        assert len(config["assumed"][reason]) > 40
    # one more cell, and still one on four chips
    assert len(manifest["workloads"]) >= 9
    assert [w["name"] for w in manifest["workloads"] if w["chips"] == 4] \
        == ["osu-allreduce-4rank"]
    # the cell reports every `.train` metric, every `.moe` one and its own
    names = set(mf.metrics_for(manifest, CELL, 1))
    assert {m["name"] for m in manifest["per_layer"]
            if m["name"].endswith((".train", ".mellum", ".moe"))} \
        | {"init_s", "compile_s"} == names
    assert set(mf.metrics_for(manifest, CELL, 0)) == {"setup_s",
                                                      "tokens_per_s"}


# -- the reference and its control ---------------------------------------------

@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_fp8_control_fails_where_the_program_passes(seed):
    """The comparison of a run at toy size under the cell's rehearsal
    limits: the bfloat16 program passes, the reference with fp8 matmul
    operands does not (`rehearsal_limits_why` says by which)."""
    pytest.importorskip("jax")
    import jax.numpy as jnp

    from benchmark import compare, weights, weights_mellum2
    from benchmark.runners import mellum2_train as mt

    _, _, traffic, config, limits = mf.cell_inputs(mf.load(), CELL,
                                                   rehearsal=True)
    sizes = mt.model_sizes(config)
    n, lr = traffic["check_steps"], traffic["lr"]
    toks, labs = weights.batches(sizes["vocab"], traffic["n_batches"],
                                 traffic["batch"], traffic["seq"], seed)
    params = weights_mellum2.device_init(sizes, seed)
    probe = mt.probes(sizes, params, toks, n, seed)
    _, program = mt.first_steps(mt.build_step(sizes, lr), params, toks, labs,
                                sizes, seed, n)
    reference = mt.reference_steps(sizes, toks, labs, seed, lr, n)
    first = mt.reference_first_batch(sizes, toks, seed)
    said = []
    assert compare.verdict(
        mt.checks_against(program, reference, limits, sizes)
        + mt.first_batch_checks(probe, first, limits), said.append), said
    fp8 = jnp.float8_e4m3fn
    control = mt.reference_steps(sizes, toks, labs, seed, lr, n,
                                 quantize=fp8)
    chosen, swa, full = mt.reference_first_batch(sizes, toks, seed, fp8)
    broken = {c[0] for c in mt.checks_against(control, reference, limits,
                                              sizes)
              + mt.first_batch_checks(
                  {"experts": mt.chosen_numbers(chosen, sizes["top_k"]),
                   "swa_out": swa, "full_out": full}, first, limits)
              if not compare.holds(c)}
    assert "swa_out_gap" in broken


# -- counts ----------------------------------------------------------------------

TOY = {"d_model": 8, "vocab": 100, "n_layers": 4,
       "layer_types": (SLIDING, SLIDING, SLIDING, FULL), "n_heads": 4,
       "n_kv_heads": 2, "head_dim": 3, "window": 5, "moe_d_ff": 6,
       "n_experts": 16, "top_k": 2}


@pytest.mark.parametrize("seq, window", [(12, 5), (12, 1), (4, 5), (5, 5),
                                         (16384, 1024)])
def test_the_pairs_a_window_keeps_by_enumeration(seq, window):
    fl = flops_mellum2
    if seq <= 64:
        kept = sum(1 for t in range(seq) for s in range(seq)
                   if s <= t and s > t - window)
        assert fl.window_pairs(seq, window) == kept
        assert fl.causal_pairs(seq) == sum(
            1 for t in range(seq) for s in range(seq) if s <= t)
    else:
        assert fl.window_pairs(seq, window) == sum(
            min(t + 1, window) for t in range(seq)) == 16_253_440
        assert fl.causal_pairs(seq) == 134_225_920


def test_flops_against_a_hand_count():
    fl = flops_mellum2
    # wq, wo 8 x 12 each; wk, wv 8 x 6 each
    assert fl.attention_params(TOY) == 2 * 96 + 2 * 48
    assert fl.expert_params_per_token(TOY) == 2 * 3 * 8 * 6
    # 12 tokens under a window of 5: 15 + 7 x 5 = 50 pairs a layer;
    # (QK^T + PV) x 4 heads x 3 wide x 2 = 48 a pair
    assert fl.swa_attn_flops_per_step(TOY, 3, 12) == 3 * 48 * 50 * 3 * 3
    assert fl.full_attn_flops_per_step(TOY, 3, 12) == 3 * 48 * 78 * 3 * 1
    assert fl.expert_flops_per_step(TOY, 3, 12) == 6 * 4 * 288 * 36
    # a layer 288 + router 128 + experts 288, four of them; head 800
    assert fl.matmul_params_per_token(TOY) == 4 * 704 + 800
    assert fl.train_flops_per_step(TOY, 3, 12) == (
        6 * 3616 * 36 + 64800 + 33696)


def test_flops_of_the_committed_cell():
    """ISSUE 43's table: MFLOP a token forward, 42.4 TFLOP a step."""
    from benchmark.runners import mellum2_train as mt

    sizes = mt.model_sizes(mf.load_json("configs", CONFIG + ".json"))
    fl, t = flops_mellum2, 16384
    assert fl.attention_params(sizes) == 21_233_664
    assert 2 * fl.attention_params(sizes) / 1e6 == pytest.approx(42.5, 1e-3)
    assert 2 * fl.expert_params_per_token(sizes) / 1e6 == pytest.approx(
        99.1, 1e-3)
    full = fl.full_attn_flops_per_step(sizes, 1, t) / 3 / t / 1e6
    swa = fl.swa_attn_flops_per_step(sizes, 1, t) / 3 / t / 1e6
    assert full == pytest.approx(134.2, 1e-3)
    assert swa / 3 == pytest.approx(16.3, 3e-3)
    step = fl.train_flops_per_step(sizes, 1, t)
    assert step / 3 / t / 1e6 == pytest.approx(863.6, 1e-3)
    assert 42.3e12 < step < 42.6e12
    assert fl.full_attn_flops_per_step(sizes, 1, t) / step \
        == pytest.approx(0.155, 0.01)
    assert fl.swa_attn_flops_per_step(sizes, 1, t) / step \
        == pytest.approx(0.056, 0.02)
    assert fl.expert_flops_per_step(sizes, 1, t) / step \
        == pytest.approx(0.459, 0.01)


# -- the readers -------------------------------------------------------------------

def _mellum_metrics():
    return [m for m in mf.load()["per_layer"]
            if m["name"].endswith(".mellum")]


def test_every_new_reader_gives_nothing_without_a_trace():
    """As on a parent commit whose run has no such scope and counter."""
    mine = _mellum_metrics()
    assert [m["name"].split(".")[0] for m in mine] == [
        "swa_attn_ms", "full_attn_ms", "swa_attn_roofline",
        "full_attn_roofline", "swa_visited_share", "swa_share"]
    for m in mine:
        assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
        reader = importlib.import_module(
            "benchmark.layer_metrics." + mf.reader_name(m["name"]))
        assert reader.read({"spans": {}, "counters": {}, "facts": {},
                            "trace": None, "peaks": None,
                            "ranks": 1}) is None, m["name"]


def test_mellum_parts_of_a_trace_without_them_are_nothing():
    """OLMoE's step (a program without the configuration) has neither
    name."""
    from benchmark.layer_metrics import _mellum, _program

    old = os.path.join(HERE, "data", "olmoe_t4096_one_step.xplane.pb")
    assert _mellum.busy_ms(_program.load(old)) is None


@pytest.mark.parametrize("tile, walked, share", [
    (1024, 31, 2.00), (512, 93, 1.50), (256, 310, 1.25)])
def test_the_visited_share_from_the_rules_counters(tile, walked, share):
    """Three windowed layers' counters as the step's one trace leaves
    them, at each tile the rule could pick."""
    from benchmark.layer_metrics import _mellum, swa_visited_share

    side = 16384 // tile
    run = {"counters": {"attn_window_layers": 3,
                        "attn_window_tiles": 3 * walked,
                        "attn_causal_tiles": 3 * side * (side + 1) // 2},
           "facts": {"seq": 16384, "tokens_per_step": 16384,
                     "swa_kept_pairs_per_layer":
                         flops_mellum2.window_pairs(16384, 1024)}}
    assert _mellum.tile_facts(run) == (tile, walked)
    assert swa_visited_share.read(run) == pytest.approx(share, abs=0.005)


ONE_STEP = os.path.join(HERE, "data", "mellum2_t16384_one_step.xplane.pb")


def test_every_new_reader_reads_a_number_on_a_trace_cut_from_the_chip(
        monkeypatch):
    """One whole step of a real `mellum2-train-t16384` trace (my chip
    run PR 43; cut with tools/cut_program_trace.py, `train:3`: two whole steps)."""
    from benchmark.layer_metrics import _mellum, _moe, _program

    events = _program.load(ONE_STEP)
    launches, _ = _moe.step_launches(events)
    assert len(launches) >= 1
    got = _mellum.busy_ms(events)
    assert got is not None and got["attn_window"] > 0 and got["attn_full"] > 0
    # three windowed layers together cost less than 1.5 x the full one
    assert got["attn_window"] < 1.5 * got["attn_full"]
    monkeypatch.setattr(_program, "trace_path", lambda: ONE_STEP)
    monkeypatch.setattr(_mellum, "_cache", {})
    step_us = max(m.end_ns - m.start_ns for m in launches) / 1e3
    sizes = {"seq": 16384, "tokens_per_step": 16384}
    run = {
        "spans": {}, "ranks": 1,
        "counters": {"attn_window_layers": 3, "attn_window_tiles": 3 * 93,
                     "attn_causal_tiles": 3 * 528},
        "facts": dict(
            sizes, swa_kept_pairs_per_layer=16_253_440,
            swa_attn_flops_per_step=3.0 * 16384 * 16_253_440 * 3,
            full_attn_flops_per_step=3.0 * 16384 * 134_225_920),
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "trace": {"windows": {"train": {"modules": {
            "jit_ompi_train_step": [{"busy_us": step_us}]}}}}}
    for m in _mellum_metrics():
        reader = importlib.import_module(
            "benchmark.layer_metrics." + mf.reader_name(m["name"]))
        value = reader.read(run)
        assert value is not None and value > 0, m["name"]
        if m["name"].endswith("_roofline.mellum"):
            assert value < 100, (m["name"], value)
