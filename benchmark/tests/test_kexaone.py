"""The `kexaone-train-t8192` cell off the chip: the plain reference
against a third, naive writing (a Python loop over the queries for the
window, the per-head norm and the rotation pair by pair); whole
rehearsal runs through the one command (and the same with the timed
path broken underneath, which must come out not correct by the limit
named for the fault); the reference's fp8 control at toy size under the
cell's rehearsal limits; the operation counts against counts by hand;
the cell and its configuration as ISSUE 50 names them; the readers the
cell reports through, without a trace and on a trace of another
model."""

import importlib
import json
import math
import os
import subprocess
import sys

import pytest

from benchmark import check_manifest as cm
from benchmark import flops_kexaone, manifest as mf
from benchmark import run as bench_run
from benchmark.common import RESULT_TAG

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "kexaone-train-t8192"
CONFIG = "k-exaone-236b-a23b"
SLIDING, FULL = "sliding_attention", "full_attention"


# -- the reference against a third writing ------------------------------------

def _naive_attention(lp, x, kind, spec):
    """One sequence x [T, d] in float64, every (head, query) in a loop of
    its own: each head normed by its own root mean square, the keys a
    query sees listed one by one, each pair of a head's dimensions
    turned by its own angle on a sliding layer and not at all on a full
    one."""
    import numpy as np

    t, h, kv = x.shape[0], spec.n_heads, spec.n_kv_heads
    dh = lp["wq"].shape[1] // h
    half = dh // 2

    def normed(rows, g):
        return np.stack([r / math.sqrt(float(r @ r) / dh + spec.rms_eps) * g
                         for r in rows])

    q = np.stack([normed(a, lp["q_norm"]["g"])
                  for a in (x @ lp["wq"]).reshape(t, h, dh)])
    k = np.stack([normed(a, lp["k_norm"]["g"])
                  for a in (x @ lp["wk"]).reshape(t, kv, dh)])
    v = (x @ lp["wv"]).reshape(t, kv, dh)

    def turned(vec, pos):
        if kind == FULL:
            return vec
        out = np.empty(dh)
        for i in range(half):
            ang = pos * spec.theta ** (-i / half)
            c, s = math.cos(ang), math.sin(ang)
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

    from benchmark import weights_kexaone
    from benchmark.reference import kexaone_decoder as ref
    from benchmark.runners import kexaone_train as kt

    config = mf.load_json("configs", CONFIG + ".rehearsal.json")
    sizes = kt.model_sizes(dict(config, param_dtype="float32"))
    spec = kt.reference_spec(sizes)._replace(q_rows=8)
    lp = weights_kexaone.device_init(sizes, 3)["layers"][1]
    gains = np.linspace(0.5, 1.5, sizes["head_dim"])
    lp = dict(lp, q_norm={"g": jax.numpy.asarray(gains, "float32")},
              k_norm={"g": jax.numpy.asarray(gains[::-1].copy(), "float32")})
    x = jax.random.normal(jax.random.key(9), (1, 40, sizes["d_model"]))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.attention(lp, x, kind, spec)[0], np.float64)
    want = _naive_attention(
        jax.tree.map(lambda a: np.asarray(a, np.float64),
                     {k: lp[k] for k in ("wq", "wk", "wv", "wo", "q_norm",
                                         "k_norm")}),
        np.asarray(x[0], np.float64), kind, spec)
    assert np.abs(got - want).max() <= 2e-5 * np.abs(want).max()
    # and the two kinds differ: the window is 16 of 40 keys, one rotates
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
    for name in ("swa_out_gap", "full_out_gap", "mtp_out_gap",
                 "route_disagreement"):
        assert f"check {name}:" in p.stdout
    assert "'window_edge_seen': 3, 'window_edge_wanted': 3" in p.stdout
    # every counter of the step's one trace, copied by name
    for said in ('"attn_window_layers": 4, "attn_full_layers": 2',
                 '"attn_head_norm_layers": 6, "attn_unrotated_layers": 2',
                 '"mtp_full_layers": 1, "mtp_window_layers": 0',
                 '"moe_held_assignments":', '"moe_bounded_layers":',
                 '"moe_row_sum_gather_layers":'):
        assert said in p.stdout, said
    errs = cm.check_line(mf.load(), CELL, trace, last)
    assert errs and all("missing" in e or "device lacks" in e
                        for e in errs), errs


@pytest.mark.parametrize("fault, failing", [
    ("window_ignored", "swa_out_gap"),
    ("window_one_key_wide", "window_leak_rows"),
    ("full_rotated", "full_out_gap"),
    ("windowed_not_rotated", "swa_out_gap"),
    ("norm_over_the_projection", "swa_out_gap"),
    ("mtp_windowed", "mtp_out_gap")])
def test_broken_timed_path_comes_out_not_correct(fault, failing, tmp_path):
    class Ns:
        workload, seed, seconds, trace, rehearsal = CELL, 5, 1.0, 0, 1

    argv = bench_run.launcher_argv(Ns, mf.workload_file(CELL), str(tmp_path))
    i = argv.index(os.path.join(mf.HERE, "rank_main.py"))
    argv[i:i + 1] = [os.path.join(HERE, "broken_kexaone_rank.py"), fault]
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
    assert wl["rehearsal"] == {"batch": 2, "seq": 64}
    assert wl["runner"] == "kexaone_train" and wl["ranks"] == 1
    assert set(limits) == set(wl["rehearsal_limits"]) == {
        "loss_gap", "first_grad_norm_gap", "first_grad_norm_rms_gap",
        "param_change_norm_gap", "router_grad_norm_gap",
        "route_disagreement", "swa_out_gap", "full_out_gap", "mtp_out_gap"}
    # every key of the catalog's row as it is there, but the cuts
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "K-EXAONE-236B-A23B")
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"] == config["source"]
    assert len(entry["source"]) <= 200
    differs = {k for k, v in row["config"].items()
               if config.get(k, "-") != v}
    assert differs == set(entry["reduced"]) == set(config["reduced"]) \
        == {"num_hidden_layers", "num_experts", "vocab_size"}
    from benchmark.runners import kexaone_train as kt

    sizes = kt.model_sizes(config)
    assert sizes["layer_types"] == (SLIDING,) * 3 + (FULL, SLIDING)
    assert sizes["mtp_layer_type"] == FULL and sizes["mtp_layers"] == 1
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 47
    assert config["sliding_windows"] == [128, 128, 128, 0] * 12
    assert [config["published"][k] for k in (
        "num_hidden_layers", "num_experts", "vocab_size")] == [
            48, 128, 153600]
    assert (config["num_hidden_layers"], config["vocab_size"],
            config["num_experts"], config["router_experts"],
            config["held_first"]) == (5, 153600 // 8, 8, 128, 0)
    assert config["parameters"]["total"] == 3_033_362_560
    for reason in ("deployment", "qk_norm", "rope_on", "norm_placement",
                   "router_bias", "mtp", "router_losses", "training",
                   "intermediate_size", "precision", "depth"):
        assert len(config["assumed"][reason]) > 40
    # one more cell, and still one on four chips
    assert len(manifest["workloads"]) >= 11
    assert [w["name"] for w in manifest["workloads"] if w["chips"] == 4] \
        == ["osu-allreduce-4rank"]
    # the cell reports every `.train` metric, every `.moe` one and its
    # own: the issue's five and, under the cell's suffix (as PR 46 did:
    # the accepted cells' tests pin their lists), the five accepted
    # readers that read a scope or a counter alone
    names = set(mf.metrics_for(manifest, CELL, 1))
    assert {m["name"] for m in manifest["per_layer"]
            if m["name"].endswith((".train", ".kexaone", ".moe"))} \
        | {"init_s", "compile_s"} == names
    assert {n for n in names if n.endswith(".kexaone")} == {
        "swa_attn_roofline.kexaone", "full_attn_roofline.kexaone",
        "swa_visited_share.kexaone", "mtp_share.kexaone",
        "moe_held_share.kexaone", "swa_attn_ms.kexaone",
        "full_attn_ms.kexaone", "mtp_ms.kexaone", "moe_shared_ms.kexaone",
        "swa_share.kexaone"}
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

    from benchmark import compare, weights, weights_kexaone
    from benchmark.runners import kexaone_train as kt

    _, _, traffic, config, limits = mf.cell_inputs(mf.load(), CELL,
                                                   rehearsal=True)
    sizes = kt.model_sizes(config)
    n, lr = traffic["check_steps"], traffic["lr"]
    toks, labs = weights.batches(sizes["vocab"], traffic["n_batches"],
                                 traffic["batch"], traffic["seq"], seed)
    params = weights_kexaone.device_init(sizes, seed)
    probe = kt.probes(sizes, params, toks, n, seed)
    _, program = kt.first_steps(kt.build_step(sizes, lr), params, toks, labs,
                                sizes, seed, n)
    reference = kt.reference_steps(sizes, toks, labs, seed, lr, n)
    first = kt.reference_first_batch(sizes, toks, seed)
    said = []
    assert compare.verdict(
        kt.checks_against(program, reference, limits, sizes)
        + kt.first_batch_checks(probe, first, limits), said.append), said
    fp8 = jnp.float8_e4m3fn
    control = kt.reference_steps(sizes, toks, labs, seed, lr, n,
                                 quantize=fp8)
    chosen, outs = kt.reference_first_batch(sizes, toks, seed, fp8)
    broken = {c[0] for c in kt.checks_against(control, reference, limits,
                                              sizes)
              + kt.first_batch_checks(
                  {"experts": kt.chosen_numbers(chosen, sizes["top_k"]),
                   **{k + "_out": v for k, v in outs.items()}}, first,
                  limits)
              if not compare.holds(c)}
    assert {"swa_out_gap", "full_out_gap", "mtp_out_gap"} <= broken


# -- counts ----------------------------------------------------------------------

TOY = {"d_model": 8, "vocab": 100, "n_layers": 5, "first_dense": 1,
       "layer_types": (SLIDING, SLIDING, SLIDING, FULL, SLIDING),
       "mtp_layer_type": FULL, "mtp_layers": 1, "n_heads": 4,
       "n_kv_heads": 2, "head_dim": 3, "window": 5, "d_ff": 10,
       "moe_d_ff": 6, "n_experts": 16, "n_shared_experts": 1, "top_k": 2}


def test_flops_against_a_hand_count():
    fl = flops_kexaone
    assert fl.window_pairs(12, 5) == sum(
        1 for t in range(12) for s in range(12) if s <= t and s > t - 5) == 50
    assert fl.causal_pairs(12) == 78
    assert fl.applications(TOY) == TOY["layer_types"] + (FULL,)
    assert (fl.layers_of(TOY, SLIDING), fl.layers_of(TOY, FULL)) == (4, 2)
    assert fl.layer_counts(TOY) == (1, 5)
    # wq, wo 8 x 12 each; wk, wv 8 x 6 each
    assert fl.attention_params(TOY) == 2 * 96 + 2 * 48 == 288
    # (QK^T + PV) x 4 heads x 3 wide x 2 = 48 a pair
    assert fl.swa_attn_flops_per_step(TOY, 3, 12) == 3 * 48 * 50 * 3 * 4
    assert fl.full_attn_flops_per_step(TOY, 3, 12) == 3 * 48 * 78 * 3 * 2
    assert fl.expert_flops_per_step(TOY, 40) == 6 * 3 * 8 * 6 * 40
    parts = fl.parts_params_per_token(TOY)
    assert parts == {"attention_projections": 6 * 288, "dense_ffn": 240,
                     "routers": 5 * 128, "shared_experts": 5 * 144,
                     "mtp_merge": 128, "heads": 2 * 800}
    assert fl.train_flops_per_step(TOY, 3, 12, 40) == (
        6 * sum(parts.values()) * 36 + 86400 + 67392 + 34560)
    # the module: a mixer, a router, a shared expert, the merge, a head,
    # one full core, a fifth of the held rows
    assert fl.mtp_flops_per_step(TOY, 3, 12, 40) == (
        6 * (288 + 128 + 144 + 128 + 800) * 36 + 3 * 48 * 78 * 3
        + 34560 / 5)


def test_flops_of_the_committed_cell():
    """ISSUE 50's reckoning: ~86 TFLOP a step and its shares."""
    from benchmark.runners import kexaone_train as kt

    sizes = kt.model_sizes(mf.load_json("configs", CONFIG + ".json"))
    fl, t, rows = flops_kexaone, 8192, 5 * 4096
    assert fl.attention_params(sizes) == 113_246_208
    assert fl.window_pairs(t, 128) == 1_040_448
    step = fl.train_flops_per_step(sizes, 1, t, rows)
    assert 86.4e12 < step < 86.6e12
    parts = {k: 6.0 * v * t / step
             for k, v in fl.parts_params_per_token(sizes).items()}
    for name, share in (("attention_projections", 0.386),
                        ("dense_ffn", 0.193), ("heads", 0.134),
                        ("shared_experts", 0.107), ("mtp_merge", 0.043)):
        assert parts[name] == pytest.approx(share, abs=0.001)
    assert fl.full_attn_flops_per_step(sizes, 1, t) / step \
        == pytest.approx(0.0763, abs=0.0005)
    assert fl.swa_attn_flops_per_step(sizes, 1, t) / step \
        == pytest.approx(0.0047, abs=0.0002)
    assert fl.expert_flops_per_step(sizes, rows) / step \
        == pytest.approx(0.0536, abs=0.0005)
    assert fl.mtp_flops_per_step(sizes, 1, t, rows) / step \
        == pytest.approx(0.245, abs=0.002)


# -- the readers -------------------------------------------------------------------

def _kexaone_metrics():
    return [m for m in mf.load()["per_layer"]
            if m["name"].endswith(".kexaone")]


def test_every_new_metrics_reader_gives_nothing_without_a_trace():
    """As on a parent commit whose run has no such scope and counter."""
    mine = _kexaone_metrics()
    assert [m["name"].split(".")[0] for m in mine] == [
        "swa_attn_roofline", "full_attn_roofline", "swa_visited_share",
        "mtp_share", "moe_held_share", "swa_attn_ms", "full_attn_ms",
        "mtp_ms", "moe_shared_ms", "swa_share"]
    for m in mine:
        assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
        reader = importlib.import_module(
            "benchmark.layer_metrics." + mf.reader_name(m["name"]))
        assert reader.read({"spans": {}, "counters": {}, "facts": {},
                            "trace": None, "peaks": None,
                            "ranks": 1}) is None, m["name"]


def test_the_modules_share_of_a_trace_without_it_is_nothing(monkeypatch):
    """OLMoE's step (a program without the module) names no part of
    it; on Solar-Open2's step neither."""
    from benchmark.layer_metrics import _glm, _program, mtp_share

    old = os.path.join(HERE, "data", "olmoe_t4096_one_step.xplane.pb")
    monkeypatch.setattr(_program, "trace_path", lambda: old)
    monkeypatch.setattr(_glm, "_cache", {})
    run = {"spans": {}, "counters": {}, "facts": {}, "peaks": None,
           "ranks": 1, "trace": {"windows": {"train": {"modules": {
               "jit_ompi_train_step": [{"busy_us": 1000.0}]}}}}}
    assert mtp_share.read(run) is None


def test_the_counter_readers_on_the_cells_own_counts():
    """`swa_visited_share.kexaone` is what `window_tiles` gives for the
    tile the rule chose; `moe_held_share.kexaone` the held share."""
    from benchmark.layer_metrics import moe_held_share, swa_visited_share
    from ompi_tpu.ops import attention as att

    tile = att.blockwise_tile("tpu", 8192, 8192, 128, window=128)
    walked, whole = (att.window_tiles(8192, tile, w) for w in (128, None))
    run = {"counters": {"attn_window_layers": 4,
                        "attn_window_tiles": 4 * walked,
                        "attn_causal_tiles": 4 * whole,
                        "moe_held_assignments": 4 * 4100,
                        "moe_assignments": 4 * 65536},
           "facts": {"seq": 8192, "tokens_per_step": 8192,
                     "swa_kept_pairs_per_layer":
                         flops_kexaone.window_pairs(8192, 128)}}
    assert swa_visited_share.read(run) == pytest.approx(
        walked * tile * tile / 1_040_448) == pytest.approx(7.81, abs=0.005)
    assert moe_held_share.read(run) == pytest.approx(4100 / 65536)
