"""The `nemotron-train-t8192` cell off the chip: the plain reference
against a third, naive writing of the scan; whole rehearsal runs through
the one command (and the same with the timed path broken underneath,
which must come out not correct); the reference's fp8 control at toy
size under the cell's rehearsal limits; the operation and byte counts
against counts by hand; the cell and its configuration as ISSUE 39
names them; the new readers on a trace that has none of their names."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from benchmark import check_manifest as cm
from benchmark import flops_nemotron, manifest as mf
from benchmark import run as bench_run
from benchmark.common import RESULT_TAG

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "nemotron-train-t8192"
CONFIG = "nemotron-3-nano-30b-a3b"


# -- the reference against a third writing ------------------------------------

def test_the_recurrence_is_the_dense_decay_masked_form():
    """One head: y = (L * (C B^T)) (dt x) with L[t, s] = exp(sum of dt A
    over s < i <= t) for s <= t — the [T, T] form no program here may
    hold, which a toy can."""
    pytest.importorskip("jax")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import nemotron_decoder as ref

    t, p, n = 24, 5, 7
    k = jax.random.split(jax.random.key(4), 4)
    x = jax.random.normal(k[0], (t, 1, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (t, 1)))
    bm = jax.random.normal(k[2], (t, 1, n))
    cm = jax.random.normal(k[3], (t, 1, n))
    a = jnp.array([-0.7])
    with jax.default_matmul_precision("highest"):
        y, last = ref.recurrence(x, dt, a, bm, cm, block=5)
        cum = jnp.cumsum(dt[:, 0] * a[0])
        decay = jnp.where(jnp.tril(jnp.ones((t, t), bool)),
                          jnp.exp(cum[:, None] - cum[None, :]), 0.0)
        dense = (decay * (cm[:, 0] @ bm[:, 0].T)) @ (dt * x[:, 0])
        state = jnp.einsum("t,tp,tn->pn", decay[-1] * dt[:, 0], x[:, 0],
                           bm[:, 0])
    assert np.abs(np.asarray(y[:, 0] - dense)).max() < 1e-4
    assert np.abs(np.asarray(last[0] - state)).max() < 1e-4
    # the convolution: tap j reads the token 3 - j places earlier
    w = jnp.array([[1.0, 10.0, 100.0, 1000.0]])
    seq = jnp.arange(1.0, 6.0)[:, None]
    out = ref.causal_conv(seq, w, jnp.zeros(1))
    want = jax.nn.silu(jnp.array([1000.0, 2100.0, 3210.0, 4321.0, 5432.0]))
    assert np.allclose(np.asarray(out[:, 0]), np.asarray(want))


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
    for name in ("ssm_out_gap", "ssm_state_gap", "ssm_grad_norm_gap",
                 "attn_out_gap"):
        assert f"check {name}:" in p.stdout
    errs = cm.check_line(mf.load(), CELL, trace, last)
    assert errs and all("missing" in e or "device lacks" in e
                        for e in errs), errs


@pytest.mark.parametrize("fault, failing", [
    ("carried_state_dropped", "ssm_state_gap"),
    ("wrong_key_heads", "attn_out_gap"),
    ("shared_expert_left_out", "first_grad_norm_gap")])
def test_broken_timed_path_comes_out_not_correct(fault, failing, tmp_path):
    class Ns:
        workload, seed, seconds, trace, rehearsal = CELL, 5, 1.0, 0, 1

    argv = bench_run.launcher_argv(Ns, mf.workload_file(CELL), str(tmp_path))
    i = argv.index(os.path.join(mf.HERE, "rank_main.py"))
    argv[i:i + 1] = [os.path.join(HERE, "broken_nemotron_rank.py"), fault]
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
    assert wl["runner"] == "nemotron_train" and wl["ranks"] == 1
    assert set(limits) == set(wl["rehearsal_limits"]) == {
        "loss_gap", "first_grad_norm_gap", "first_grad_norm_rms_gap",
        "param_change_norm_gap", "router_grad_norm_gap", "ssm_grad_norm_gap",
        "route_disagreement", "ssm_out_gap", "ssm_state_gap", "attn_out_gap"}
    # every key of the catalog's row as it is there, but the cuts
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"] == config["source"]
    differs = {k for k, v in row["config"].items()
               if config.get(k, "-") != v}
    assert differs == set(entry["reduced"]) == set(config["reduced"]) == {
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"}
    assert config["published"] == {k: row["config"][k] for k in differs}
    assert (config["num_hidden_layers"], config["hybrid_override_pattern"],
            config["n_routed_experts"], config["router_experts"],
            config["vocab_size"]) == (9, "MEMEM*EME", 16, 128, 131072 // 8)
    assert row["config"]["hybrid_override_pattern"].startswith("MEMEM*EME")
    # eight cells, one of them on four chips
    assert len(manifest["workloads"]) == 8
    assert [w["name"] for w in manifest["workloads"] if w["chips"] == 4] \
        == ["osu-allreduce-4rank"]
    # the cell reports every `.train` metric, its own, and the `.moe`
    # ones whose readers read it truthfully (no `qk_rope` scope here)
    names = set(mf.metrics_for(manifest, CELL, 1))
    assert {m["name"] for m in manifest["per_layer"]
            if m["name"].endswith((".train", ".nemo", ".moe"))} \
        - {"qk_rope_ms.moe"} | {"init_s", "compile_s"} == names
    assert set(mf.metrics_for(manifest, CELL, 0)) == {"setup_s",
                                                      "tokens_per_s"}


def test_the_seeded_tree_counts_what_the_file_says():
    """986,254,848 parameters at the published widths (ISSUE 39's
    table), from the plan's shapes alone."""
    import math

    from benchmark import weights_nemotron
    from benchmark.runners import nemotron_train as nt

    config = mf.load_json("configs", CONFIG + ".json")
    plan = weights_nemotron.plan(nt.model_sizes(config))

    def count(tree):
        if isinstance(tree, tuple):
            return math.prod(tree[0])
        return sum(count(v) for v in (
            tree.values() if isinstance(tree, dict) else tree))

    layers = dict(zip("M E M E M * E M E".split(), plan["layers"]))
    assert count(layers["M"]) == 38_744_896
    assert count(layers["*"]) == 23_399_040
    assert count(layers["E"]) == 20_302_592 + 16 * 9_977_856
    assert count(plan) == config["parameters"]["total"] == 986_254_848


# -- the reference and its control ---------------------------------------------

@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_fp8_control_fails_where_the_program_passes(seed):
    """The comparison of a run at toy size under the cell's rehearsal
    limits: the bfloat16 program passes, the reference with fp8 matmul
    operands does not — by the first state-space layer's output and
    final state and by the routing (`rehearsal_limits_why`)."""
    pytest.importorskip("jax")
    import jax.numpy as jnp

    from benchmark import compare, weights, weights_nemotron
    from benchmark.runners import nemotron_train as nt

    _, _, traffic, config, limits = mf.cell_inputs(mf.load(), CELL,
                                                   rehearsal=True)
    sizes = nt.model_sizes(config)
    n, lr = traffic["check_steps"], traffic["lr"]
    toks, labs = weights.batches(sizes["vocab"], traffic["n_batches"],
                                 traffic["batch"], traffic["seq"], seed)
    params = weights_nemotron.device_init(sizes, seed)
    probe = nt.probes(sizes, params, toks, labs, n)
    _, program = nt.first_steps(nt.build_step(sizes, lr), params, toks, labs,
                                sizes, seed, n)
    reference = nt.reference_steps(sizes, toks, labs, seed, lr, n)
    first = nt.reference_first_batch(sizes, toks, seed)
    said = []
    assert compare.verdict(
        nt.checks_against(program + (probe["ssm_grads"],), reference, limits,
                          sizes) + nt.first_batch_checks(probe, first,
                                                         limits),
        said.append), said
    fp8 = jnp.float8_e4m3fn
    control = nt.reference_steps(sizes, toks, labs, seed, lr, n,
                                 quantize=fp8)
    chosen, (out, last), attn = nt.reference_first_batch(sizes, toks, seed,
                                                         fp8)
    broken = {c[0] for c in nt.checks_against(control, reference, limits,
                                              sizes)
              + nt.first_batch_checks(
                  {"experts": nt.chosen_numbers(chosen, sizes["top_k"]),
                   "ssm_out": out, "ssm_state": last, "attn_out": attn},
                  first, limits)
              if not compare.holds(c)}
    assert {"ssm_out_gap", "ssm_state_gap", "route_disagreement"} <= broken


# -- counts ----------------------------------------------------------------------

TOY = {"d_model": 8, "vocab": 100, "pattern": "ME*M", "n_heads": 4,
       "n_kv_heads": 2, "head_dim": 3, "moe_d_ff": 5, "shared_d_ff": 7,
       "n_experts": 16, "ssm_heads": 4, "ssm_head_dim": 2, "ssm_groups": 2,
       "ssm_state": 3, "ssm_conv": 4, "ssm_chunk": 4}


def test_flops_and_bytes_against_a_hand_count():
    fl = flops_nemotron
    assert fl.layer_counts(TOY) == {"M": 2, "E": 1, "*": 1}
    assert (fl.ssm_inner(TOY), fl.ssm_conv_width(TOY)) == (8, 8 + 12)
    # in_proj 8 x (8 + 20 + 4) + out_proj 8 x 8
    assert fl.ssm_proj_params(TOY) == 256 + 64
    # wq, wo 8 x 12 each; wk, wv 8 x 6 each
    assert fl.attention_params(TOY) == 2 * 96 + 2 * 48
    # 8 tokens in 2 chunks of 4: 8 x 5 / 2 = 20 causal pairs; scores
    # 2 x 3 x 2 a pair, decays x 2 x 2 x 4 a pair; states and read-out
    # 2 x 2 x 3 x 4 a token each, the carry the same a chunk
    assert fl.scan_flops_forward(TOY, 8) == 20 * 12 + 20 * 16 \
        + 48 * (8 + 8 + 2)
    assert fl.ssm_scan_flops_per_step(TOY, 3, 8) == 3 * 1424 * 3 * 2
    # a token: x, B, C, dt = 20 + 4 numbers and y = 8 forward; those,
    # dy and four cotangents backward
    assert fl.ssm_scan_bytes_per_step(TOY, 3, 8) == (
        (24 + 8) + (24 + 8 + 24)) * 2 * 24 * 2
    assert fl.ssm_conv_flops_per_step(TOY, 3, 8) == 3 * 2 * 4 * 20 * 24 * 2
    # 36 causal pairs x (QK^T + PV) x 4 heads x 3 wide x 2
    assert fl.gqa_attn_flops_per_step(TOY, 3, 8) == 3 * 48 * 36 * 3
    assert fl.expert_flops_per_step(TOY, 7) == 6 * 2 * 8 * 5 * 7
    # 2 x 320 + 288 + router 128 + shared 2 x 56 + head 800
    assert fl.dense_params_per_token(TOY) == 640 + 288 + 128 + 112 + 800
    assert fl.train_flops_per_step(TOY, 3, 8, 7) == (
        6 * 1968 * 24 + 25632 + 23040 + 15552 + 3360)


def test_flops_of_the_committed_cell():
    from benchmark.runners import nemotron_train as nt

    sizes = nt.model_sizes(mf.load_json("configs", CONFIG + ".json"))
    fl = flops_nemotron
    assert fl.ssm_proj_params(sizes) == 27_697_152 + 11_010_048
    assert fl.attention_params(sizes) == 23_396_352
    per_token = fl.scan_flops_forward(sizes, 8192) / 8192
    assert 2.76e6 < per_token < 2.78e6
    held = 8192 * 6 * 16 // 128 * 4  # an eighth of the assignments
    step = fl.train_flops_per_step(sizes, 1, 8192, held)
    assert 18.0e12 < step < 18.6e12
    mamba = 4 * (6 * fl.ssm_proj_params(sizes) * 8192) \
        + fl.ssm_scan_flops_per_step(sizes, 1, 8192) \
        + fl.ssm_conv_flops_per_step(sizes, 1, 8192)
    assert 0.42 < mamba / step < 0.44
    assert 0.14 < (6 * fl.attention_params(sizes) * 8192
                   + fl.gqa_attn_flops_per_step(sizes, 1, 8192)) / step < 0.16
    # the scan is bound by memory: its bytes at 819 GB/s outlast its
    # operations at 197 TFLOP/s
    assert fl.ssm_scan_bytes_per_step(sizes, 1, 8192) / 819e9 \
        > fl.ssm_scan_flops_per_step(sizes, 1, 8192) / 197e12


def test_every_new_reader_gives_nothing_without_a_trace():
    """As on a parent commit whose run has no such scope and no probe."""
    nemo = [m for m in mf.load()["per_layer"] if m["name"].endswith(".nemo")]
    assert len(nemo) == 11
    for m in nemo:
        assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
        reader = importlib.import_module(
            "benchmark.layer_metrics." + mf.reader_name(m["name"]))
        assert reader.read({"spans": {}, "counters": {}, "facts": {},
                            "trace": None, "peaks": None,
                            "ranks": 1}) is None, m["name"]


def test_nemo_parts_of_a_trace_without_them_are_nothing():
    """OLMoE's step (a program without the configuration) has none of
    the names; the rooflines take the longer of two least times."""
    from benchmark.layer_metrics import _nemo, _program

    old = os.path.join(HERE, "data", "olmoe_t4096_one_step.xplane.pb")
    assert _nemo.busy_ms(_program.load(old)) is None
    run = {"facts": {"f": 197e12 * 1e-3, "b": 819e9 * 4e-3},
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    assert _nemo.roofline(run, 10.0, "f") == pytest.approx(10.0)
    assert _nemo.roofline(run, 10.0, "f", "b") == pytest.approx(40.0)
    assert _nemo.roofline(run, None, "f") is None
    assert _nemo.roofline(run, 10.0, "absent") is None
