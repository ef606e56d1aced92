"""The `glm5-train-t4096` cell off the chip: whole rehearsal runs
through the one command (and the same with the timed path broken
underneath, which must come out not correct), the plain reference
against its fp8 control at toy size under the cell's rehearsal limits,
the operation count against a count by hand, the cell and its
configuration as ISSUE 30 names them, and the new readers on a trace
that has none of their names."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from benchmark import check_manifest as cm
from benchmark import flops_glm5, manifest as mf
from benchmark import run as bench_run
from benchmark.common import RESULT_TAG

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "glm5-train-t4096"


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
                 "nonfinite_window_losses"):
        assert f"check {name}: 0 against limit 0 ok" in p.stdout
    assert "check select_disagreement:" in p.stdout  # selection happens
    errs = cm.check_line(mf.load(), CELL, trace, last)
    assert errs and all("missing" in e or "device lacks" in e
                        for e in errs), errs


@pytest.mark.parametrize("fault, failing", [
    ("unchanged_state", "param_change_norm_gap"),
    ("selection_ignored", "select_disagreement"),
    ("absent_experts_computed", "first_grad_norm_gap")])
def test_broken_timed_path_comes_out_not_correct(fault, failing, tmp_path):
    class Ns:
        workload, seed, seconds, trace, rehearsal = CELL, 5, 1.0, 0, 1

    argv = bench_run.launcher_argv(Ns, mf.workload_file(CELL), str(tmp_path))
    i = argv.index(os.path.join(mf.HERE, "rank_main.py"))
    argv[i:i + 1] = [os.path.join(HERE, "broken_glm5_rank.py"), fault]
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
    cell, wl, traffic, config, limits = mf.cell_inputs(manifest, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "glm-5", "b1-t4096", 1)
    assert traffic == {"batch": 1, "seq": 4096, "n_batches": 8, "lr": 0.01,
                       "check_steps": 3, "trace_steps": 5}
    assert wl["runner"] == "glm5_train" and wl["ranks"] == 1
    assert set(limits) == set(wl["rehearsal_limits"]) == {
        "loss_gap", "first_grad_norm_gap", "first_grad_norm_rms_gap",
        "param_change_norm_gap", "router_grad_norm_gap",
        "indexer_grad_norm_gap", "route_disagreement", "select_disagreement"}
    # every key of the catalog's row as it is there, but the cuts
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "GLM-5")
    entry = next(c for c in manifest["configs"] if c["name"] == "glm-5")
    assert entry["source"] == row["source_url"] == config["source"]
    differs = {k for k, v in row["config"].items()
               if config.get(k, "-") != v}
    assert differs == set(entry["reduced"]) == set(config["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size"}
    assert config["published"] == {k: row["config"][k] for k in differs}
    assert (config["num_hidden_layers"], config["first_k_dense_replace"],
            config["n_routed_experts"], config["router_experts"],
            config["vocab_size"]) == (5, 1, 8, 256, 154880 // 8)
    # the cell reports every `.train` metric, its own, and the `.moe`
    # ones whose readers read it unedited
    names = set(mf.metrics_for(manifest, CELL, 1))
    assert {m["name"] for m in manifest["per_layer"]
            if m["name"].endswith((".train", ".glm", ".moe"))} <= names
    assert set(mf.metrics_for(manifest, CELL, 0)) == {"setup_s",
                                                      "tokens_per_s"}


# -- the reference and its control ---------------------------------------------

@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_fp8_control_fails_where_the_program_passes(seed):
    """The comparison of a run at toy size under the cell's rehearsal
    limits: the bfloat16 program passes, the reference with fp8 matmul
    operands does not — by the two discrete choices, which are what
    separates at these widths (`rehearsal_limits_why`)."""
    pytest.importorskip("jax")
    import jax.numpy as jnp

    from benchmark import compare, weights, weights_glm5
    from benchmark.runners import glm5_train as gt

    _, _, traffic, config, limits = mf.cell_inputs(mf.load(), CELL,
                                                   rehearsal=True)
    sizes = gt.model_sizes(config)
    n, lr = traffic["check_steps"], traffic["lr"]
    toks, labs = weights.batches(sizes["vocab"], traffic["n_batches"],
                                 traffic["batch"], traffic["seq"], seed)
    params = weights_glm5.device_init(sizes, seed)
    probe = gt.probes(sizes, params, toks, n)
    _, program = gt.first_steps(gt.build_step(sizes, lr), params, toks, labs,
                                sizes, seed, n)
    reference = gt.reference_steps(sizes, toks, labs, seed, lr, n)
    chosen, keep = gt.reference_choices(sizes, toks, seed)
    fp8 = jnp.float8_e4m3fn
    c_chosen, c_keep = gt.reference_choices(sizes, toks, seed, fp8)
    c_experts = gt.chosen_numbers(c_chosen, sizes["top_k"])

    def checks(run, experts, mine_keep):
        return gt.checks_against(run, reference, limits, sizes) + [
            ("route_disagreement", gt.route_disagreement(experts, chosen),
             limits["route_disagreement"]),
            ("select_disagreement", gt.select_disagreement(
                mine_keep, keep, sizes["index_topk"]),
             limits["select_disagreement"])]

    said = []
    assert compare.verdict(checks(program, probe["experts"], probe["keep"]),
                           said.append), said
    control = gt.reference_steps(sizes, toks, labs, seed, lr, n,
                                 quantize=fp8)
    broken = {c[0] for c in checks(control, c_experts, c_keep)
              if not compare.holds(c)}
    assert {"route_disagreement", "select_disagreement"} <= broken, said


# -- counts ----------------------------------------------------------------------

def test_flops_against_a_hand_count():
    cfg = {"d_model": 8, "n_heads": 2, "q_lora_rank": 4, "kv_lora_rank": 2,
           "qk_nope_dim": 3, "qk_rope_dim": 2, "v_head_dim": 4,
           "index_heads": 2, "index_dim": 4, "index_topk": 3, "d_ff": 16,
           "moe_d_ff": 4, "n_experts": 16, "n_shared_experts": 1,
           "first_dense": 1, "n_layers": 3, "mtp_layers": 1, "vocab": 100}
    # wq_a 32 + wq_b 4x10=40 + wkv_a 8x4=32 + wkv_b 2x14=28 + wo 8x8=64
    assert flops_glm5.attention_params(cfg) == 196
    # wi_q 4x8 + wi_k 8x4 + wi_w 8x2
    assert flops_glm5.indexer_params(cfg) == 80
    assert flops_glm5.layer_counts(cfg) == (1, 3)  # 2 + the MTP's
    # 5 positions, top-3: 1 + 2 + 3 + 3 + 3 pairs; no selection at 3
    assert flops_glm5.attended_pairs(cfg, 5) == 12
    assert flops_glm5.attended_pairs(cfg, 3) == 6
    assert not flops_glm5.selects(cfg, 3)
    assert flops_glm5.dsa_index_flops_per_step(cfg, 2, 3) == 0.0
    # per pair 2 x 2 heads x (5 + 4); x 3 passes; 12 pairs; 2 rows; 4 layers
    assert flops_glm5.dsa_attend_flops_per_step(cfg, 2, 5) \
        == 3 * 36 * 12 * 2 * 4
    # per layer 6 x 80 x 5 tokens + 3 x 2 x 2 x 4 x 15 causal pairs
    assert flops_glm5.dsa_index_flops_per_step(cfg, 2, 5) \
        == (2400 + 720) * 2 * 4
    assert flops_glm5.expert_flops_per_step(cfg, 7) == 6 * 3 * 8 * 4 * 7
    # 4 x 196 + dense 3x8x16 + 3 x (router 128 + shared 96) + merge 128
    # + 2 heads x 800
    assert flops_glm5.dense_params_per_token(cfg) \
        == 784 + 384 + 672 + 128 + 1600
    assert flops_glm5.train_flops_per_step(cfg, 2, 5, 7) == (
        6 * 3568 * 10 + 3 * 36 * 12 * 2 * 4 + 3120 * 8 + 4032)


def test_flops_of_the_committed_cell():
    from benchmark.runners import glm5_train as gt

    sizes = gt.model_sizes(mf.load_json("configs", "glm-5.json"))
    # ISSUE 30's arithmetic: attention 165.02 M, indexer 9.37 M a layer
    assert flops_glm5.attention_params(sizes) == 165_019_648
    assert flops_glm5.indexer_params(sizes) == 9_371_648
    assert flops_glm5.layer_counts(sizes) == (1, 5)
    # 75% of the causal pairs survive the selection
    pairs = flops_glm5.attended_pairs(sizes, 4096)
    assert pairs == 6_292_480 and 0.7499 < pairs / (4096 * 4097 // 2) < 0.75
    held = 4096 * 8 * 8 // 256 * 5  # 1/32 of the assignments, 5 layers
    step = flops_glm5.train_flops_per_step(sizes, 1, 4096, held)
    assert 53.5e12 < step < 53.8e12
    assert 0.13 < flops_glm5.dsa_attend_flops_per_step(
        sizes, 1, 4096) / step < 0.14
    assert 0.02 < flops_glm5.expert_flops_per_step(sizes, held) / step < 0.025


def test_every_new_reader_gives_nothing_without_a_trace():
    """As on a parent commit whose run has no such scope and no probe."""
    glm = [m for m in mf.load()["per_layer"] if m["name"].endswith(".glm")]
    assert len(glm) == 10
    for m in glm:
        assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
        reader = importlib.import_module(
            "benchmark.layer_metrics." + mf.reader_name(m["name"]))
        assert reader.read({"spans": {}, "counters": {}, "facts": {},
                            "trace": None, "peaks": None,
                            "ranks": 1}) is None, m["name"]


def test_glm_parts_of_a_trace_without_them_are_nothing():
    """OLMoE's step (the parent's program) has none of the names; the
    counters' shares are plain ratios."""
    from benchmark.layer_metrics import _glm, _program

    old = os.path.join(HERE, "data", "olmoe_t4096_one_step.xplane.pb")
    assert _glm.busy_ms(_program.load(old)) is None
    run = {"counters": {"dsa_selected_pairs": 3, "dsa_causal_pairs": 4,
                        "moe_held_assignments": 0, "moe_assignments": 32}}
    assert _glm.share(run, "dsa_selected_pairs", "dsa_causal_pairs") == 0.75
    assert _glm.share(run, "moe_held_assignments", "moe_assignments") == 0.0
