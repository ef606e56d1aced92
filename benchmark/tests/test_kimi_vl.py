"""The `kimivl-train-t4096` cell off the chip: whole rehearsal runs
through the one command (and the same with the timed path broken
underneath, which must come out not correct), the plain reference
against its fp8 control at toy size under the cell's rehearsal limits,
the seeded tree against the program's own, the operation count against
a count by hand and against ISSUE 37's arithmetic, the cell and its
configuration as the issue names them, and the new readers on a trace
that has none of their names and on hand-made events."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from benchmark import check_manifest as cm
from benchmark import flops_kimivl, manifest as mf
from benchmark import run as bench_run
from benchmark.common import RESULT_TAG

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "kimivl-train-t4096"
IMAGES = [(96, 64), (64, 48), (48, 40), (36, 32)]


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
    for name in ("vision_embed_gap", "tower_grad_norm_gap"):
        assert f"check {name}:" in p.stdout
    counters = json.loads(next(
        ln for ln in p.stdout.splitlines()
        if ln.startswith("counters "))[len("counters "):])
    assert counters["attn_segment_layers"] == 2  # one a block
    assert counters["attn_mla_plain_q_layers"] == 2
    assert counters["vision_patches"] == 40
    assert counters["vision_image_positions"] == 10
    assert counters["compiles_in_window"] == 0
    errs = cm.check_line(mf.load(), CELL, trace, last)
    assert errs and all("missing" in e or "device lacks" in e
                        for e in errs), errs


@pytest.mark.parametrize("fault, failing", [
    ("segment_mask_dropped", "vision_embed_gap"),
    ("tower_gradient_stopped", "tower_grad_norm_gap"),
    ("rows_one_late", "route_disagreement")])
def test_broken_timed_path_comes_out_not_correct(fault, failing, tmp_path):
    class Ns:
        workload, seed, seconds, trace, rehearsal = CELL, 5, 1.0, 0, 1

    argv = bench_run.launcher_argv(Ns, mf.workload_file(CELL), str(tmp_path))
    i = argv.index(os.path.join(mf.HERE, "rank_main.py"))
    argv[i:i + 1] = [os.path.join(HERE, "broken_kimivl_rank.py"), fault]
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
    assert (cell["config"], cell["chips"]) == ("kimi-vl-a3b", 1)
    assert traffic == {"batch": 1, "seq": 4096,
                       "images": [list(g) for g in IMAGES], "text_run": 256,
                       "n_batches": 8, "lr": 0.01, "check_steps": 3,
                       "trace_steps": 5}
    assert wl["runner"] == "kimivl_train" and wl["ranks"] == 1
    assert sum(r * c for r, c in IMAGES) == 12288
    assert sum(r * c // 4 for r, c in IMAGES) + 4 * 256 == 4096
    entry = next(c for c in manifest["configs"] if c["name"] == "kimi-vl-a3b")
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert set(config["reduced"]) == set(entry["reduced"])
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Kimi-VL-A3B-Instruct")
    assert entry["source"] == row["source_url"] == config["source"]
    for key, value in row["config"].items():  # every key, widths untouched
        if key not in entry["reduced"]:
            assert config[key] == value, key
    assert config["published"] == {k: row["config"][k]
                                   for k in entry["reduced"]}
    assert config["n_routed_experts"] == config["router_experts"] == 64
    names = set(mf.metrics_for(manifest, CELL, 1))
    assert {m["name"] for m in manifest["per_layer"]
            if m["name"].endswith((".train", ".moe", ".kimi"))} | {
        "init_s", "compile_s"} == names
    assert set(mf.metrics_for(manifest, CELL, 0)) == {"setup_s",
                                                      "tokens_per_s"}
    four = [w["name"] for w in manifest["workloads"] if w["chips"] == 4]
    assert len(manifest["workloads"]) == 7 and four == [
        "osu-allreduce-4rank"]


def test_seeded_tree_is_the_program_s_tree():
    """Same names, shapes and types as init_params builds for the
    configuration, at toy width."""
    pytest.importorskip("jax")
    import jax
    import numpy as np

    from benchmark import weights_kimivl
    from benchmark.runners import kimivl_train as kt
    from ompi_tpu.models import transformer as tfm

    sizes = kt.model_sizes(mf.load_json("configs",
                                        "kimi-vl-a3b.rehearsal.json"))
    mine = weights_kimivl.device_init(sizes, 3)
    theirs = tfm.init_params(np.random.default_rng(0),
                             kt.program_config(sizes))
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(mine),
                            jax.tree.leaves(theirs)):
        assert a.shape == b.shape and a.dtype == b.dtype, path
    assert weights_kimivl.delta_norms(sizes, 3, mine).max() == 0.0
    kinds = kt.leaf_kinds(sizes)
    assert kinds.count("router") == 2 and kinds.count("tower") == 2 * 12 + 11


def test_packing_is_the_program_s_packing():
    """weights_kimivl builds the batch's packing without the program;
    the program's own `vision.pack` gives the same leaves."""
    import numpy as np

    from benchmark import weights_kimivl
    from ompi_tpu.models import vision

    images = [(4, 6), (2, 4), (4, 2)]
    _, where = weights_kimivl.layout(images, 18, 64)
    mine = weights_kimivl.packing(images, where, (16, 16), 2)
    theirs = vision.pack(images, where, vision.VisionConfig(pos_grid=(16, 16)))
    assert set(mine) == set(theirs)
    for k in mine:
        np.testing.assert_allclose(mine[k], theirs[k], atol=1e-7, err_msg=k)
        assert mine[k].dtype == theirs[k].dtype, k


# -- the reference and its control ---------------------------------------------

@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_fp8_control_fails_where_the_program_passes(seed):
    """The comparison of a run at toy size under the cell's rehearsal
    limits: the bfloat16 program passes, the reference with fp8 matmul
    operands does not."""
    pytest.importorskip("jax")
    import jax.numpy as jnp

    from benchmark import compare, weights_kimivl
    from benchmark.runners import glm5_train as gt, kimivl_train as kt

    _, _, traffic, config, limits = mf.cell_inputs(mf.load(), CELL,
                                                   rehearsal=True)
    sizes = kt.model_sizes(config)
    n, lr = traffic["check_steps"], traffic["lr"]
    toks, labs = weights_kimivl.batches(sizes, traffic, seed)
    params = weights_kimivl.device_init(sizes, seed)
    probe = kt.probes(sizes, params, toks, n)
    _, program = kt.first_steps(kt.build_step(sizes, lr), params, toks, labs,
                                sizes, seed, n)
    reference = kt.reference_steps(sizes, traffic, toks, labs, seed, lr, n)
    chosen, rows = kt.reference_first_batch(sizes, traffic, toks, seed)

    def checks(steps, experts, mine_rows):
        return kt.checks_against(steps, reference, limits, sizes) + [
            ("route_disagreement", gt.route_disagreement(experts, chosen),
             limits["route_disagreement"]),
            ("vision_embed_gap", kt.rows_gap(mine_rows, rows),
             limits["vision_embed_gap"])]

    said = []
    assert compare.verdict(checks(program, probe["experts"], probe["rows"]),
                           said.append), said
    fp8 = jnp.float8_e4m3fn
    control = kt.reference_steps(sizes, traffic, toks, labs, seed, lr, n,
                                 quantize=fp8)
    c_chosen, c_rows = kt.reference_first_batch(sizes, traffic, toks, seed,
                                                fp8)
    broken = {c[0] for c in checks(
        control, gt.chosen_numbers(c_chosen, sizes["top_k"]), c_rows)
        if not compare.holds(c)}
    assert "vision_embed_gap" in broken, broken


# -- counts ----------------------------------------------------------------------

def test_flops_against_a_hand_count():
    cfg = {"d_model": 8, "n_heads": 2, "kv_lora_rank": 4, "qk_nope_dim": 2,
           "qk_rope_dim": 2, "v_head_dim": 3, "d_ff": 16, "moe_d_ff": 6,
           "n_experts": 4, "n_shared_experts": 2, "first_dense": 1,
           "n_layers": 3, "vocab": 100,
           "vision": {"d_model": 4, "n_layers": 2, "n_heads": 2, "d_ff": 10,
                      "patch_dim": 12, "merge": 2}}
    images = [(2, 4), (2, 2)]
    assert flops_kimivl.patches(images) == 12
    assert flops_kimivl.diag_pairs(images) == 64 + 16
    # QK^T and PV: 2 x 2 x d a pair, x 3 for the backward, 2 blocks
    assert flops_kimivl.vit_attn_flops_per_step(cfg, images) == (
        3 * 4 * 4 * 80 * 2)
    # a patch: 12 x 4 + 16 x 4 + 2 x (4 x 16 + 2 x 40) = 400; a merged
    # row: 16 x 16 + 16 x 8 = 384
    assert flops_kimivl.vit_dense_flops_per_step(cfg, images) == 6 * (
        400 * 12 + 384 * 3)
    # wq 8 x 8, wkv_a 8 x 6, wkv_b 4 x 10, wo 6 x 8
    assert flops_kimivl.attention_params(cfg) == 64 + 48 + 40 + 48
    assert flops_kimivl.layer_counts(cfg) == (1, 2)
    # 5 positions: 15 causal pairs x 2 heads x (4 + 3) x 2, fwd + bwd,
    # 3 layers
    assert flops_kimivl.mla_attn_flops_per_step(cfg, 1, 5) == (
        3 * 28 * 15 * 3)
    assert flops_kimivl.decoder_params_per_token(cfg) == (
        3 * 200 + 3 * 8 * 16 + 2 * (8 * 4 + 3 * 8 * 12) + 800)
    assert flops_kimivl.expert_flops_per_step(cfg, 7) == 6 * 3 * 8 * 6 * 7


def test_flops_of_the_committed_cell():
    """ISSUE 37's arithmetic: the tower's products 30 TFLOP, its
    attention 19.5 over the block diagonal, the decoder 12.5 (the
    issue's ~12.7) of which 1.3 attention and 5.1 the experts; 0.3457
    of the packed row's pairs are on the diagonal."""
    from benchmark.runners import kimivl_train as kt

    sizes = kt.model_sizes(mf.load_json("configs", "kimi-vl-a3b.json"))
    assert flops_kimivl.diag_pairs(IMAGES) == 52_199_424
    assert flops_kimivl.diag_pairs(IMAGES) / 12288 ** 2 == pytest.approx(
        0.3457, abs=1e-4)
    assert flops_kimivl.vit_attn_flops_per_step(sizes, IMAGES) \
        == pytest.approx(19.48e12, rel=1e-3)
    assert flops_kimivl.vit_dense_flops_per_step(sizes, IMAGES) \
        == pytest.approx(30.9e12, rel=1e-2)
    assert flops_kimivl.mla_attn_flops_per_step(sizes, 1, 4096) \
        == pytest.approx(1.289e12, rel=1e-3)
    rows = 4 * 4096 * 6  # every expert layer's assignments: all held
    step = flops_kimivl.train_flops_per_step(sizes, 1, 4096, IMAGES, rows)
    assert 60e12 < step < 66e12
    assert flops_kimivl.expert_flops_per_step(sizes, rows) \
        == pytest.approx(5.10e12, rel=1e-2)


# -- the readers -----------------------------------------------------------------

def test_every_new_reader_gives_nothing_without_a_trace():
    """As on a parent commit whose run has no such scope and no probe."""
    kimi = [m for m in mf.load()["per_layer"] if m["name"].endswith(".kimi")]
    assert len(kimi) == 7
    for m in kimi:
        assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
        reader = importlib.import_module(
            "benchmark.layer_metrics." + mf.reader_name(m["name"]))
        assert reader.read({"spans": {}, "counters": {}, "facts": {},
                            "trace": None, "peaks": None,
                            "ranks": 1}) is None, m["name"]


def test_kimi_parts_of_a_trace_without_them_are_nothing():
    from benchmark.layer_metrics import _kimi, _program, vit_diag_share

    old = os.path.join(HERE, "data", "olmoe_t4096_one_step.xplane.pb")
    assert _kimi.busy_ms(_program.load(old)) is None
    run = {"counters": {"vision_diag_pairs": 52_199_424,
                        "vision_row_pairs": 12288 ** 2}}
    assert vit_diag_share.read(run) == pytest.approx(0.3457, abs=1e-4)


def test_tower_parts_from_op_paths():
    """The reduction on hand-made events: the tower's time is the union
    of the ops under `vision` inside a launch; its attention those that
    are under `attn_core` too; the decoder's `attn_core` is not the
    tower's; the accepted scope reader sees the tower under `embed` and
    nothing of it unscoped."""
    from types import SimpleNamespace as Ev

    from benchmark import trace_reduce as tr
    from benchmark.layer_metrics import _kimi, _program

    def op(a, b, path):
        return Ev(start_ns=a, end_ns=b, stats={"tf_op": path}, name="f")

    j = "jit(ompi_train_step)/"
    ops = [op(0, 10, j + "jvp(embed)/vision/vit_embed/dot"),
           op(10, 30, j + "jvp(embed)/vision/vit_0/attn_core/splash"),
           op(25, 40, j + "jvp(embed)/vision/vit_0/mlp/dot"),
           op(40, 45, j + "jvp(embed)/vision/vit_merge/gather"),
           op(45, 60, j + "jvp(layer_0)/attn_core/splash"),
           op(60, 70, j + "transpose(jvp(embed))/vision/vit_0/attn_core/dkv"),
           op(70, 80, j + "transpose(jvp(embed))/vision/vit_embed/dot")]
    launch = Ev(start_ns=0, end_ns=100, name="jit_ompi_train_step(1)")
    win = Ev(start_ns=0, end_ns=100, name=tr.WINDOW + "train", stats={})
    events = {"host": {"main": [win]},
              "chips": {"/device:TPU:0": {tr.MODULES_LINE: [launch],
                                          tr.OPS_LINE: ops}}}
    assert _kimi.busy_ms(events) == {"vit": 65 / 1e6, "vit_attn": 30 / 1e6,
                                     "vit_merge": 25 / 1e6}
    busy = _program.scope_busy(ops, 0, 100)
    assert busy["embed"] == 65 and busy["attn_core"] == 45
    assert "unscoped" not in busy
