"""The `olmoe-train-t4096` cell off the chip: whole rehearsal runs
through the one command (and the same with the timed path broken
underneath, which must come out not correct), the plain reference
against its fp8 control at toy size, the seeded tree against the
program's, the operation count against a count by hand, and the MoE
readers on a trace cut from a real run of the cell."""

import importlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import check_manifest as cm
from benchmark import flops_olmoe, manifest as mf
from benchmark import run as bench_run
from benchmark.common import RESULT_TAG

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "olmoe-train-t4096"
TOY = {"vocab": 128, "d_model": 64, "n_layers": 2, "n_heads": 4, "d_ff": 32,
       "max_seq": 64, "n_experts": 8, "top_k": 2, "norm_topk_prob": False,
       "act": "silu", "rope_theta": 10000.0, "rms_eps": 1e-5,
       "tie_head": False, "balance_weight": 0.01, "z_weight": 0.001,
       "param_dtype": "float32"}


# -- whole rehearsal runs ------------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_run_is_correct_and_claims_no_device_number(trace):
    p = subprocess.run(
        [sys.executable, os.path.join(mf.HERE, "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 17), "--seconds", "2", "--trace", str(trace),
         "--rehearsal", "1"], capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    last = p.stdout.strip().splitlines()[-1]
    res = json.loads(last)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert res["device"]["platform"] == "cpu"
    assert res["metrics"] == {}  # never a rate under a device name
    assert "REHEARSAL counts" in p.stdout
    assert "check moe_dropped_assignments: 0 against limit 0 ok" in p.stdout
    assert "check route_counts_short: 0 against limit 0 ok" in p.stdout
    errs = cm.check_line(mf.load(), CELL, trace, last)
    assert errs and all("missing" in e or "device lacks" in e
                        for e in errs), errs


@pytest.mark.parametrize("fault, failing", [
    ("unchanged_state", "param_change_norm_gap"),
    ("dropped_assignments", "route_counts_short"),
    ("dropped_assignments", "moe_dropped_assignments")])
def test_broken_timed_path_comes_out_not_correct(fault, failing, tmp_path):
    class Ns:
        workload, seed, seconds, trace, rehearsal = CELL, 5, 1.0, 0, 1

    argv = bench_run.launcher_argv(Ns, mf.workload_file(CELL), str(tmp_path))
    i = argv.index(os.path.join(mf.HERE, "rank_main.py"))
    argv[i:i + 1] = [os.path.join(HERE, "broken_olmoe_rank.py"), fault]
    p = subprocess.run(argv, env=bench_run.child_env(), capture_output=True,
                       text=True, timeout=600, cwd=mf.ROOT)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    line = next(ln for ln in p.stdout.splitlines()
                if ln.startswith(RESULT_TAG))
    assert json.loads(line[len(RESULT_TAG):])["correct"] is False
    assert "NOT CORRECT" in next(
        ln for ln in p.stdout.splitlines()
        if ln.startswith(f"check {failing}:"))


def test_the_cell_is_the_one_the_issue_names():
    cell, wl, traffic, config, limits = mf.cell_inputs(mf.load(), CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "olmoe-1b-7b", "b1-t4096", 1)
    assert traffic == {"batch": 1, "seq": 4096, "n_batches": 8, "lr": 0.01,
                       "check_steps": 3, "trace_steps": 5}
    assert wl["runner"] == "olmoe_train" and wl["ranks"] == 1
    assert set(limits) == {"loss_gap", "first_grad_norm_gap",
                           "first_grad_norm_rms_gap", "param_change_norm_gap",
                           "router_grad_norm_gap", "route_disagreement"}
    # every published key as the catalog has it, depth alone reduced
    published = {
        "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 1024,
        "max_position_embeddings": 4096, "model_type": "olmoe",
        "norm_topk_prob": False, "num_attention_heads": 16,
        "num_experts": 64, "num_experts_per_tok": 8,
        "num_hidden_layers": 16, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "tie_word_embeddings": False, "vocab_size": 50304}
    differs = {k for k, v in published.items() if config.get(k, "-") != v}
    assert differs == {"num_hidden_layers"} == set(config["reduced"])
    assert config["published"] == {"num_hidden_layers": 16}


# -- the reference and its control --------------------------------------------

@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_fp8_control_fails_where_the_float32_program_passes(seed):
    """The comparison of a run, at toy size: the program computing in
    float32 sits four orders of magnitude inside limits that the
    reference with fp8 matmul operands breaks — the control is seen.
    (In bfloat16 at THESE widths, 128 tokens choosing 2 of 8 experts,
    re-routed tokens blur the two: the workload file's
    `rehearsal_limits_why`. The chip's limits are read at the
    published widths: tools/calibrate_olmoe.py.)"""
    pytest.importorskip("jax")
    import jax
    import jax.numpy as jnp

    from benchmark import compare, weights, weights_olmoe
    from benchmark.reference import olmoe_decoder as ref
    from benchmark.runners import olmoe_train as ot
    from ompi_tpu.models import transformer as tfm

    toks, labs = weights.batches(TOY["vocab"], 3, 2, 32, seed)
    cfg = ot.program_config(TOY)
    cfg = tfm.Config(**{**cfg.__dict__, "dtype": jnp.float32})
    ax = tfm.Axes()
    step = jax.jit(tfm.make_train_step(cfg, ax, tfm.param_specs(cfg, ax),
                                       lr=0.01), donate_argnums=(0,))
    _, program = ot.first_steps(step, weights_olmoe.device_init(TOY, seed),
                                toks, labs, TOY, seed, 3)
    reference = ot.reference_steps(TOY, toks, labs, seed, 0.01, 3)
    control = ot.reference_steps(TOY, toks, labs, seed, 0.01, 3,
                                 quantize=jnp.float8_e4m3fn)
    tight = {"loss_gap": 1e-5, "first_grad_norm_gap": 1e-3,
             "first_grad_norm_rms_gap": 1e-4, "param_change_norm_gap": 1e-3,
             "router_grad_norm_gap": 1e-3}
    said = []
    assert compare.verdict(ot.checks_against(program, reference, tight, TOY),
                           said.append), said
    broken = [c for c in ot.checks_against(control, reference, tight, TOY)
              if not compare.holds(c)]
    assert len(broken) == 5, said  # every gap sees the lower precision
    assert all(c[1] > 20 * c[2] for c in broken)
    # and so does the routing: the float32 program chooses the
    # reference's experts, the control does not
    experts = np.sort(np.asarray(tfm.route_experts(
        weights_olmoe.device_init(TOY, seed), toks[0], cfg)[0]), -1)
    assert ot.route_disagreement(experts, TOY, toks, seed) == 0.0
    fp8 = np.asarray(ref.chosen_experts(
        weights_olmoe.device_init(TOY, seed), toks[0], ot.reference_spec(TOY),
        jnp.float8_e4m3fn))
    assert ot.route_disagreement(fp8, TOY, toks, seed) > 0.01


def test_router_leaves_are_the_wg_leaves():
    jax = pytest.importorskip("jax")

    from benchmark import weights_olmoe
    from benchmark.runners import olmoe_train as ot

    tree = weights_olmoe.device_init(TOY, 0)
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_leaves_with_path(tree)]
    marks = ot.router_leaves(TOY)
    assert len(marks) == len(names) and sum(marks) == TOY["n_layers"]
    assert all(("'wg'" in n) == m for n, m in zip(names, marks))


# -- counts and trees ----------------------------------------------------------

def test_flops_against_a_hand_count():
    cfg = {"d_model": 8, "d_ff": 4, "vocab": 100, "n_layers": 2,
           "n_experts": 16, "top_k": 3}
    # one token's experts in one layer: 3 experts x 3 matrices x 8 x 4
    assert flops_olmoe.expert_params_per_token(cfg) == 288
    # per layer: wq wk wv wo 4 x 64 = 256, router 8 x 16 = 128, experts
    # 288 -> 672; two layers 1344, untied head 800 -> 2144
    assert flops_olmoe.matmul_params_per_token(cfg) == 2144
    # 6 x 2144 = 12864; attention 6 x L2 x T16 x d8 = 1536
    assert flops_olmoe.train_flops_per_token(cfg, 16) == 12864 + 1536
    assert flops_olmoe.train_flops_per_step(cfg, 3, 16) == 14400 * 48
    assert flops_olmoe.expert_flops_per_step(cfg, 3, 16) == 6 * 2 * 288 * 48


def test_flops_of_the_committed_cell():
    from benchmark.runners import olmoe_train as ot

    sizes = ot.model_sizes(mf.load_json("configs", "olmoe-1b-7b.json"))
    layer = 4 * 2048 ** 2 + 2048 * 64 + 8 * 3 * 2048 * 1024
    assert layer == 67_239_936
    assert flops_olmoe.matmul_params_per_token(sizes) \
        == sizes["n_layers"] * layer + 50304 * 2048
    step = flops_olmoe.train_flops_per_step(sizes, 1, 4096)
    experts = flops_olmoe.expert_flops_per_step(sizes, 1, 4096)
    head = 6 * 50304 * 2048 * 4096
    # the shares the configuration file states for its depth
    assert sizes["n_layers"] == 4
    assert 0.253 < head / step < 0.255 and 0.496 < experts / step < 0.498
    full = dict(sizes, n_layers=16)
    assert 0.078 < head / flops_olmoe.train_flops_per_step(full, 1, 4096) \
        < 0.079
    assert 0.613 < flops_olmoe.expert_flops_per_step(full, 1, 4096) \
        / flops_olmoe.train_flops_per_step(full, 1, 4096) < 0.615


def test_seeded_tree_is_the_programs_tree():
    jax = pytest.importorskip("jax")

    from benchmark import weights_olmoe
    from benchmark.runners import olmoe_train as ot
    from ompi_tpu.models import transformer as tfm

    sizes = dict(TOY, param_dtype="bfloat16")
    lib = tfm.init_params(np.random.default_rng(0), ot.program_config(sizes))
    mine = weights_olmoe.device_init(sizes, 0)
    sig = lambda t: jax.tree.map(  # noqa: E731
        lambda a: (tuple(a.shape), str(a.dtype)), t)
    assert sig(lib) == sig(mine)
    for a, b in zip(jax.tree.leaves(lib), jax.tree.leaves(mine)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if a.std() > 0:  # same scale, not the same draw
            assert 0.8 < b.std() / a.std() < 1.25
        else:
            assert (a == b).all()
    again = weights_olmoe.device_init(sizes, 0)
    assert all((np.asarray(x) == np.asarray(y)).all() for x, y in zip(
        jax.tree.leaves(mine), jax.tree.leaves(again)))
    big = weights_olmoe.device_init(sizes, 2**31 + 5)
    assert not (np.asarray(big["head"]) == np.asarray(
        weights_olmoe.device_init(sizes, 5)["head"])).all()


def test_every_new_reader_gives_nothing_without_a_trace():
    """As on a parent commit whose run has no MoE scope and no probe."""
    for m in mf.load()["per_layer"]:
        if m["name"].endswith(".moe"):
            reader = importlib.import_module(
                "benchmark.layer_metrics." + mf.reader_name(m["name"]))
            assert reader.read({"spans": {}, "counters": {}, "facts": {},
                                "trace": None, "peaks": None,
                                "ranks": 1}) is None, m["name"]


# -- the MoE readers on a trace cut from the chip -------------------------------

ONE_STEP = os.path.join(HERE, "data", "olmoe_t4096_one_step.xplane.pb")


def test_moe_parts_on_a_trace_cut_from_the_chip(monkeypatch):
    """One whole step (and the start of the next) of a real
    `olmoe-train-t4096` trace (my chip run PR 26, 4 layers; cut with
    tools/cut_program_trace.py, `train:3`)."""
    from benchmark.layer_metrics import _moe, _program

    events = _program.load(ONE_STEP)
    launches, ops = _moe.step_launches(events)
    assert len(launches) == 1
    assert _program.program_of(launches[0].name) == "ompi_train_step"
    got = _moe.busy_ms(events)
    assert got == pytest.approx({
        "moe_route": 0.4074, "moe_dispatch": 6.2724, "moe_experts": 73.3743,
        "moe_combine": 11.3231, "qk_rope": 2.2314}, rel=1e-3)
    # libtpu's grouped-matmul kernels carry no op path: 36 of them in a
    # step of 4 layers (3 forward, 3 + 3 backward a layer) and a
    # metadata kernel for each pair of passes, found by their OWN name
    # (an event's name also lists its operands: the gather that reads a
    # kernel's result is not one)
    inside = [o for o in ops if launches[0].start_ns <= o.start_ns
              and o.end_ns <= launches[0].end_ns]
    own = lambda o: o.name.split(" = ", 1)[0]  # noqa: E731
    kernels = [o for o in inside if own(o).startswith("%ragged-dot-none")]
    assert len(kernels) == 36
    assert {o.stats.get("tf_op") for o in kernels} == {"ragged-dot-none:"}
    assert all(_moe.parts_of(o) == {"moe_experts"} for o in kernels)
    assert all(_program.scopes_of(o.stats.get("tf_op")) == []
               for o in kernels)  # `_program.py` sees them as unscoped
    reads = [o for o in inside if "ragged-dot" in o.name
             and not own(o).startswith("%ragged-dot")]
    assert reads and all("moe_experts" not in _moe.parts_of(o)
                         or "moe_experts" in (o.stats.get("tf_op") or "")
                         for o in reads)
    # the parts sit inside `mlp` / `attn_proj`: the accepted readers'
    # sums are what they were, and `unscoped` holds the kernels
    busy = _program.analyse(events)["windows"]["train"]["scope_busy_us"]
    kernels_ms = sum(o.end_ns - o.start_ns for o in inside
                     if own(o).startswith("%ragged-dot")) / 1e6
    assert kernels_ms == pytest.approx(68.466, rel=1e-3)
    assert busy["unscoped"] / 1e3 > kernels_ms
    assert got["moe_route"] + got["moe_dispatch"] + got["moe_combine"] \
        + got["moe_experts"] - kernels_ms == pytest.approx(
            busy["mlp"] / 1e3, rel=1e-2)
    assert got["qk_rope"] < busy["attn_proj"] / 1e3

    # through the readers, as a run asks them
    monkeypatch.setattr(_program, "trace_path", lambda: ONE_STEP)
    monkeypatch.setattr(_moe, "_cache", {})
    run = {"spans": {}, "counters": {"moe_load_max_over_mean": 7.5},
           "facts": {"moe_experts_flops_per_step": 6 * 4 * 8 * 3 * 2048
                     * 1024 * 4096},
           "trace": None, "peaks": {"bf16_flops_per_s": 197e12}, "ranks": 1}
    read = lambda name: importlib.import_module(  # noqa: E731
        "benchmark.layer_metrics." + name).read(run)
    assert read("moe_experts_ms") == pytest.approx(73.3743, rel=1e-3)
    assert read("moe_dispatch_ms") == pytest.approx(6.2724, rel=1e-3)
    # 4.95 TFLOP / 197 TFLOP/s = 25.1 ms least, of 73.4 ms
    assert read("moe_experts_roofline") == pytest.approx(34.2, rel=5e-3)
    assert read("moe_load_max_over_mean") == 7.5


def test_moe_parts_of_a_trace_without_them_are_nothing():
    """OPT's step (the parent's program): no MoE scope, no kernel."""
    from benchmark.layer_metrics import _moe, _program

    old = os.path.join(HERE, "data", "train_t1024_scoped_two_steps.xplane.pb")
    events = _program.load(old)
    assert len(_moe.step_launches(events)[0]) == 2
    assert _moe.busy_ms(events) is None
