"""The `ouro-train-t4096` cell off the chip: whole rehearsal runs
through the one command (and the same with the timed path broken
underneath, which must come out not correct), the plain reference
against its fp8 control at toy size under the cell's rehearsal limits,
the operation count against a count by hand, the cell and its
configuration as ISSUE 32 names them, and the new readers on a trace
that has none of their names."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from benchmark import check_manifest as cm
from benchmark import flops_ouro, manifest as mf
from benchmark import run as bench_run
from benchmark.common import RESULT_TAG

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "ouro-train-t4096"
LIMITS = {"loss_gap", "first_grad_norm_gap", "first_grad_norm_rms_gap",
          "param_change_norm_gap", "gate_grad_norm_gap", "pass_loss_gap",
          "exit_prob_gap"}


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
    assert "traced 4 passes, 8 layer applications" in p.stdout
    for name in ("seed_tree_remade_gap", "nonfinite_window_losses"):
        assert f"check {name}: 0" in p.stdout
    for name in LIMITS:
        assert f"check {name}:" in p.stdout
    errs = cm.check_line(mf.load(), CELL, trace, last)
    assert errs and all("missing" in e or "device lacks" in e
                        for e in errs), errs


@pytest.mark.parametrize("fault, failing", [
    ("unchanged_state", "param_change_norm_gap"),
    ("stack_run_once", "pass_loss_gap"),
    ("no_norm_between_passes", "pass_loss_gap")])
def test_broken_timed_path_comes_out_not_correct(fault, failing, tmp_path):
    class Ns:
        workload, seed, seconds, trace, rehearsal = CELL, 5, 1.0, 0, 1

    argv = bench_run.launcher_argv(Ns, mf.workload_file(CELL), str(tmp_path))
    i = argv.index(os.path.join(mf.HERE, "rank_main.py"))
    argv[i:i + 1] = [os.path.join(HERE, "broken_ouro_rank.py"), fault]
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
        "ouro-2.6b", "b1-t4096", 1)
    assert traffic == {"batch": 1, "seq": 4096, "n_batches": 8, "lr": 0.01,
                       "check_steps": 3, "trace_steps": 5}
    assert wl["runner"] == "ouro_train" and wl["ranks"] == 1
    assert set(limits) == set(wl["rehearsal_limits"]) == LIMITS
    # every key of the catalog's row as it is there, but the depth
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    entry = next(c for c in manifest["configs"] if c["name"] == "ouro-2.6b")
    assert entry["source"] == row["source_url"] == config["source"]
    differs = {k for k, v in row["config"].items()
               if config.get(k, "-") != v}
    assert differs == set(entry["reduced"]) == set(config["reduced"]) == {
        "num_hidden_layers"}
    assert config["published"] == {"num_hidden_layers": 48}
    assert config["num_hidden_layers"] in (8, 12)
    assert config["total_ut_steps"] == 4  # the mechanism is never cut
    # the cell reports every `.train` metric and its own, nothing of
    # another configuration's
    names = set(mf.metrics_for(manifest, CELL, 1))
    assert {m["name"] for m in manifest["per_layer"]
            if m["name"].endswith((".train", ".ouro"))} | {
        "init_s", "compile_s"} == names
    assert set(mf.metrics_for(manifest, CELL, 0)) == {"setup_s",
                                                      "tokens_per_s"}


# -- the reference and its control ---------------------------------------------

@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_fp8_control_fails_where_the_program_passes(seed):
    """The comparison of a run at toy size under the cell's rehearsal
    limits: the bfloat16 program passes, the reference with fp8 matmul
    operands does not — nothing here is discrete, so OPT's kind of
    limits separate (`rehearsal_limits_why`)."""
    pytest.importorskip("jax")
    import jax.numpy as jnp

    from benchmark import compare, weights, weights_ouro
    from benchmark.runners import ouro_train as ot

    _, _, traffic, config, limits = mf.cell_inputs(mf.load(), CELL,
                                                   rehearsal=True)
    sizes = ot.model_sizes(config)
    n, lr = traffic["check_steps"], traffic["lr"]
    toks, labs = weights.batches(sizes["vocab"], traffic["n_batches"],
                                 traffic["batch"], traffic["seq"], seed)
    params = weights_ouro.device_init(sizes, seed)
    probe = ot.exit_probe(sizes, params, toks[0], labs[0])
    _, program = ot.first_steps(ot.build_step(sizes, lr), params, toks, labs,
                                sizes, seed, n)
    reference, ref_exits = ot.reference_steps(sizes, toks, labs, seed, lr, n)

    def checks(steps, exits):
        return ot.checks_against(steps, reference, limits, sizes) \
            + ot.exit_checks(exits, ref_exits, limits)

    said = []
    assert compare.verdict(checks(program, (probe["nll"], probe["mass"])),
                           said.append), said
    control = ot.reference_steps(sizes, toks, labs, seed, lr, n,
                                 quantize=jnp.float8_e4m3fn)
    broken = {c[0] for c in checks(*control) if not compare.holds(c)}
    assert {"first_grad_norm_rms_gap", "pass_loss_gap", "loss_gap",
            "param_change_norm_gap"} <= broken, broken


# -- counts ----------------------------------------------------------------------

def test_flops_against_a_hand_count():
    cfg = {"d_model": 8, "d_ff": 16, "vocab": 100, "n_layers": 3, "loops": 4}
    # wq wk wv wo 4 x 64 + w1 w3 w2 3 x 128
    assert flops_ouro.layer_matmul_params(cfg) == 640
    assert flops_ouro.applications(cfg) == 12
    # per token at 5 positions: 12 applications x (6 x 640 + 6 x 5 x 8)
    # + 4 heads x 6 x 800 + 3 gates x 6 x 8
    assert flops_ouro.train_flops_per_token(cfg, 5) == (
        12 * (3840 + 240) + 4 * 4800 + 3 * 48)
    assert flops_ouro.train_flops_per_step(cfg, 2, 5) == 10 * (
        12 * 4080 + 19200 + 144)
    # a stack run once with one exit is flops.py's dense count with a
    # gated FFN and an untied head
    once = dict(cfg, loops=1)
    assert flops_ouro.train_flops_per_token(once, 5) == (
        6 * (3 * 640 + 800) + 6 * 3 * 5 * 8)


def test_flops_of_the_committed_cell():
    from benchmark.runners import ouro_train as ot

    sizes = ot.model_sizes(mf.load_json("configs", "ouro-2.6b.json"))
    # ISSUE 32's arithmetic: 51,388,416 parameters a layer, 8,192 of
    # them its four norms' gains
    assert flops_ouro.layer_matmul_params(sizes) == 51_388_416 - 4 * 2048
    assert sizes["loops"] == 4
    apps = flops_ouro.applications(sizes)
    assert apps == 4 * sizes["n_layers"]
    step = flops_ouro.train_flops_per_step(sizes, 1, 4096)
    # per application 1.47 TFLOP, per exit 2.47
    assert step == pytest.approx(apps * 1.469e12 + 4 * 2.474e12, rel=1e-3)
    if sizes["n_layers"] == 12:
        assert 80.3e12 < step < 80.5e12


def test_every_new_reader_gives_nothing_without_a_trace():
    """As on a parent commit whose run has no such scope and no probe."""
    ouro = [m for m in mf.load()["per_layer"] if m["name"].endswith(".ouro")]
    assert len(ouro) == 5
    for m in ouro:
        assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
        reader = importlib.import_module(
            "benchmark.layer_metrics." + mf.reader_name(m["name"]))
        assert reader.read({"spans": {}, "counters": {}, "facts": {},
                            "trace": None, "peaks": None,
                            "ranks": 1}) is None, m["name"]


def test_ouro_parts_of_a_trace_without_them_are_nothing():
    """OLMoE's step (the parent's program) has none of the names; the
    counters' readers are plain reads."""
    from benchmark.layer_metrics import (_ouro, _program,
                                         exit_last_pass_share,
                                         loop_layer_applications)

    old = os.path.join(HERE, "data", "olmoe_t4096_one_step.xplane.pb")
    assert _ouro.busy_ms(_program.load(old)) is None
    run = {"counters": {"loop_layer_applications": 48,
                        "exit_probe_tokens": 4096,
                        "exit_last_pass_mass_micro": 512_000_000}}
    assert loop_layer_applications.read(run) == 48
    assert exit_last_pass_share.read(run) == 0.125


def test_passes_and_gate_from_op_paths():
    """The reduction on hand-made events: a pass's time is the union of
    its ops inside a launch, forward and backward alike; the spread is
    the slowest over the fastest."""
    from types import SimpleNamespace as Ev

    from benchmark import trace_reduce as tr
    from benchmark.layer_metrics import _ouro

    def op(a, b, path):
        return Ev(start_ns=a, end_ns=b, stats={"tf_op": path}, name="f")

    ops = [op(0, 10, "jit(ompi_train_step)/jvp(loop_0)/layer_0/mlp/dot"),
           op(5, 20, "jit(s)/transpose(jvp(loop_0))/layer_1/attn_core/x"),
           op(20, 50, "jit(s)/jvp(loop_1)/layer_0/mlp/dot"),
           op(50, 60, "jit(s)/jvp(loop_1)/ln/mul"),
           op(60, 70, "jit(s)/jvp(head_loss)/exit_gate/exp"),
           op(70, 90, "jit(s)/jvp(head_loss)/exit_1/dot")]
    launch = Ev(start_ns=0, end_ns=100, name="jit_ompi_train_step(1)")
    win = Ev(start_ns=0, end_ns=100, name=tr.WINDOW + "train", stats={})
    events = {"host": {"main": [win]},
              "chips": {"/device:TPU:0": {tr.MODULES_LINE: [launch],
                                          tr.OPS_LINE: ops}}}
    got = _ouro.busy_ms(events)
    assert got == {"loop_0": 20 / 1e6, "loop_1": 40 / 1e6,
                   "exit_gate": 10 / 1e6}
