"""The train cells' control, at a size a test run can hold: the plain
reference put in the program's place and computed with every matmul
operand in float8_e4m3fn (the step below the bfloat16 the
configuration states) must come out as NOT correct, while the program
itself passes, under the same comparison and the cell's rehearsal
limits. On the chip, at the cells' own sizes, the same readings set
the real limits (tools/calibrate_train.py, PERF.md section 2)."""

import pytest

pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from benchmark import compare, manifest as mf, weights  # noqa: E402
from benchmark.runners import train_step as ts  # noqa: E402


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_fp8_control_fails_and_program_passes(seed):
    _, _, traffic, config, limits = mf.cell_inputs(
        mf.load(), "opt30b-train-t1024", rehearsal=True)
    sizes = ts.model_sizes(config)
    n, lr = traffic["check_steps"], traffic["lr"]
    toks, labs = weights.batches(sizes["vocab"], traffic["n_batches"],
                                 traffic["batch"], traffic["seq"], seed)
    _, program = ts.first_steps(
        ts.build_step(sizes, lr), weights.device_init(sizes, seed),
        toks, labs, sizes, seed, n)
    reference = ts.reference_steps(sizes, toks, labs, seed, lr, n)
    control = ts.reference_steps(sizes, toks, labs, seed, lr, n,
                                 quantize=jnp.float8_e4m3fn)
    said = []
    assert compare.verdict(
        ts.checks_against(program, reference, limits), said.append), said
    assert not compare.verdict(
        ts.checks_against(control, reference, limits), said.append), said


def test_a_check_needs_a_finite_number_under_its_limit():
    assert compare.holds(("x", 0.5, 1.0))
    assert compare.holds(("exact", 0, 0))
    assert not compare.holds(("x", 1.5, 1.0))
    assert not compare.holds(("x", float("nan"), 1.0))
    assert not compare.holds(("x", float("inf"), 1.0))
    assert not compare.verdict([], lambda s: None)  # nothing compared
    assert compare.worst_leaf_gap([0, 0], [0, 0]) == float("inf")
    # the gap of the norms against the larger of leaf and median norm
    assert compare.worst_leaf_gap([1.1, 0.0, 10.0], [1.0, 0.001, 10.0]) \
        == pytest.approx(0.1)
