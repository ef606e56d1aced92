"""scripts/row_reduce_probe.py's reading of a result: what each part of
a per-token sum costs, from the four programs' times (tests/ holds no
chip and times nothing here; the script's run is a chip call, ~10 s a
shape and sum)."""

import importlib.util
import os

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "row_reduce_probe", os.path.join(HERE, "scripts", "row_reduce_probe.py"))
probe = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(probe)


def test_a_sums_time_is_its_program_less_its_product():
    """`reduce_ms`: XLA's sum and the kernel's are each program's time
    less its product's alone, the packed epilogue the difference of the
    two products, and the gain what is left of XLA's sum once the
    kernel's and the epilogue are paid — negative where the kernel's
    path loses."""
    got = probe.reduce_ms({"plain": 11.5, "plain+xla": 17.5, "packed": 12.0,
                           "packed+kernel": 15.0})
    assert got == {"xla": 6.0, "kernel": 3.0, "epilogue": 0.5, "gain": 2.5}
    lost = probe.reduce_ms({"plain": 8.0, "plain+xla": 9.0, "packed": 8.5,
                            "packed+kernel": 10.5})
    assert lost["gain"] == pytest.approx(-1.5)


def test_the_probes_shapes_are_the_cells():
    """`CELLS` against the rules the runners size a layer by: the bound
    is `held_rows_bound`'s, the experts' width a multiple of the
    kernels' lanes (`expert_width_pad`: 1,856 -> 1,920), and the toy
    twin a full layer whose sums the kernel takes."""
    from ompi_tpu.ops import moe

    assert sorted(probe.CELLS) == sorted(
        c + "-train-" + t for c, t in (
            ("mellum2", "t16384"), ("solar2", "t8192"), ("kexaone", "t8192"),
            ("nemotron", "t8192"), ("glm5", "t4096"), ("olmoe", "t4096"),
            ("kimivl", "t4096")))
    for cell, (t, k, bound, d, f, held, of) in probe.CELLS.items():
        assert bound == moe.held_rows_bound(t, k, held, of), cell
        assert d % 128 == 0 and f % 128 == 0 and t % 128 == 0, cell
    t, k, bound, d, f, held, of = probe.TOY
    assert bound == t * k and held == of
