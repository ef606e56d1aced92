"""Seconds the run's collective programs spent in their FIRST launch,
where jax compiles a program or loads it from the persistent cache:
the program's always-on counter `coll_xla_cold_launch_ns`, read after
the run (so it holds the warm-up's programs and, in the sweep, the two
`deterministic="linear"` programs of the check after the window; the
information line gives the count)."""

from benchmark.common import say
from benchmark.layer_metrics import _program


def read(run: dict):
    s = _program.counter_seconds("coll_xla_cold_launch_ns")
    if s is not None:
        say(f"program: {_program.counter('coll_xla_cold_launches')} cold "
            f"launches took {s:.3f} s (information)")
    return s
