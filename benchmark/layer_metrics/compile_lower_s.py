"""Seconds jax spent LOWERING the job's own programs' jaxprs to
StableHLO modules: the program's always-on counter `compile_lower_ns`
(`_compile.py`)."""

from benchmark.layer_metrics import _compile


def read(run: dict):
    return _compile.seconds("compile_lower_ns")
