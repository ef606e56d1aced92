"""Seconds of mpi.Init()'s phase `import` on rank 0: loading numpy, the package and jax (the import of ompi_tpu.mpi, then init_instance's `import jax` and the compile cache's wiring). The
program's always-on counter `init_import_ns` (the phases end before any
profiler session can exist)."""

from benchmark.layer_metrics import _program


def read(run: dict):
    return _program.counter_seconds("init_import_ns")
