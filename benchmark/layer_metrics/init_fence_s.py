"""Seconds of mpi.Init()'s phase `fence` on rank 0: the device plane's modex and fence: publishing this rank's device, waiting for the slowest rank, reading every rank's. The
program's always-on counter `init_fence_ns` (the phases end before any
profiler session can exist)."""

from benchmark.layer_metrics import _program


def read(run: dict):
    return _program.counter_seconds("init_fence_ns")
