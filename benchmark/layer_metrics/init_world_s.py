"""Seconds of mpi.Init()'s phase `world` on rank 0: building COMM_WORLD/COMM_SELF (coll selection per communicator) and the init hooks. The
program's always-on counter `init_world_ns` (the phases end before any
profiler session can exist)."""

from benchmark.layer_metrics import _program


def read(run: dict):
    return _program.counter_seconds("init_world_ns")
