"""Seconds of mpi.Init()'s phase `distributed` on rank 0: joining the job's jax.distributed cluster (the coordinator's address through the store, then `jax.distributed.initialize`). The
program's always-on counter `init_distributed_ns` (the phases end before any
profiler session can exist)."""

from benchmark.layer_metrics import _program


def read(run: dict):
    return _program.counter_seconds("init_distributed_ns")
