"""Seconds of mpi.Init()'s phase `client` on rank 0: the first `jax.local_devices()`, which makes the backend's client (on the chip: the TPU runtime's start). The
program's always-on counter `init_client_ns` (the phases end before any
profiler session can exist)."""

from benchmark.layer_metrics import _program


def read(run: dict):
    return _program.counter_seconds("init_client_ns")
