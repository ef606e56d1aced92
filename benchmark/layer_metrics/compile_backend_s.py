"""Seconds of the job's own programs' backend events that were NOT the
persistent cache's load: XLA's compile and the write of the entry where
the cache missed, the cache key's hashing where it hit. The program's
always-on counter `compile_backend_ns` (`_compile.py`)."""

from benchmark.layer_metrics import _compile


def read(run: dict):
    return _compile.seconds("compile_backend_ns")
