"""Seconds the persistent compilation cache took to hand the job's own
programs back (read, decompress, deserialise, load onto the chip: jax's
`cache_retrieval_time_sec` inside a backend event that hit): the
program's always-on counter `compile_cache_load_ns` (`_compile.py`);
0 in a run that hit nothing."""

from benchmark.layer_metrics import _compile


def read(run: dict):
    return _compile.seconds("compile_cache_load_ns")
