"""Seconds jax spent TRACING the job's own programs — their Python,
before anything is lowered; nested traces counted once: the program's
always-on counter `compile_trace_ns` (`_compile.py`). Read after the
run; the one reader that also prints the program's compile table as
information lines."""

from benchmark.layer_metrics import _compile


def read(run: dict):
    s = _compile.seconds("compile_trace_ns")
    rows = None if s is None else _compile.table()
    if rows:
        _compile.say_table(rows)
    return s
