"""Microseconds from the end of `ompi:coll_xla.launch` to the
program's start on the chip, per small-message collective (median over
the traced small pass), the chip's clock moved onto the host's by the
middle of `_runtime.py`'s bracket: holds to half `clock_bracket_us`.
Signed: the chip may start before `fn(*args)` has returned."""

from benchmark.layer_metrics import _runtime


def read(run: dict):
    return _runtime.metric("enqueue_to_start_us")
