"""Microseconds from the program's end on the chip to the end of the
caller's wait, per small-message collective (median over the traced
small pass), the chip's clock moved onto the host's by the middle of
`_runtime.py`'s bracket: holds to half `clock_bracket_us`. The
runtime's notice of the end and the caller thread's wake-up together;
`wake_after_done_us` is the second alone."""

from benchmark.layer_metrics import _runtime


def read(run: dict):
    return _runtime.metric("wake_us")
