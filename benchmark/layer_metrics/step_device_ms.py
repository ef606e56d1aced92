"""Device-busy milliseconds of one train step: the union of the
operation intervals inside each launch of the step's executable, the
median over the traced steps (the train runner's window "train")."""

from benchmark.layer_metrics import _trace


def read(run: dict):
    us = _trace.median_program_us(run, "train")
    return None if us is None else us / 1e3
