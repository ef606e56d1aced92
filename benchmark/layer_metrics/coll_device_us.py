"""Device microseconds of one small-message collective: device-busy
time of the collective's executable per launch, the median over the
traced iterations (rank 0's chip)."""

from benchmark.layer_metrics import _trace


def read(run: dict):
    return _trace.median_program_us(run, "small")
