"""Host microseconds of one small-message collective that the device
does not account for: rank 0's median host time around the blocking
call in the window, less the median device time of the collective's
program in the trace. What the API, coll/xla's launch path and the
runtime's dispatch cost a caller."""

from benchmark.layer_metrics import _trace


def read(run: dict):
    host = run["facts"].get("host_small_median_us")
    dev = _trace.median_program_us(run, "small")
    if host is None or dev is None:
        return None
    return host - dev
