"""Width in microseconds of the bracket on (device clock - host clock)
that `_runtime.py` drew from the program's spans and the runtime's
events together: `enqueue_to_start_us` and `wake_us` hold to half of
it. It is the smallest enqueue-to-start latency plus the smallest
end-to-notice latency of the run."""

from benchmark.layer_metrics import _runtime


def read(run: dict):
    return _runtime.metric("clock_bracket_us")
