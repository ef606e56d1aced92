"""Device-busy milliseconds per train step of the attention cores under
the sliding window (scope `layer_<i>/attn_core/attn_window`: three of
the cell's four layers, together), forward, recomputed forward and
backward, the median over the traced steps: from the `tf_op` path of
the step's `XLA Ops` events (layer_metrics/_mellum.py)."""

from benchmark.layer_metrics import _mellum


def read(run: dict):
    return _mellum.part_ms("attn_window")
