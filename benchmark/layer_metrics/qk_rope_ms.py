"""Device-busy milliseconds per train step of QK-norm over the whole q and k projections and RoPE in every layer (scope `qk_rope`), forward and backward together, the median over
the traced steps: from the `tf_op` path of the step's `XLA Ops` events
(layer_metrics/_moe.py)."""

from benchmark.layer_metrics import _moe


def read(run: dict):
    return _moe.part_ms("qk_rope")
