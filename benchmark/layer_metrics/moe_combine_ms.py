"""Device-busy milliseconds per train step of the combine of every MoE layer: the gather back into token order and the weighted sum of each token's rows (and its transpose) (scope `moe_combine`), forward and backward together, the median over
the traced steps: from the `tf_op` path of the step's `XLA Ops` events
(layer_metrics/_moe.py)."""

from benchmark.layer_metrics import _moe


def read(run: dict):
    return _moe.part_ms("moe_combine")
