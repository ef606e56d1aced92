"""Device-busy milliseconds per train step of the router of every MoE layer: its float32 matmul, softmax, top-k and the two router losses (scope `moe_route`), forward and backward together, the median over
the traced steps: from the `tf_op` path of the step's `XLA Ops` events
(layer_metrics/_moe.py)."""

from benchmark.layer_metrics import _moe


def read(run: dict):
    return _moe.part_ms("moe_route")
