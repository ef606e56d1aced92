"""Device-busy milliseconds per train step of the dispatch of every MoE layer: the stable sort of the token-expert assignments and the gather of the rows into expert order (and its transpose) (scope `moe_dispatch`), forward and backward together, the median over
the traced steps: from the `tf_op` path of the step's `XLA Ops` events
(layer_metrics/_moe.py)."""

from benchmark.layer_metrics import _moe


def read(run: dict):
    return _moe.part_ms("moe_dispatch")
