"""Device-busy milliseconds per train step of the delta-rule layers' mixers (scope `layer_<i>/kda`: the three Kimi-Delta-Attention layers), forward,
recomputed forwards and backward together, the median over the traced
steps: from the `tf_op` path of the step's `XLA Ops` events
(layer_metrics/_solar.py)."""

from benchmark.layer_metrics import _solar


def read(run: dict):
    return _solar.part_ms("kda")
