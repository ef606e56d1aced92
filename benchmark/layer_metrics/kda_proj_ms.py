"""Device-busy milliseconds per train step of a delta-rule mixer's products (scope `kda/kda_proj`: q, k, v, the decay's and the gate's bottlenecks, beta's, the output product and the residual add) of every delta-rule layer, forward,
recomputed forwards and backward together, the median over the traced
steps: from the `tf_op` path of the step's `XLA Ops` events
(layer_metrics/_solar.py)."""

from benchmark.layer_metrics import _solar


def read(run: dict):
    return _solar.part_ms("kda_proj")
