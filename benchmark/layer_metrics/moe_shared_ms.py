"""Device-busy milliseconds per train step of the shared expert of every expert layer (scope `mlp/moe_shared`: one gated FFN every token passes), forward,
recomputation and backward together, the median over the traced steps:
from the `tf_op` path of the step's `XLA Ops` events
(layer_metrics/_glm.py)."""

from benchmark.layer_metrics import _glm


def read(run: dict):
    return _glm.part_ms("moe_shared")
