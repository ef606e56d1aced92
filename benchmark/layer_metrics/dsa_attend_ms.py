"""Device-busy milliseconds per train step of attention over each query's selected keys in every layer (scope `attn_core/dsa_attend`: first support computes every causal block of scores and masks it), forward,
recomputation and backward together, the median over the traced steps:
from the `tf_op` path of the step's `XLA Ops` events
(layer_metrics/_glm.py)."""

from benchmark.layer_metrics import _glm


def read(run: dict):
    return _glm.part_ms("dsa_attend")
