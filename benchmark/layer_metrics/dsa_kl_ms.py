"""Device-busy milliseconds per train step of the indexer's own loss in every layer (scope `attn_core/dsa_kl`: the KL divergence of the head-summed attention probabilities from the softmax of the indexer's scores over the selected keys), forward,
recomputation and backward together, the median over the traced steps:
from the `tf_op` path of the step's `XLA Ops` events
(layer_metrics/_glm.py)."""

from benchmark.layer_metrics import _glm


def read(run: dict):
    return _glm.part_ms("dsa_kl")
