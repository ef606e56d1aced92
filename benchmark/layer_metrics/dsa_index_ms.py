"""Device-busy milliseconds per train step of the sparse-attention indexer in every layer (scopes `attn_proj/dsa_index_proj`: its three projections, and `attn_core/dsa_index`: the score products summed over its heads block by block, and the top-k selection), forward,
recomputation and backward together, the median over the traced steps:
from the `tf_op` path of the step's `XLA Ops` events
(layer_metrics/_glm.py)."""

from benchmark.layer_metrics import _glm


def read(run: dict):
    return _glm.part_ms("dsa_index")
