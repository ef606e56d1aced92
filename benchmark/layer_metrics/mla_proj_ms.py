"""Device-busy milliseconds per train step of latent attention's projections in every layer (scopes `attn_proj/{mla_q, mla_kv, mla_o}`: the low-rank q path with its norm, the kv path with its norm, the output projection), forward,
recomputation and backward together, the median over the traced steps:
from the `tf_op` path of the step's `XLA Ops` events
(layer_metrics/_glm.py)."""

from benchmark.layer_metrics import _glm


def read(run: dict):
    return _glm.part_ms("mla_proj")
