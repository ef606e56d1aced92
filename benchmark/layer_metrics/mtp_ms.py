"""Device-busy milliseconds per train step of the multi-token-prediction module (every op of the `layer_<n>` that holds `attn_proj/mtp_merge`, and its head under `head_loss/mtp`), forward,
recomputation and backward together, the median over the traced steps:
from the `tf_op` path of the step's `XLA Ops` events
(layer_metrics/_glm.py)."""

from benchmark.layer_metrics import _glm


def read(run: dict):
    return _glm.part_ms("mtp")
