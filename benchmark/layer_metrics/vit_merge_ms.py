"""Device-busy milliseconds per train step of the tower's two ends (scopes `vit_embed`: patch product and the position table's bicubic resize; `vit_merge`: final norm, 2 x 2 merge, projector, the scatter into the sequence), forward,
recomputed forward and backward together, the median over the traced
steps (layer_metrics/_kimi.py)."""

from benchmark.layer_metrics import _kimi


def read(run: dict):
    return _kimi.part_ms("vit_merge")
