"""Device-busy milliseconds per train step of the vision tower and its projector (every op under scope `vision`: patch product and position table, the 27 blocks, final norm, merge, projector, the scatter into the sequence), forward,
recomputed forward and backward together, the median over the traced
steps: from the `tf_op` path of the step's `XLA Ops` events
(layer_metrics/_kimi.py)."""

from benchmark.layer_metrics import _kimi


def read(run: dict):
    return _kimi.part_ms("vit")
