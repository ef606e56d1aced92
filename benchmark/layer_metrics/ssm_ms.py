"""Device-busy milliseconds per train step of the state-space layers' mixers (scope `layer_<i>/ssm`: the four Mamba-2 layers), forward, recomputed
forward and backward together, the median over the traced steps: from
the `tf_op` path of the step's `XLA Ops` events
(layer_metrics/_nemo.py)."""

from benchmark.layer_metrics import _nemo


def read(run: dict):
    return _nemo.part_ms("ssm")
