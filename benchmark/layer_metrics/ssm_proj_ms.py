"""Device-busy milliseconds per train step of both products of a state-space mixer (scope `ssm/ssm_proj`: `in_proj` and `out_proj`) of every state-space layer, forward, recomputed forward and
backward together, the median over the traced steps: from the `tf_op`
path of the step's `XLA Ops` events (layer_metrics/_nemo.py)."""

from benchmark.layer_metrics import _nemo


def read(run: dict):
    return _nemo.part_ms("ssm_proj")
