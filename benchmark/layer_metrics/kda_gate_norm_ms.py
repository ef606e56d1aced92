"""Device-busy milliseconds per train step of the per-head RMSNorm and the output gate (scope `kda/kda_gate_norm`) of every delta-rule layer, forward,
recomputed forwards and backward together, the median over the traced
steps: from the `tf_op` path of the step's `XLA Ops` events
(layer_metrics/_solar.py)."""

from benchmark.layer_metrics import _solar


def read(run: dict):
    return _solar.part_ms("kda_gate_norm")
