"""Device-busy milliseconds per train step of operations no named scope of the model claims (copies and what XLA made without an op path): should be small, forward and
backward together, the median over the traced steps: from the `tf_op`
path of the step's `XLA Ops` events."""

from benchmark.layer_metrics import _program


def read(run: dict):
    return _program.scope_ms_per_step("unscoped")
