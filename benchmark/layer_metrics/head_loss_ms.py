"""Device-busy milliseconds per train step of the final LN, the tied vocabulary head and the loss (scope `head_loss`), forward and
backward together, the median over the traced steps: from the `tf_op`
path of the step's `XLA Ops` events."""

from benchmark.layer_metrics import _program


def read(run: dict):
    return _program.scope_ms_per_step("head_loss")
