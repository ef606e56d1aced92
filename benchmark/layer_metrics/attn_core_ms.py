"""Device-busy milliseconds per train step of attention's scores, softmax and AV in every layer (scope `attn_core`: what T2048 has twice of per token), forward and
backward together, the median over the traced steps: from the `tf_op`
path of the step's `XLA Ops` events."""

from benchmark.layer_metrics import _program


def read(run: dict):
    return _program.scope_ms_per_step("attn_core")
