"""Device-busy milliseconds per train step of the SGD update (scope `sgd_update`; XLA fuses much of it into the weight-gradient matmuls, which then carry the matmul's scope), forward and
backward together, the median over the traced steps: from the `tf_op`
path of the step's `XLA Ops` events."""

from benchmark.layer_metrics import _program


def read(run: dict):
    return _program.scope_ms_per_step("sgd_update")
