"""Device-busy milliseconds per train step of ONE pass over the layer list (scope `loop_<s>`: its layers and the norm that ends it), forward, recomputed forward and backward together: the
median over the passes of each pass's median over the traced steps,
from the `tf_op` path of the step's `XLA Ops` events
(layer_metrics/_ouro.py)."""

import statistics

from benchmark.layer_metrics import _ouro


def read(run: dict):
    passes = _ouro.passes()
    return statistics.median(passes) if passes else None
