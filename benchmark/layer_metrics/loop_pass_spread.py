"""The slowest pass over the layer list against the fastest (device-busy time per step under `loop_<s>`, layer_metrics/_ouro.py). The passes do identical work on the same weights, so anything
over ~1.02 is scheduling, or where the sums of a layer's per-pass
gradients were placed."""

from benchmark.layer_metrics import _ouro


def read(run: dict):
    passes = _ouro.passes()
    if not passes or min(passes) <= 0:
        return None
    return max(passes) / min(passes)
