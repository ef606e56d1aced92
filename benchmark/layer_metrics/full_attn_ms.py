"""Device-busy milliseconds per train step of the full attention cores
(scope `layer_<i>/attn_core/attn_full`: the cell's one layer over the
whole causal triangle), forward, recomputed forward and backward, the
median over the traced steps (layer_metrics/_mellum.py)."""

from benchmark.layer_metrics import _mellum


def read(run: dict):
    return _mellum.part_ms("attn_full")
