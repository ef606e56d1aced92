"""Device-busy milliseconds per train step of the tower's attention (scope `vision/.../attn_core`: block-diagonal two-way attention over the packed row of patches; on the TPU the blockwise kernels, heads of 72 padded to 128 lanes), forward,
recomputed forward and backward together, the median over the traced
steps (layer_metrics/_kimi.py)."""

from benchmark.layer_metrics import _kimi


def read(run: dict):
    return _kimi.part_ms("vit_attn")
