"""The windowed attention cores' share of their roofline. Compute-bound:
the least time is the operations they REQUIRE (benchmark/flops_mellum2.py
`swa_attn_flops_per_step`: QK^T and PV over the `sum_t min(t + 1, W)`
pairs the window keeps, each of the 32 query heads, the three windowed
layers, forward and backward, nothing recomputed) over the chip's peak
bf16 rate. The masked part of every tile the kernels walk, the forward
made again, the scores the two-kernel backward makes twice and the
layout changes only lower the share. Share = least time / device-busy
time under `attn_window` (swa_attn_ms.py)."""

from benchmark.layer_metrics import _mellum, _nemo


def read(run: dict):
    return _nemo.roofline(run, _mellum.part_ms("attn_window"),
                          "swa_attn_flops_per_step")
