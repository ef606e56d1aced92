"""The full attention core's share of its roofline. Compute-bound: the
operations it REQUIRES (benchmark/flops_mellum2.py
`full_attn_flops_per_step`: QK^T and PV over the causal triangle's
T (T + 1) / 2 pairs, each of the 32 query heads, forward and backward,
nothing recomputed) over the chip's peak bf16 rate, as a share of the
device-busy time under `attn_full` (full_attn_ms.py). Whole tiles on
the diagonal, the forward made again and the layout changes only lower
it."""

from benchmark.layer_metrics import _mellum, _nemo


def read(run: dict):
    return _nemo.roofline(run, _mellum.part_ms("attn_full"),
                          "full_attn_flops_per_step")
