"""Sparse attention's share of its roofline. Compute-bound: the least
time is the operations it REQUIRES (benchmark/flops_glm5.py
`dsa_attend_flops_per_step`: QK^T and PV over each query's SELECTED
keys, `sum_t min(t + 1, index_topk)` pairs a sequence, forward and
backward, nothing recomputed) over the chip's peak bf16 rate — so a
pass that computes every causal block and masks it reads low, and
nothing can read over 100%. Share = least time / device-busy time
under `dsa_attend` (dsa_attend_ms.py)."""

from benchmark.layer_metrics import _glm


def read(run: dict):
    return _glm.roofline(run, "dsa_attend", "dsa_attend_flops_per_step")
