"""The grouped-query attention's share of its roofline. Compute-bound:
the least time is the operations it REQUIRES (benchmark/flops_nemotron.py
`gqa_attn_flops_per_step`: QK^T and PV over the causal half for each of
the 32 QUERY heads — the 2 key heads are shared, the products are not —
forward and backward, nothing recomputed) over the chip's peak bf16
rate. Whole tiles on the diagonal, the recomputed forward and the
layout changes into the kernel's head-major operands (the key heads
repeated 16 times on the way) only lower the share. Share = least time
/ device-busy time of every `attn_core` of the step (attn_core_ms.py:
this model has one kind of attention)."""

from benchmark.layer_metrics import _nemo, _program


def read(run: dict):
    return _nemo.roofline(run, _program.scope_ms_per_step("attn_core"),
                          "gqa_attn_flops_per_step")
