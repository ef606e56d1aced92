"""The decoder's causal attention's share of its roofline.
Compute-bound: the least time is the operations it REQUIRES
(benchmark/flops_kimivl.py `mla_attn_flops_per_step`: QK^T at 192 and
PV at 128 over the causal pairs, forward and backward, nothing
recomputed) over the chip's peak bf16 rate. The kernel pads q and k to
256 lanes and computes whole tiles on the diagonal: each only lowers
the share. Share = least time / device-busy time of the decoder's
`attn_core` — every `attn_core` of the step (attn_core_ms.py) less the
tower's (vit_attn_ms.py)."""

from benchmark.layer_metrics import _kimi


def read(run: dict):
    return _kimi.roofline(run, _kimi.decoder_attn_ms(),
                          "mla_attn_flops_per_step")
