"""The tower's attention's share of its roofline. Compute-bound: the
least time is the operations it REQUIRES (benchmark/flops_kimivl.py
`vit_attn_flops_per_step`: QK^T and PV over the BLOCK DIAGONAL of the
packed row — each image's patches squared — at the heads' own width of
72, forward and backward, nothing recomputed) over the chip's peak bf16
rate. The kernel pads a head to 128 lanes (1.78x the products), visits
whole tiles (a tile that straddles two images is computed and masked)
and runs its forward twice where the block is recomputed: each only
lowers the share, and nothing can read over 100%. Share = least time /
device-busy time under `vision/.../attn_core` (vit_attn_ms.py)."""

from benchmark.layer_metrics import _kimi


def read(run: dict):
    return _kimi.roofline(run, _kimi.part_ms("vit_attn"),
                          "vit_attn_flops_per_step")
