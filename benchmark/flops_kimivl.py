"""Operations a `kimi-vl-a3b` train step requires, from shapes alone
(beside flops.py, flops_olmoe.py, flops_glm5.py and flops_ouro.py; kept
with the benchmark so that no later PR can change what a roofline share
means).

Counted, at 6 operations per parameter per row (2 forward, 4 backward),
every matrix a row passes. The tower, over the packed row's patches:
the patch product, a block's four matrices (`wqkv wo w1 w2`), and over
the merged rows the projector's two; the position table's resize is a
product too (a [patches, table cells] matrix of 16 non-zeros a row:
counted at its 16 taps). The decoder, over the sequence's positions:
latent attention's four (`wq wkv_a wkv_b wo`), a dense layer's three or
an expert layer's router and shared expert, the head; the HELD experts'
three matrices at the rows they really get (`held_rows`, read from the
program's routing probe). Attention at the pairs it REQUIRES: the
tower's over the BLOCK DIAGONAL of the packed row — each image's
patches squared, both ways, at the heads' own width of 72, not the 128
lanes a kernel pads them to and not the packed row's whole square a
mask-blind kernel would compute; the decoder's over the causal half at
192 (q . k) and 128 (p . v). Not counted: biases, the embedding lookup,
RoPE, the norms, softmax, GELU, the merge's gather, the scatter into
the sequence, the router's top-k, the sort and gathers of the dispatch,
the optimizer update, anything recomputed.
"""

from __future__ import annotations


def diag_pairs(images: list) -> int:
    """(query, key) pairs of the block diagonal: each image's patches
    squared."""
    return sum((r * c) ** 2 for r, c in images)


def patches(images: list) -> int:
    return sum(r * c for r, c in images)


def vit_attn_flops_per_step(cfg: dict, images: list) -> float:
    """QK^T and PV over the block diagonal, every head at its own
    width, forward and backward, every block."""
    vc = cfg["vision"]
    return 3.0 * 2 * 2 * vc["d_model"] * diag_pairs(images) * vc["n_layers"]


def vit_dense_flops_per_step(cfg: dict, images: list) -> float:
    vc = cfg["vision"]
    d, m = vc["d_model"], vc["merge"] ** 2 * vc["d_model"]
    per_patch = (vc["patch_dim"] * d + 16 * d
                 + vc["n_layers"] * (4 * d * d + 2 * d * vc["d_ff"]))
    per_merged = m * m + m * cfg["d_model"]
    p = patches(images)
    return 6.0 * (per_patch * p + per_merged * (p // vc["merge"] ** 2))


def attention_params(cfg: dict) -> int:
    d, h = cfg["d_model"], cfg["n_heads"]
    qk = cfg["qk_nope_dim"] + cfg["qk_rope_dim"]
    return (d * h * qk + d * (cfg["kv_lora_rank"] + cfg["qk_rope_dim"])
            + cfg["kv_lora_rank"] * h * (cfg["qk_nope_dim"]
                                         + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * d)


def layer_counts(cfg: dict):
    dense = min(cfg["first_dense"], cfg["n_layers"])
    return dense, cfg["n_layers"] - dense


def mla_attn_flops_per_step(cfg: dict, batch: int, seq: int) -> float:
    """QK^T at 192 and PV at 128 over the causal pairs, every head,
    forward and backward, every layer."""
    per_pair = 2 * cfg["n_heads"] * (cfg["qk_nope_dim"] + cfg["qk_rope_dim"]
                                     + cfg["v_head_dim"])
    return 3.0 * per_pair * (seq * (seq + 1) // 2) * batch * cfg["n_layers"]


def expert_flops_per_step(cfg: dict, held_rows: float) -> float:
    return 6.0 * 3 * cfg["d_model"] * cfg["moe_d_ff"] * held_rows


def decoder_params_per_token(cfg: dict) -> int:
    d = cfg["d_model"]
    dense, moe = layer_counts(cfg)
    shared = 3 * d * cfg["n_shared_experts"] * cfg["moe_d_ff"]
    return ((dense + moe) * attention_params(cfg)
            + dense * 3 * d * cfg["d_ff"]
            + moe * (d * cfg["n_experts"] + shared) + cfg["vocab"] * d)


def train_flops_per_step(cfg: dict, batch: int, seq: int, images: list,
                         held_rows: float) -> float:
    return (vit_dense_flops_per_step(cfg, images)
            + vit_attn_flops_per_step(cfg, images)
            + 6.0 * decoder_params_per_token(cfg) * batch * seq
            + mla_attn_flops_per_step(cfg, batch, seq)
            + expert_flops_per_step(cfg, held_rows))
