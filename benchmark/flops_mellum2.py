"""Operations a `mellum2-12b-a2.5b` train step requires, from shapes
alone (beside flops_olmoe.py, whose expert layer this model shares; kept
with the benchmark so that no later PR can change what a roofline share
means).

Counted, at 6 operations per parameter per token (2 forward, 4
backward): each layer's wq wk wv wo, the router, the three matrices of
the `top_k` experts a token is routed to (NOT of all experts), and the
untied head's slice once; and the attention cores — QK^T and PV, 4 x
head_dim operations a (query, key) pair and query head forward, three
times that with the backward pass — over exactly the pairs a layer's
mask KEEPS: the causal triangle's T (T + 1) / 2 on a full layer, `sum_t
min(t + 1, W)` on a layer under the window (`window_pairs`), so that a
kernel gets no credit for the masked part of a tile it visits. The key
heads are shared and the products are not: every QUERY head counts.
Not counted: the embedding lookup, RoPE and YaRN's table, the norms,
softmax, top-k, the sort and the gathers of the dispatch, the
optimizer update, anything recomputed.
"""

from __future__ import annotations

SLIDING, FULL = "sliding_attention", "full_attention"


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def window_pairs(seq: int, window: int) -> int:
    """sum over t of min(t + 1, window): query t sees itself and the
    window - 1 keys before it, as many as there are."""
    w = min(seq, window)
    return w * (w + 1) // 2 + (seq - w) * w


def kept_pairs(cfg: dict, seq: int, kind: str) -> int:
    return window_pairs(seq, cfg["window"]) if kind == SLIDING \
        else causal_pairs(seq)


def layers_of(cfg: dict, kind: str) -> int:
    return sum(1 for k in cfg["layer_types"] if k == kind)


def attn_core_flops_per_step(cfg: dict, batch: int, seq: int,
                             kind: str) -> float:
    """Every layer of `kind`'s scores and values, every query head,
    forward and backward, nothing recomputed."""
    per_pair = 2 * 2 * cfg["n_heads"] * cfg["head_dim"]
    return 3.0 * per_pair * kept_pairs(cfg, seq, kind) * batch \
        * layers_of(cfg, kind)


def swa_attn_flops_per_step(cfg: dict, batch: int, seq: int) -> float:
    return attn_core_flops_per_step(cfg, batch, seq, SLIDING)


def full_attn_flops_per_step(cfg: dict, batch: int, seq: int) -> float:
    return attn_core_flops_per_step(cfg, batch, seq, FULL)


def attention_params(cfg: dict) -> int:
    wide = cfg["n_heads"] * cfg["head_dim"]
    narrow = cfg["n_kv_heads"] * cfg["head_dim"]
    return cfg["d_model"] * (2 * wide + 2 * narrow)


def expert_params_per_token(cfg: dict) -> int:
    """Parameters of the experts ONE token passes through in one
    layer: top_k experts x (W1, W3, W2)."""
    return cfg["top_k"] * 3 * cfg["d_model"] * cfg["moe_d_ff"]


def matmul_params_per_token(cfg: dict) -> int:
    d = cfg["d_model"]
    return cfg["n_layers"] * (attention_params(cfg) + d * cfg["n_experts"]
                              + expert_params_per_token(cfg)) \
        + cfg["vocab"] * d


def expert_flops_per_step(cfg: dict, batch: int, seq: int) -> float:
    """The experts' part alone (the grouped matmuls of every layer,
    forward and backward): what `moe_experts_roofline.moe` divides."""
    return 6.0 * cfg["n_layers"] * expert_params_per_token(cfg) \
        * batch * seq


def train_flops_per_step(cfg: dict, batch: int, seq: int) -> float:
    return (6.0 * matmul_params_per_token(cfg) * batch * seq
            + swa_attn_flops_per_step(cfg, batch, seq)
            + full_attn_flops_per_step(cfg, batch, seq))
