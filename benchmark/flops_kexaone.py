"""Operations a `k-exaone-236b-a23b` train step requires, from shapes
alone (beside flops_mellum2.py and flops_glm5.py, whose window and whose
held share this model has together; kept with the benchmark so that no
later PR can change what a roofline share means).

Counted, at 6 operations per parameter per token (2 forward, 4
backward), every matrix a token passes: each attention mixer's wq wk wv
wo (the trunk's layers and the multi-token-prediction module's), the
dense layer's three, an expert layer's router and shared expert, both
heads' slices, the module's merge matrix; the HELD experts' three
matrices at the rows they really get (`held_rows`: the token-expert
assignments that fell to this chip's experts, summed over the expert
layers, read from the program's routing probe — NOT tokens x top_k:
most of those belong to other chips); and the attention cores — QK^T
and PV, 4 x head_dim operations a (query, key) pair and query head
forward, three times that with the backward pass — over exactly the
pairs a layer's mask KEEPS: the causal triangle's T (T + 1) / 2 on a
full layer (the module's is one), `sum_t min(t + 1, W)` on a layer
under the window, so that a kernel gets no credit for the masked part
of a tile it walks. The key heads are shared and the products are not:
every QUERY head counts. Not counted: the embedding lookups, the
per-head norms and RoPE, the norms, softmax, top-k, the sort and the
gathers of the dispatch, the optimizer update, anything recomputed.
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


def applications(cfg: dict) -> tuple:
    """The kind of attention of every layer application of a step: the
    trunk's layers, then the module's."""
    return tuple(cfg["layer_types"]) \
        + (cfg["mtp_layer_type"],) * cfg["mtp_layers"]


def layers_of(cfg: dict, kind: str) -> int:
    return sum(1 for k in applications(cfg) if k == kind)


def kept_pairs(cfg: dict, seq: int, kind: str) -> int:
    return window_pairs(seq, cfg["window"]) if kind == SLIDING \
        else causal_pairs(seq)


def attn_core_flops_per_step(cfg: dict, batch: int, seq: int,
                             kind: str) -> float:
    """Every application of `kind`'s scores and values, every query
    head, forward and backward, nothing recomputed."""
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


def layer_counts(cfg: dict):
    """(dense layers, expert layers), the MTP module's among the
    latter."""
    dense = min(cfg["first_dense"], cfg["n_layers"])
    return dense, cfg["n_layers"] - dense + cfg["mtp_layers"]


def expert_flops_per_step(cfg: dict, held_rows: float) -> float:
    """The held experts' grouped matmuls, forward and backward, at the
    rows they get (all expert layers together)."""
    return 6.0 * 3 * cfg["d_model"] * cfg["moe_d_ff"] * held_rows


def parts_params_per_token(cfg: dict) -> dict:
    """Parameters of the matrices EVERY token passes, by part (the
    held experts are counted by their rows, the attention products by
    their pairs)."""
    d = cfg["d_model"]
    dense, moe = layer_counts(cfg)
    return {
        "attention_projections": (dense + moe) * attention_params(cfg),
        "dense_ffn": dense * 3 * d * cfg["d_ff"],
        "routers": moe * d * cfg["n_experts"],
        "shared_experts": moe * 3 * d * cfg["n_shared_experts"]
        * cfg["moe_d_ff"],
        "mtp_merge": cfg["mtp_layers"] * 2 * d * d,
        "heads": (1 + cfg["mtp_layers"]) * cfg["vocab"] * d,
    }


def mtp_flops_per_step(cfg: dict, batch: int, seq: int,
                       held_rows: float) -> float:
    """What the module alone requires: its mixer's projections, its
    router and shared expert, the merge, its head, its full core, and
    its share of the held rows (one expert layer's of `moe`)."""
    d = cfg["d_model"]
    if not cfg["mtp_layers"]:
        return 0.0
    moe = layer_counts(cfg)[1]
    per_token = attention_params(cfg) + d * cfg["n_experts"] \
        + 3 * d * cfg["n_shared_experts"] * cfg["moe_d_ff"] + 2 * d * d \
        + cfg["vocab"] * d
    core = 3.0 * 2 * 2 * cfg["n_heads"] * cfg["head_dim"] * batch \
        * kept_pairs(cfg, seq, cfg["mtp_layer_type"])
    return 6.0 * per_token * batch * seq + core \
        + expert_flops_per_step(cfg, held_rows) / moe


def train_flops_per_step(cfg: dict, batch: int, seq: int,
                         held_rows: float) -> float:
    return (6.0 * sum(parts_params_per_token(cfg).values()) * batch * seq
            + swa_attn_flops_per_step(cfg, batch, seq)
            + full_attn_flops_per_step(cfg, batch, seq)
            + expert_flops_per_step(cfg, held_rows))
