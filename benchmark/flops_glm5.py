"""Operations a `glm-5` train step requires, from shapes alone (beside
flops.py and flops_olmoe.py; kept with the benchmark so that no later
PR can change what a roofline share means).

Counted, at 6 operations per parameter per token (2 forward, 4
backward), every matrix a token passes: latent attention's five (`wq_a
wq_b wkv_a wkv_b wo`), the indexer's three where it selects (the
sequence is longer than `index_topk`), a dense layer's three or an
expert layer's router and shared expert, both heads, the multi-token
prediction module's merge matrix and its layer; the HELD experts' three
matrices at the rows they really get (`held_rows`: the token-expert
assignments that fell to this chip's experts, summed over the expert
layers, read from the program's routing probe — NOT tokens x top_k:
most of those belong to other chips). Attention at the pairs it
REQUIRES: `sum_t min(t + 1, index_topk)` a sequence (each query's
selected keys), not the causal half a masked pass computes; the
indexer's score products at every causal pair (it must score them all
to select). Not counted: the embedding lookups, RoPE, the norms,
softmax, the top-k of the selection and of the router, the sort and
gathers of the dispatch, the indexer's loss, the optimizer update,
anything recomputed (the step recomputes every layer once).
"""

from __future__ import annotations


def attention_params(cfg: dict) -> int:
    d, h = cfg["d_model"], cfg["n_heads"]
    qk = cfg["qk_nope_dim"] + cfg["qk_rope_dim"]
    return (d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * h * qk
            + d * (cfg["kv_lora_rank"] + cfg["qk_rope_dim"])
            + cfg["kv_lora_rank"] * h * (cfg["qk_nope_dim"]
                                         + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * d)


def indexer_params(cfg: dict) -> int:
    return (cfg["q_lora_rank"] * cfg["index_heads"] * cfg["index_dim"]
            + cfg["d_model"] * (cfg["index_dim"] + cfg["index_heads"]))


def selects(cfg: dict, seq: int) -> bool:
    return bool(cfg["index_topk"]) and seq > cfg["index_topk"]


def attended_pairs(cfg: dict, seq: int) -> int:
    """(query, key) pairs one sequence's attention requires."""
    k = cfg["index_topk"] if selects(cfg, seq) else seq
    return sum(min(t + 1, k) for t in range(seq))


def layer_counts(cfg: dict):
    """(dense layers, expert layers), the MTP module's among the
    latter."""
    dense = min(cfg["first_dense"], cfg["n_layers"])
    return dense, cfg["n_layers"] - dense + cfg["mtp_layers"]


def dsa_attend_flops_per_step(cfg: dict, batch: int, seq: int) -> float:
    """QK^T and PV over the attended pairs, every head, forward and
    backward, every layer."""
    per_pair = 2 * cfg["n_heads"] * (cfg["qk_nope_dim"] + cfg["qk_rope_dim"]
                                     + cfg["v_head_dim"])
    return 3.0 * per_pair * attended_pairs(cfg, seq) * batch \
        * sum(layer_counts(cfg))


def dsa_index_flops_per_step(cfg: dict, batch: int, seq: int) -> float:
    """The indexer's three projections and its score products over
    every causal pair, forward and backward, every layer (0 where it
    does not select)."""
    if not selects(cfg, seq):
        return 0.0
    pairs = seq * (seq + 1) // 2
    per_layer = 6.0 * indexer_params(cfg) * seq \
        + 3.0 * 2 * cfg["index_heads"] * cfg["index_dim"] * pairs
    return per_layer * batch * sum(layer_counts(cfg))


def expert_flops_per_step(cfg: dict, held_rows: int) -> float:
    """The held experts' grouped matmuls, forward and backward, at the
    rows they get (all expert layers together)."""
    return 6.0 * 3 * cfg["d_model"] * cfg["moe_d_ff"] * held_rows


def dense_params_per_token(cfg: dict) -> int:
    """Parameters of the matrices EVERY token passes (the held experts
    are counted by their rows, the attention products by their
    pairs)."""
    d = cfg["d_model"]
    dense, moe = layer_counts(cfg)
    shared = 3 * d * cfg["n_shared_experts"] * cfg["moe_d_ff"]
    return ((dense + moe) * attention_params(cfg)
            + dense * 3 * d * cfg["d_ff"]
            + moe * (d * cfg["n_experts"] + shared)
            + cfg["mtp_layers"] * 2 * d * d
            + (1 + cfg["mtp_layers"]) * cfg["vocab"] * d)


def train_flops_per_step(cfg: dict, batch: int, seq: int,
                         held_rows: int) -> float:
    return (6.0 * dense_params_per_token(cfg) * batch * seq
            + dsa_attend_flops_per_step(cfg, batch, seq)
            + dsa_index_flops_per_step(cfg, batch, seq)
            + expert_flops_per_step(cfg, held_rows))
