"""Operations an `olmoe-1b-7b` train step requires, from shapes alone
(beside flops.py, which counts OPT's; kept with the benchmark so that
no later PR can change what a roofline share means).

Counted, at 6 operations per parameter per token (2 forward, 4
backward): each layer's wq wk wv wo, the router, the three matrices of
the `top_k` experts a token is routed to (NOT of all experts: the
others are work a dense dispatch would execute and the algorithm does
not require), and the untied head once; and causal attention at half
of the full T x T products, as flops.py counts it. Not counted: the
embedding lookup, RoPE, the norms, softmax, top-k, the sort and the
gathers of the dispatch, the two router losses, the optimizer update,
anything recomputed.
"""

from __future__ import annotations


def expert_params_per_token(cfg: dict) -> int:
    """Parameters of the experts ONE token passes through in one
    layer: top_k experts x (W1, W3, W2)."""
    return cfg["top_k"] * 3 * cfg["d_model"] * cfg["d_ff"]


def matmul_params_per_token(cfg: dict) -> int:
    d = cfg["d_model"]
    return cfg["n_layers"] * (4 * d * d + d * cfg["n_experts"]
                              + expert_params_per_token(cfg)) \
        + cfg["vocab"] * d


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward; causal attention 6 * L * T * d as in
    flops.py."""
    return (6.0 * matmul_params_per_token(cfg)
            + 6.0 * cfg["n_layers"] * seq * cfg["d_model"])


def train_flops_per_step(cfg: dict, batch: int, seq: int) -> float:
    return train_flops_per_token(cfg, seq) * batch * seq


def expert_flops_per_step(cfg: dict, batch: int, seq: int) -> float:
    """The experts' part alone (the grouped matmuls of every layer,
    forward and backward): what `moe_experts_roofline.moe` divides."""
    return 6.0 * cfg["n_layers"] * expert_params_per_token(cfg) \
        * batch * seq
