"""Operations and bytes a `solar-open2-250b` train step requires, from
shapes alone (beside flops_nemotron.py; kept with the benchmark so that
no later PR can change what a roofline share means).

Counted at 6 operations per parameter per token (2 forward, 4
backward), every matrix a token passes: a delta-rule layer's four wide
products, its two bottlenecks and `w_b`; the attention layer's five
(the gate's among them); every layer's router and shared expert; the
head; the HELD experts' three matrices at the rows they really get
(`held_rows`: the token-expert assignments that fell to this chip's
experts, summed over the layers, read from the program's routing
probe). Attention at the pairs it REQUIRES: the causal half, `T (T +
1) / 2` a sequence, QK^T and PV, every QUERY head (the key heads are
shared, the products are not).

The core of a delta-rule layer (Kimi Delta Attention), per sequence of
T tokens in chunks of C, H heads, keys and values K wide — the chunked
(WY) form's products at the configuration's C, the causal half inside
a chunk, whatever implements them:

    pair sums  k_r . k_i, q_r . k_i (decayed)   2 x 2 K  x  T (C + 1) / 2
    (I + A)^-1 applied to [beta k e^G | beta v] 2 (K + K) x  T (C + 1) / 2
    V' = U - W S,  S += Kd^T V',  (q e^G) S     3 x 2 K K x  T
    pairs applied to V'                         2 K      x  T (C + 1) / 2

forward and a head; three times that with the backward pass (6 a
multiply-add as everywhere here). Published sizes, C = 64: 140 KFLOP a
token and head forward, 8.9 MFLOP a token and layer. The three
convolutions: 2 x taps a channel and token. The core's BYTES are what
any form of it must move: `q`, `k`, `v`, `g` and `beta` read and `o`
written once forward; those and `do` read and the five cotangents
written once backward, every one at the activations' 2 bytes (the
program's `g` is float32: counted at 2, the bound stays a lower one):
with R = 4 H K + H numbers read a token, (R + H K) forward and (R + H K
+ R) backward (`kda_core_bytes_per_step`). Both are LOWER bounds — the
program's float32 decays, sums and exponentials, its per-chunk systems,
its layout changes, the entering states it writes and reads and the
recomputed forwards are not required — so a share of the roofline made
of them cannot pass 100%.

Not counted: the embedding lookup, the norms and the gates' sigmoids,
softplus and the exponentials, the l2 norms, softmax, the router's
top-k, the sort and gathers of the dispatch, the optimizer update,
anything recomputed.
"""

from __future__ import annotations


def layer_counts(cfg: dict) -> dict:
    """"G" / "K" -> how many of the cell's layers are gated attention /
    delta-rule layers."""
    gqa = sum(1 for i in range(cfg["n_layers"]) if i in cfg["gqa_layers"])
    return {"G": gqa, "K": cfg["n_layers"] - gqa}


def kda_width(cfg: dict) -> int:
    return cfg["kda_heads"] * cfg["kda_head_dim"]


def kda_proj_params(cfg: dict) -> int:
    """One delta-rule layer's matrices: four wide, two bottlenecks,
    `w_b`."""
    d, wide = cfg["d_model"], kda_width(cfg)
    return 4 * d * wide + 2 * cfg["kda_rank"] * (d + wide) \
        + d * cfg["kda_heads"]


def attention_params(cfg: dict) -> int:
    wide = cfg["n_heads"] * cfg["head_dim"]
    narrow = cfg["n_kv_heads"] * cfg["head_dim"]
    return cfg["d_model"] * (3 * wide + 2 * narrow)  # q, gate, o; k, v


def core_flops_forward(cfg: dict, seq: int) -> int:
    """One layer, one sequence, forward."""
    k, chunk = cfg["kda_head_dim"], cfg["kda_chunk"]
    pairs = seq * (chunk + 1) // 2  # the causal half inside the chunks
    return cfg["kda_heads"] * (
        (4 * k + 4 * k + 2 * k) * pairs + 6 * k * k * seq)


def kda_core_flops_per_step(cfg: dict, batch: int, seq: int) -> float:
    """Every delta-rule layer's core, forward and backward."""
    return 3.0 * core_flops_forward(cfg, seq) * batch \
        * layer_counts(cfg)["K"]


def kda_core_bytes_per_step(cfg: dict, batch: int, seq: int,
                            itemsize: int = 2) -> float:
    """Every delta-rule layer's core: q, k, v, g, beta read and o
    written forward; those and do read, their five cotangents written
    backward."""
    wide = kda_width(cfg)
    reads = 4 * wide + cfg["kda_heads"]
    per_token = (reads + wide) + (reads + wide + reads)
    return float(per_token * itemsize * batch * seq
                 * layer_counts(cfg)["K"])


def kda_conv_flops_per_step(cfg: dict, batch: int, seq: int) -> float:
    return 3.0 * 2 * cfg["kda_conv"] * 3 * kda_width(cfg) * batch * seq \
        * layer_counts(cfg)["K"]


def gqa_attn_flops_per_step(cfg: dict, batch: int, seq: int) -> float:
    """QK^T and PV over the causal half, every query head, forward and
    backward, every attention layer."""
    per_pair = 2 * 2 * cfg["n_heads"] * cfg["head_dim"]
    return 3.0 * per_pair * (seq * (seq + 1) // 2) * batch \
        * layer_counts(cfg)["G"]


def expert_flops_per_step(cfg: dict, held_rows: float) -> float:
    """The held experts' grouped matmuls (three matrices an expert),
    forward and backward, at the rows they get (all layers together)."""
    return 6.0 * 3 * cfg["d_model"] * cfg["moe_d_ff"] * held_rows


def dense_params_per_token(cfg: dict) -> int:
    """Parameters of the matrices EVERY token passes."""
    d = cfg["d_model"]
    counts = layer_counts(cfg)
    shared = 3 * d * cfg["n_shared_experts"] * cfg["moe_d_ff"]
    return (counts["K"] * kda_proj_params(cfg)
            + counts["G"] * attention_params(cfg)
            + cfg["n_layers"] * (d * cfg["n_experts"] + shared)
            + cfg["vocab"] * d)


def train_flops_per_step(cfg: dict, batch: int, seq: int,
                         held_rows: float) -> float:
    return (6.0 * dense_params_per_token(cfg) * batch * seq
            + kda_core_flops_per_step(cfg, batch, seq)
            + kda_conv_flops_per_step(cfg, batch, seq)
            + gqa_attn_flops_per_step(cfg, batch, seq)
            + expert_flops_per_step(cfg, held_rows))
