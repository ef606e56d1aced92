"""Operations and bytes a `nemotron-3-nano-30b-a3b` train step requires,
from shapes alone (beside flops_glm5.py; kept with the benchmark so
that no later PR can change what a roofline share means).

Counted at 6 operations per parameter per token (2 forward, 4
backward), every matrix a token passes: a state-space layer's `in_proj`
and `out_proj`, the attention layer's four, an expert layer's router
and shared expert (two matrices: no gate), the head; the HELD experts'
two matrices at the rows they really get (`held_rows`: the token-expert
assignments that fell to this chip's experts, summed over the expert
layers, read from the program's routing probe). Attention at the pairs
it REQUIRES: the causal half, `T (T + 1) / 2` a sequence, QK^T and PV,
every QUERY head (the key heads are shared, the products are not).

The scan of a state-space layer, per sequence of T tokens in chunks of
L, H heads of P, G groups of N — the least a chunked form must do, the
causal half inside a chunk and one multiply-add a chunk for the carry:

    scores C B^T        2 N G  x  T (L + 1) / 2
    (decays * scores) x 2 P H  x  T (L + 1) / 2
    chunk states        2 P N H x T
    carried state       2 P N H x T / L
    read-out through C  2 P N H x T

forward; three times that with the backward pass (6 a multiply-add as
everywhere here). Published sizes, 8,192 tokens: 2.77 MFLOP a token
forward (0.13 + 0.53 + 1.05 + 0.01 + 1.05). The convolution: 2 x taps
a channel and token. The scan's BYTES are what any form of it must
move: `x`, `B`, `C` and `dt` read and `y` written once forward, and
those read again with `dy` and the cotangents of the four written once
backward, in the activations' type (2 bytes): with R = HP + 2GN + H
numbers read a token, (R + HP) forward and (R + HP + R) backward
(`ssm_scan_bytes_per_step`). Both are LOWER bounds — the program's float32 decay matrices
and states, its layout changes and the recomputed forward are not
required — so a share of the roofline made of them cannot pass 100%.

Not counted: the embedding lookup, the norms and the gate, softplus
and the exponentials, softmax, the router's top-k, the sort and
gathers of the dispatch, the optimizer update, anything recomputed.
"""

from __future__ import annotations


def layer_counts(cfg: dict) -> dict:
    """letter -> how many layers of the pattern are of that kind."""
    return {kind: cfg["pattern"].count(kind) for kind in "ME*"}


def ssm_inner(cfg: dict) -> int:
    return cfg["ssm_heads"] * cfg["ssm_head_dim"]


def ssm_conv_width(cfg: dict) -> int:
    return ssm_inner(cfg) + 2 * cfg["ssm_groups"] * cfg["ssm_state"]


def ssm_proj_params(cfg: dict) -> int:
    """`in_proj` and `out_proj` of one state-space layer."""
    inner = ssm_inner(cfg)
    return cfg["d_model"] * (inner + ssm_conv_width(cfg)
                             + cfg["ssm_heads"]) + inner * cfg["d_model"]


def attention_params(cfg: dict) -> int:
    wide = cfg["n_heads"] * cfg["head_dim"]
    narrow = cfg["n_kv_heads"] * cfg["head_dim"]
    return cfg["d_model"] * (2 * wide + 2 * narrow)


def scan_flops_forward(cfg: dict, seq: int) -> int:
    """One layer, one sequence, forward."""
    h, p = cfg["ssm_heads"], cfg["ssm_head_dim"]
    g, n, chunk = cfg["ssm_groups"], cfg["ssm_state"], cfg["ssm_chunk"]
    pairs = seq * (chunk + 1) // 2  # the causal half inside the chunks
    return (2 * n * g * pairs + 2 * p * h * pairs
            + 2 * p * n * h * (2 * seq + seq // chunk))


def ssm_scan_flops_per_step(cfg: dict, batch: int, seq: int) -> float:
    """Every state-space layer's scan, forward and backward."""
    return 3.0 * scan_flops_forward(cfg, seq) * batch \
        * layer_counts(cfg)["M"]


def ssm_scan_bytes_per_step(cfg: dict, batch: int, seq: int,
                            itemsize: int = 2) -> float:
    """Every state-space layer's scan: x, B, C, dt read and y written
    forward; those and dy read, their four cotangents written
    backward."""
    inner = ssm_inner(cfg)
    reads = ssm_conv_width(cfg) + cfg["ssm_heads"]  # x, B, C and dt
    per_token = (reads + inner) + (reads + inner + reads)
    return float(per_token * itemsize * batch * seq
                 * layer_counts(cfg)["M"])


def ssm_conv_flops_per_step(cfg: dict, batch: int, seq: int) -> float:
    return 3.0 * 2 * cfg["ssm_conv"] * ssm_conv_width(cfg) * batch * seq \
        * layer_counts(cfg)["M"]


def gqa_attn_flops_per_step(cfg: dict, batch: int, seq: int) -> float:
    """QK^T and PV over the causal half, every query head, forward and
    backward, every attention layer."""
    per_pair = 2 * 2 * cfg["n_heads"] * cfg["head_dim"]
    return 3.0 * per_pair * (seq * (seq + 1) // 2) * batch \
        * layer_counts(cfg)["*"]


def expert_flops_per_step(cfg: dict, held_rows: float) -> float:
    """The held experts' grouped matmuls (two matrices an expert),
    forward and backward, at the rows they get (all expert layers
    together)."""
    return 6.0 * 2 * cfg["d_model"] * cfg["moe_d_ff"] * held_rows


def dense_params_per_token(cfg: dict) -> int:
    """Parameters of the matrices EVERY token passes."""
    d = cfg["d_model"]
    counts = layer_counts(cfg)
    return (counts["M"] * ssm_proj_params(cfg)
            + counts["*"] * attention_params(cfg)
            + counts["E"] * (d * cfg["n_experts"]
                             + 2 * d * cfg["shared_d_ff"])
            + cfg["vocab"] * d)


def train_flops_per_step(cfg: dict, batch: int, seq: int,
                         held_rows: float) -> float:
    return (6.0 * dense_params_per_token(cfg) * batch * seq
            + ssm_scan_flops_per_step(cfg, batch, seq)
            + ssm_conv_flops_per_step(cfg, batch, seq)
            + gqa_attn_flops_per_step(cfg, batch, seq)
            + expert_flops_per_step(cfg, held_rows))
