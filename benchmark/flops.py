"""Operations a step requires, from shapes alone. Kept with the
benchmark so that no later PR can change what a roofline share means.

Counted: the matrix multiplications of the decoder (each layer's
wq wk wv wo w1 w2 and the tied head, once) at 6 operations per
parameter per token (2 forward, 4 backward), and causal attention at
half of the full T x T products the code executes — the work the
algorithm REQUIRES, so a kernel that skips the masked half is not
flattered and one that computes it is not credited. Not counted: the
embedding lookup, learned positions, LayerNorm, softmax, the
optimizer update, anything recomputed.
"""

from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    d, f, v = cfg["d_model"], cfg["d_ff"], cfg["vocab"]
    return cfg["n_layers"] * (4 * d * d + 2 * d * f) + v * d


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward. Causal attention per token and layer:
    QK^T and PV are 2 * 2 * T * d operations over the full square,
    so 2 * T * d required forward and 3 x that with the backward
    pass: 6 * L * T * d."""
    return (6.0 * matmul_params(cfg)
            + 6.0 * cfg["n_layers"] * seq * cfg["d_model"])


def train_flops_per_step(cfg: dict, batch: int, seq: int) -> float:
    return train_flops_per_token(cfg, seq) * batch * seq
