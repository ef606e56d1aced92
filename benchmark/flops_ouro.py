"""Operations an `ouro-2.6b` train step requires, from shapes alone
(beside flops.py, which counts OPT's; kept with the benchmark so that
no later PR can change what a roofline share means).

The layer list runs `loops` times with the same weights, and the work
is required every time: counted ONCE PER PASS, at 6 operations per
parameter per token (2 forward, 4 backward), are each layer's wq wk wv
wo and w1 w3 w2, and causal attention at half of the full T x T
products, as flops.py counts it; once per EXIT (there is one after
every pass) the untied head; once per gate that is read (every pass
but the last) the gate's vector. Not counted: the embedding lookup,
RoPE, the four norms a layer and the norm between passes, softmax, the
exit distribution and its entropy, the sum of a layer's per-pass
gradients, the optimizer update, anything recomputed.
"""

from __future__ import annotations


def layer_matmul_params(cfg: dict) -> int:
    d = cfg["d_model"]
    return 4 * d * d + 3 * d * cfg["d_ff"]


def applications(cfg: dict) -> int:
    """Layer applications a step: layers x passes."""
    return cfg["n_layers"] * cfg["loops"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward; causal attention 6 * T * d per application
    as in flops.py."""
    d = cfg["d_model"]
    return (applications(cfg) * (6.0 * layer_matmul_params(cfg)
                                 + 6.0 * seq * d)
            + cfg["loops"] * 6.0 * cfg["vocab"] * d
            + (cfg["loops"] - 1) * 6.0 * d)


def train_flops_per_step(cfg: dict, batch: int, seq: int) -> float:
    return train_flops_per_token(cfg, seq) * batch * seq
