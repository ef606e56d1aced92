"""Seeded weights for the `solar-open2-250b` configuration, made on the
device by one small jitted jax.random program a leaf (weights_glm5.py's
way and for its reason: how far a leaf moved is read against the seed's
leaf made AGAIN, and only the same executable is sure to give the same
bits; batches and the seed's key are weights.py's own).

The tree is the one ompi_tpu.models.transformer.init_params builds for
this configuration (same names and shapes; checked at toy width in
benchmark/tests): every layer two RMSNorm gains `ln1`, `ln2`, a mixer
and the experts — the router `wg` with its selection bias `wg_bias`,
the HELD experts' `w1`, `w3`, `w2`, the shared expert's `ws1`, `ws3`,
`ws2`. The mixer of a layer in `gqa_layers` is gated attention: `wq`,
`wk`, `wv`, the gate's `wa`, `wo`; of every other layer Kimi Delta
Attention: `wq`, `wk`, `wv` with a convolution each (`conv_q`, `conv_k`,
`conv_v`, no bias), the decay through its bottleneck (`w_fa`, `w_fb`,
`dt_bias`, `A_log`), `w_b` for beta, the output norm's gain `o_norm`,
the gate through its bottleneck (`w_ga`, `w_gb`) and `wo`. An untied
head and the final norm.

Every matrix is the benchmark's scaled normal (1 / sqrt(fan-in); the
output projections `wo` further by 1 / sqrt(2 L)). The decay's small
leaves follow the family's initialisation (weights_nemotron.py's draws:
`A_log = log(u)`, `u` uniform in [1, 16], one a head; `dt_bias` the
inverse softplus of a log-uniform step in [0.001, 0.1], one a channel),
so that the decays are a trained model's and not all ~1 or ~0. Nothing
here imports the program: the plain reference starts from the same
call.
"""

from __future__ import annotations

import math

from benchmark import weights_glm5, weights_nemotron
from benchmark.weights import seed_key


def plan(cfg: dict):
    """name tree of (shape, how): `how` as weights_nemotron.plan's."""
    d, v = cfg["d_model"], cfg["vocab"]
    s_emb = 1.0 / math.sqrt(d)
    wide = cfg["n_heads"] * cfg["head_dim"]
    narrow = cfg["n_kv_heads"] * cfg["head_dim"]
    heads, kda = cfg["kda_heads"], cfg["kda_heads"] * cfg["kda_head_dim"]
    rank, taps = cfg["kda_rank"], cfg["kda_conv"]
    e, held, f = cfg["n_experts"], cfg["held_count"], cfg["moe_d_ff"]
    fs = cfg["n_shared_experts"] * f
    out = 1.0 / math.sqrt(2 * cfg["n_layers"])

    def gain(n=d):
        return {"g": ((n,), ("fill", 1.0))}

    def experts():
        return {
            "ln2": gain(), "wg": ((d, e), s_emb), "wg_bias": ((e,), 0.01),
            "w1": ((held, d, f), s_emb), "w3": ((held, d, f), s_emb),
            "w2": ((held, f, d), 1.0 / math.sqrt(f)),
            "ws1": ((d, fs), s_emb), "ws3": ((d, fs), s_emb),
            "ws2": ((fs, d), 1.0 / math.sqrt(fs))}

    def gqa():
        return {
            "ln1": gain(), "wq": ((d, wide), s_emb),
            "wk": ((d, narrow), s_emb), "wv": ((d, narrow), s_emb),
            "wa": ((d, wide), s_emb),
            "wo": ((wide, d), out / math.sqrt(wide))}

    def delta():
        conv = ((kda, taps), 1.0 / math.sqrt(taps))
        return {
            "ln1": gain(), "wq": ((d, kda), s_emb), "wk": ((d, kda), s_emb),
            "wv": ((d, kda), s_emb), "conv_q": conv, "conv_k": conv,
            "conv_v": conv,
            "w_fa": ((d, rank), s_emb),
            "w_fb": ((rank, kda), 1.0 / math.sqrt(rank)),
            "dt_bias": ((kda,), ("dt_bias", cfg["dt_min"], cfg["dt_max"],
                                 0.0)),
            "A_log": ((heads,), ("log_uniform", 1.0, 16.0)),
            "w_b": ((d, heads), s_emb), "o_norm": gain(cfg["kda_head_dim"]),
            "w_ga": ((d, rank), s_emb),
            "w_gb": ((rank, kda), 1.0 / math.sqrt(rank)),
            "wo": ((kda, d), out / math.sqrt(kda))}

    return {"embed": ((v, d), s_emb), "head": ((v, d), s_emb),
            "ln_f": gain(),
            "layers": [dict(gqa() if i in cfg["gqa_layers"] else delta(),
                            **experts()) for i in range(cfg["n_layers"])]}


def _plan_leaves(cfg: dict, seed: int):
    """(treedef, [(key, shape, how)]) of the seed's tree."""
    import jax

    is_leaf = lambda t: isinstance(t, tuple)  # noqa: E731
    leaves, treedef = jax.tree.flatten(plan(cfg), is_leaf=is_leaf)
    keys = jax.random.split(seed_key(seed), len(leaves))
    return treedef, [(k, shape, how) for k, (shape, how) in zip(keys,
                                                                leaves)]


def device_init(cfg: dict, seed: int):
    """The whole tree in cfg["param_dtype"], from --seed."""
    import jax
    import jax.numpy as jnp

    pdt = jnp.dtype(cfg["param_dtype"])
    treedef, leaves = _plan_leaves(cfg, seed)
    return jax.tree.unflatten(treedef, [
        weights_nemotron._leaf(shape, how, pdt)(k)
        for k, shape, how in leaves])


def delta_norms(cfg: dict, seed: int, now):
    """Per leaf of `now`, in tree order, the float32 norm of (leaf -
    the seed's leaf), the seed's tree made again one leaf at a time by
    device_init's own programs (weights_glm5.delta_norms). A tree fresh
    from device_init reads 0.0 in every leaf."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    pdt = jnp.dtype(cfg["param_dtype"])
    _, leaves = _plan_leaves(cfg, seed)
    mine = jax.tree.leaves(now)
    if len(mine) != len(leaves):
        raise ValueError(f"{len(mine)} leaves against the plan's "
                         f"{len(leaves)}")
    norm = weights_glm5._norm_of_difference()
    return np.asarray(jax.device_get([
        norm(leaf, weights_nemotron._leaf(shape, how, pdt)(k))
        for (k, shape, how), leaf in zip(leaves, mine)]))
