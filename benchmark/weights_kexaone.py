"""Seeded weights for the `k-exaone-236b-a23b` configuration, made on the
device by one small jitted jax.random program a leaf (weights_glm5.py's
way and for its reason: how far a leaf moved is read against the seed's
leaf made AGAIN, and only the same executable is sure to give the same
bits; batches and the seed's key are weights.py's own).

The tree is the one ompi_tpu.models.transformer.init_params builds for
this configuration (same names, shapes and scales; checked at toy width
in benchmark/tests): RMSNorm gains without a bias, no position table,
an untied head, and per layer — windowed and full layers alike — `wq`
(hidden -> heads x head_dim), `wk`, `wv` (hidden -> key heads x
head_dim), `wo`, the two per-head QK-norm gains `q_norm`, `k_norm` of
`[head_dim]`, and either one dense FFN (`w1`, `w3`, `w2`: the layers
before `first_dense`) or a router `wg` with its selection bias
`wg_bias`, the HELD experts' three matrices and the shared expert's
(`ws1`, `ws3`, `ws2`); one multi-token-prediction module (an expert
layer, two norms, the merge matrix). Nothing here imports the program:
the plain reference starts from the same call.
"""

from __future__ import annotations

import math

from benchmark import weights_glm5
from benchmark.weights import seed_key


def plan(cfg: dict):
    """name tree of (shape, scale) or (shape, ("fill", value))."""
    d, v = cfg["d_model"], cfg["vocab"]
    e, held, fe = cfg["n_experts"], cfg["held_count"], cfg["moe_d_ff"]
    fs = cfg["n_shared_experts"] * fe
    s_emb = 1.0 / math.sqrt(d)
    wide = cfg["n_heads"] * cfg["head_dim"]
    narrow = cfg["n_kv_heads"] * cfg["head_dim"]

    def gain(n=d):
        return {"g": ((n,), ("fill", 1.0))}

    def layer(moe: bool):
        lp = {
            "ln1": gain(), "ln2": gain(),
            "wq": ((d, wide), s_emb), "wk": ((d, narrow), s_emb),
            "wv": ((d, narrow), s_emb),
            "wo": ((wide, d), 1.0 / math.sqrt(wide)
                   / math.sqrt(2 * cfg["n_layers"])),
            "q_norm": gain(cfg["head_dim"]), "k_norm": gain(cfg["head_dim"]),
        }
        if not moe:
            f = cfg["d_ff"]
            return dict(lp, w1=((d, f), s_emb), w3=((d, f), s_emb),
                        w2=((f, d), 1.0 / math.sqrt(f)))
        return dict(
            lp, wg=((d, e), s_emb), wg_bias=((e,), 0.01),
            w1=((held, d, fe), s_emb), w3=((held, d, fe), s_emb),
            w2=((held, fe, d), 1.0 / math.sqrt(fe)),
            ws1=((d, fs), s_emb), ws3=((d, fs), s_emb),
            ws2=((fs, d), 1.0 / math.sqrt(fs)))

    tree = {
        "embed": ((v, d), s_emb), "head": ((v, d), s_emb), "ln_f": gain(),
        "layers": [layer(i >= cfg["first_dense"])
                   for i in range(cfg["n_layers"])],
    }
    if cfg["mtp_layers"]:
        tree["mtp"] = [dict(layer(True), enorm=gain(), hnorm=gain(),
                            eh_proj=((2 * d, d), 1.0 / math.sqrt(2 * d)))
                       for _ in range(cfg["mtp_layers"])]
    return tree


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
        weights_glm5._leaf(shape, how, pdt)(k) for k, shape, how in leaves])


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
        norm(leaf, weights_glm5._leaf(shape, how, pdt)(k))
        for (k, shape, how), leaf in zip(leaves, mine)]))
