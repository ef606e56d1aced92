"""Seeded weights for the `nemotron-3-nano-30b-a3b` configuration, made
on the device by one small jitted jax.random program a leaf
(weights_glm5.py's way and for its reason: how far a leaf moved is read
against the seed's leaf made AGAIN, and only the same executable is
sure to give the same bits; batches and the seed's key are weights.py's
own).

The tree is the one ompi_tpu.models.transformer.init_params builds for
this configuration (same names and shapes; checked at toy width in
benchmark/tests): a layer is one pre-norm `ln` and one mixer by the
pattern's letter — `M`: `in_proj` (columns `[z | x B C | dt]`),
`conv_w`, `conv_b`, `A_log`, `dt_bias`, `D`, `ssm_norm`, `out_proj`;
`*`: `wq`, `wk`, `wv`, `wo`; `E`: the router `wg` with its selection
bias `wg_bias`, the HELD experts' `w1`, `w2` (no gate matrix), the
shared expert's `ws1`, `ws2` — an untied head and the final norm.

Every matrix is the benchmark's scaled normal (1 / sqrt(fan-in); the
output projections `wo` further by 1 / sqrt(2 L)). The scan's small
leaves follow the family's initialisation, so that the decays are a
trained model's and not all ~1 or ~0: `A_log = log(u)`, `u` uniform in
[1, 16]; `dt_bias` the inverse softplus of a log-uniform draw in
[`time_step_min`, `time_step_max`] floored at `time_step_floor`; `D` =
1. The convolution's taps and bias are normal at 1 / sqrt(taps) (a
bias of zeros would hide a bias that is never added). Nothing here
imports the program: the plain reference starts from the same call.
"""

from __future__ import annotations

import math

from benchmark import weights_glm5
from benchmark.weights import seed_key


def plan(cfg: dict):
    """name tree of (shape, how): `how` a normal's scale, ("fill",
    value), ("log_uniform", low, high): log(u), u uniform in [low,
    high], or ("dt_bias", low, high, floor)."""
    d, v = cfg["d_model"], cfg["vocab"]
    s_emb = 1.0 / math.sqrt(d)
    heads, inner = cfg["ssm_heads"], cfg["ssm_heads"] * cfg["ssm_head_dim"]
    conv = inner + 2 * cfg["ssm_groups"] * cfg["ssm_state"]
    taps = cfg["ssm_conv"]
    wide = cfg["n_heads"] * cfg["head_dim"]
    narrow = cfg["n_kv_heads"] * cfg["head_dim"]
    e, held = cfg["n_experts"], cfg["held_count"]
    fe, fs = cfg["moe_d_ff"], cfg["shared_d_ff"]

    def gain(n=d):
        return {"g": ((n,), ("fill", 1.0))}

    def layer(kind: str):
        if kind == "M":
            return {
                "ln": gain(),
                "in_proj": ((d, inner + conv + heads), s_emb),
                "conv_w": ((conv, taps), 1.0 / math.sqrt(taps)),
                "conv_b": ((conv,), 1.0 / math.sqrt(taps)),
                "A_log": ((heads,), ("log_uniform", 1.0, 16.0)),
                "dt_bias": ((heads,), ("dt_bias", cfg["dt_min"],
                                       cfg["dt_max"], cfg["dt_floor"])),
                "D": ((heads,), ("fill", 1.0)),
                "ssm_norm": gain(inner),
                "out_proj": ((inner, d), 1.0 / math.sqrt(inner))}
        if kind == "*":
            return {
                "ln": gain(), "wq": ((d, wide), s_emb),
                "wk": ((d, narrow), s_emb), "wv": ((d, narrow), s_emb),
                "wo": ((wide, d), 1.0 / math.sqrt(wide)
                       / math.sqrt(2 * cfg["n_layers"]))}
        if kind == "E":
            return {
                "ln": gain(), "wg": ((d, e), s_emb), "wg_bias": ((e,), 0.01),
                "w1": ((held, d, fe), s_emb),
                "w2": ((held, fe, d), 1.0 / math.sqrt(fe)),
                "ws1": ((d, fs), s_emb),
                "ws2": ((fs, d), 1.0 / math.sqrt(fs))}
        raise ValueError(f"no layer kind {kind!r}")

    return {"embed": ((v, d), s_emb), "head": ((v, d), s_emb),
            "ln_f": gain(),
            "layers": [layer(kind) for kind in cfg["pattern"]]}


_LEAF = {}


def _leaf(shape, how, pdt):
    """The jitted program that makes one leaf from its key, kept: the
    same executable gives the same bits. A normal and a fill are
    weights_glm5._leaf's; the scan's two draws are made here."""
    import jax
    import jax.numpy as jnp

    if not isinstance(how, tuple) or how[0] == "fill":
        return weights_glm5._leaf(shape, how, pdt)
    what = (shape, how, str(pdt))
    if what not in _LEAF:
        if how[0] == "log_uniform":
            def make(k):
                return jnp.log(jax.random.uniform(
                    k, shape, jnp.float32, how[1], how[2]))
        elif how[0] == "dt_bias":
            def make(k):
                dt = jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, math.log(how[1]),
                    math.log(how[2])))
                dt = jnp.maximum(dt, how[3])
                return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1
        else:
            raise ValueError(f"no way to make a leaf {how!r}")
        _LEAF[what] = jax.jit(lambda k: make(k).astype(pdt))
    return _LEAF[what]


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
    return jax.tree.unflatten(
        treedef, [_leaf(shape, how, pdt)(k) for k, shape, how in leaves])


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
        norm(leaf, _leaf(shape, how, pdt)(k))
        for (k, shape, how), leaf in zip(leaves, mine)]))
