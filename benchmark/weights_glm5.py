"""Seeded weights for the `glm-5` configuration, made on the device by
one small jitted jax.random program a leaf (weights_olmoe.py makes
OLMoE's tree in one program; here a leaf must be made AGAIN alone, and
only the same executable is sure to give the same bits; batches and
the seed's key are weights.py's own).

The tree is the one ompi_tpu.models.transformer.init_params builds for
this configuration (same names, shapes, scales; checked at toy width in
benchmark/tests): per layer the latent attention's low-rank matrices
with their norms, the indexer's three matrices and its key norm, and
either one dense FFN or a router with its correction bias, the HELD
experts' three matrices and the shared expert's; an untied head; one
multi-token-prediction module (a layer of the last kind, two norms, the
merge matrix). Nothing here imports the program: the plain reference
starts from the same call.
"""

from __future__ import annotations

import functools
import math

from benchmark.weights import seed_key


def plan(cfg: dict):
    """name tree of (shape, scale) or (shape, ("fill", value))."""
    d, v, h = cfg["d_model"], cfg["vocab"], cfg["n_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, dv = cfg["qk_nope_dim"], cfg["qk_rope_dim"], cfg["v_head_dim"]
    hi, di = cfg["index_heads"], cfg["index_dim"]
    e, held, fe = cfg["n_experts"], cfg["held_count"], cfg["moe_d_ff"]
    fs = cfg["n_shared_experts"] * fe
    s_emb = 1.0 / math.sqrt(d)

    def gain(n=d):
        return {"g": ((n,), ("fill", 1.0))}

    def layer(moe: bool):
        lp = {
            "ln1": gain(), "ln2": gain(),
            "wq_a": ((d, rq), s_emb), "q_a_norm": gain(rq),
            "wq_b": ((rq, h * (nope + rope)), 1.0 / math.sqrt(rq)),
            "wkv_a": ((d, rkv + rope), s_emb), "kv_a_norm": gain(rkv),
            "wkv_b": ((rkv, h * (nope + dv)), 1.0 / math.sqrt(rkv)),
            "wo": ((h * dv, d), 1.0 / math.sqrt(h * dv)
                   / math.sqrt(2 * cfg["n_layers"])),
            "wi_q": ((rq, hi * di), 1.0 / math.sqrt(rq)),
            "wi_k": ((d, di), s_emb),
            "wi_k_norm": {"g": ((di,), ("fill", 1.0)),
                          "b": ((di,), ("fill", 0.0))},
            "wi_w": ((d, hi), s_emb),
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


_LEAF = {}


def _leaf(shape, how, pdt):
    """The jitted program that makes one leaf from its key, kept: the
    same executable gives the same bits every time it is asked (a leaf
    made inside ANOTHER program need not: the compiler may fuse the
    normal's float arithmetic differently)."""
    import jax
    import jax.numpy as jnp

    what = (shape, how, str(pdt))
    if what not in _LEAF:
        if isinstance(how, tuple):
            _LEAF[what] = jax.jit(lambda k: jnp.full(shape, how[1], pdt))
        else:
            _LEAF[what] = jax.jit(lambda k: (
                jax.random.normal(k, shape, jnp.float32) * how).astype(pdt))
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
    """The whole tree in cfg["param_dtype"], from --seed, one small
    jitted program a leaf (a few dozen distinct ones)."""
    import jax
    import jax.numpy as jnp

    pdt = jnp.dtype(cfg["param_dtype"])
    treedef, leaves = _plan_leaves(cfg, seed)
    return jax.tree.unflatten(
        treedef, [_leaf(shape, how, pdt)(k) for k, shape, how in leaves])


@functools.lru_cache(maxsize=None)
def _norm_of_difference():
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda x, y: jnp.sqrt(jnp.sum(
        (x.astype(jnp.float32) - y.astype(jnp.float32)) ** 2)))


def delta_norms(cfg: dict, seed: int, now):
    """Per leaf of `now`, in tree order, the float32 norm of (leaf -
    the seed's leaf): compare.leaf_delta_norms against device_init,
    with the seed's tree made again ONE LEAF AT A TIME by device_init's
    own programs — the state is 6.6 GB at the published widths and does
    not fit the chip twice. A tree fresh from device_init reads 0.0 in
    every leaf (the runner checks that on the chip)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    pdt = jnp.dtype(cfg["param_dtype"])
    _, leaves = _plan_leaves(cfg, seed)
    mine = jax.tree.leaves(now)
    if len(mine) != len(leaves):
        raise ValueError(f"{len(mine)} leaves against the plan's "
                         f"{len(leaves)}")
    norm = _norm_of_difference()
    return np.asarray(jax.device_get([
        norm(leaf, _leaf(shape, how, pdt)(k))
        for (k, shape, how), leaf in zip(leaves, mine)]))
