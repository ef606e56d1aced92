"""Seeded weights for the `olmoe-1b-7b` configuration, made on the
device by one jitted jax.random program (as weights.py does for OPT;
batches and the seed's key are weights.py's own).

The tree is the one ompi_tpu.models.transformer.init_params builds for
this configuration (RMSNorm gains without a bias, no position table,
an untied head, QK-norm gains, a router and three expert matrices per
layer; same names, shapes, scales), checked at toy width in
benchmark/tests. Nothing here imports the program: the plain reference
starts from the same call.
"""

from __future__ import annotations

import math

from benchmark.weights import seed_key


def plan(cfg: dict):
    """name tree of (shape, scale) or (shape, ("fill", value))."""
    d, f, v, e = cfg["d_model"], cfg["d_ff"], cfg["vocab"], cfg["n_experts"]
    s_emb = 1.0 / math.sqrt(d)

    def gain():
        return {"g": ((d,), ("fill", 1.0))}

    return {
        "embed": ((v, d), s_emb), "head": ((v, d), s_emb),
        "ln_f": gain(),
        "layers": [{
            "ln1": gain(), "ln2": gain(),
            "q_norm": gain(), "k_norm": gain(),
            "wq": ((d, d), s_emb), "wk": ((d, d), s_emb),
            "wv": ((d, d), s_emb),
            "wo": ((d, d), s_emb / math.sqrt(2 * cfg["n_layers"])),
            "wg": ((d, e), s_emb),
            "w1": ((e, d, f), s_emb), "w3": ((e, d, f), s_emb),
            "w2": ((e, f, d), 1.0 / math.sqrt(f)),
        } for _ in range(cfg["n_layers"])],
    }


_INIT = {}


def device_init(cfg: dict, seed: int):
    """The whole tree in cfg["param_dtype"], from --seed. The jitted
    program is kept, so a second call with the same sizes runs the
    same executable and returns the same bits."""
    import jax
    import jax.numpy as jnp

    pdt = jnp.dtype(cfg["param_dtype"])
    key = (cfg["d_model"], cfg["d_ff"], cfg["vocab"], cfg["n_experts"],
           cfg["n_layers"], str(pdt))
    if key not in _INIT:
        is_leaf = lambda t: isinstance(t, tuple)  # noqa: E731
        leaves, treedef = jax.tree.flatten(plan(cfg), is_leaf=is_leaf)

        def make(k):
            out = []
            for kk, (shape, how) in zip(
                    jax.random.split(k, len(leaves)), leaves):
                if isinstance(how, tuple):
                    out.append(jnp.full(shape, how[1], pdt))
                else:
                    out.append((jax.random.normal(kk, shape, jnp.float32)
                                * how).astype(pdt))
            return jax.tree.unflatten(treedef, out)

        _INIT[key] = jax.jit(make)
    return _INIT[key](seed_key(seed))
