"""Seeded weights for the `ouro-2.6b` configuration, made on the device
one leaf at a time by weights_glm5.py's kept per-leaf programs (the
same executable gives the same bits every time it is asked, so the
seed's tree made again is the tree a run started from; batches and the
seed's key are weights.py's own).

The tree is the one ompi_tpu.models.transformer.init_params builds for
this configuration (same names, shapes, scales; checked at toy width in
benchmark/tests): RMSNorm gains without a bias, four norms a layer (the
two on the sub-layers' outputs are `ln1_post`, `ln2_post`), full MHA,
a gated FFN, an untied head, and the exit gate (a vector of the hidden
width and one bias). The layer list is made ONCE: every pass runs it.
Nothing here imports the program: the plain reference starts from the
same call.
"""

from __future__ import annotations

import math

from benchmark import weights_glm5
from benchmark.weights import seed_key


def plan(cfg: dict):
    """name tree of (shape, scale) or (shape, ("fill", value))."""
    d, f, v = cfg["d_model"], cfg["d_ff"], cfg["vocab"]
    s_emb = 1.0 / math.sqrt(d)

    def gain():
        return {"g": ((d,), ("fill", 1.0))}

    return {
        "embed": ((v, d), s_emb), "head": ((v, d), s_emb), "ln_f": gain(),
        "exit_gate": {"w": ((d,), s_emb), "b": ((1,), ("fill", 0.0))},
        "layers": [{
            "ln1": gain(), "ln1_post": gain(), "ln2": gain(),
            "ln2_post": gain(),
            "wq": ((d, d), s_emb), "wk": ((d, d), s_emb),
            "wv": ((d, d), s_emb),
            "wo": ((d, d), s_emb / math.sqrt(2 * cfg["n_layers"])),
            "w1": ((d, f), s_emb), "w3": ((d, f), s_emb),
            "w2": ((f, d), 1.0 / math.sqrt(f)),
        } for _ in range(cfg["n_layers"])],
    }


def device_init(cfg: dict, seed: int):
    """The whole tree in cfg["param_dtype"], from --seed."""
    import jax
    import jax.numpy as jnp

    pdt = jnp.dtype(cfg["param_dtype"])
    leaves, treedef = jax.tree.flatten(
        plan(cfg), is_leaf=lambda t: isinstance(t, tuple))
    keys = jax.random.split(seed_key(seed), len(leaves))
    return jax.tree.unflatten(treedef, [
        weights_glm5._leaf(shape, how, pdt)(k)
        for k, (shape, how) in zip(keys, leaves)])
