"""Seeded weights and batches, made on the device by one jitted
jax.random program each (copied from chip_smoke._device_init; host
init_params is 117 s of numpy at OPT-30B's widths, PERF.md PR 21).

The tree is the one ompi_tpu.models.transformer.init_params builds
(same names, shapes, scales), checked at toy width in
benchmark/tests. Nothing here imports the program: the plain
reference starts from the same call.
"""

from __future__ import annotations

import math


def plan(cfg: dict):
    """name tree of (shape, scale) or (shape, ("fill", value))."""
    d, f, v = cfg["d_model"], cfg["d_ff"], cfg["vocab"]
    s_emb = 1.0 / math.sqrt(d)
    ones, zeros = ("fill", 1.0), ("fill", 0.0)

    def ln():
        return {"g": ((d,), ones), "b": ((d,), zeros)}

    return {
        "embed": ((v, d), s_emb), "pos": ((cfg["max_seq"], d), 0.02),
        "ln_f": ln(),
        "layers": [{
            "ln1": ln(), "ln2": ln(),
            "wq": ((d, d), s_emb), "wk": ((d, d), s_emb),
            "wv": ((d, d), s_emb),
            "wo": ((d, d), s_emb / math.sqrt(2 * cfg["n_layers"])),
            "w1": ((d, f), s_emb), "w2": ((f, d), 1.0 / math.sqrt(f)),
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
    key = (cfg["d_model"], cfg["d_ff"], cfg["vocab"], cfg["max_seq"],
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


def batches(vocab: int, n: int, batch: int, seq: int, seed: int):
    """n batches of seeded tokens with their next-token labels, as two
    lists of [batch, seq] int32 device arrays: rows all differ."""
    import jax
    import jax.numpy as jnp

    def make(k):
        tok = jax.random.randint(k, (n, batch, seq), 0, vocab, jnp.int32)
        return tok, jnp.roll(tok, -1, axis=-1)

    tok, lab = jax.jit(make)(jax.random.fold_in(seed_key(seed), 1))
    return [tok[i] for i in range(n)], [lab[i] for i in range(n)]


def seed_key(seed: int):
    """--seed is any whole number up to a little over 2**31; fold both
    halves in so that none is lost to a 32-bit key."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF),
                              seed >> 31)
