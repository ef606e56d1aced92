"""Seeded weights and batches for the `kimi-vl-a3b` configuration, made
on the device one leaf at a time by weights_glm5.py's kept per-leaf
programs (the same executable gives the same bits every time it is
asked, so the seed's tree made again is the tree a run started from;
the seed's key is weights.py's own).

The tree is the one ompi_tpu.models.transformer.init_params builds for
this configuration (same names, shapes, scales; checked at toy width in
benchmark/tests): per decoder layer the latent attention WITHOUT a
query latent (`wq` is one matrix), its kv path with its norm, and
either one dense FFN or a router with its correction bias, the held
experts' three matrices and the shared expert's; an untied head; and
under `vision` the tower — the patch product, the learned position
table, per block two LayerNorms (gain and bias), the fused q/k/v
product, the output product and the MLP, each with its bias, the final
LayerNorm — and the projector. Nothing here imports the program: the
plain reference starts from the same call.

A batch is the dict the program's step takes (the names of
ompi_tpu/models/vision.py's docstring, built here without it): the
ids, the pixels, and the packing of the cell's images — image ids,
(row, column) of every patch, the bicubic taps of the position table,
the patches of every merged neighbourhood, the image positions of the
sequence. Labels are the next token where that is a text token, -1
elsewhere.
"""

from __future__ import annotations

import math

from benchmark import weights_glm5
from benchmark.weights import seed_key

ONES, ZEROS = ("fill", 1.0), ("fill", 0.0)


def plan(cfg: dict):
    """name tree of (shape, scale) or (shape, ("fill", value))."""
    d, v, h = cfg["d_model"], cfg["vocab"], cfg["n_heads"]
    rkv = cfg["kv_lora_rank"]
    nope, rope, dv = cfg["qk_nope_dim"], cfg["qk_rope_dim"], cfg["v_head_dim"]
    e, held, fe = cfg["n_experts"], cfg["held_count"], cfg["moe_d_ff"]
    fs = cfg["n_shared_experts"] * fe
    s_emb = 1.0 / math.sqrt(d)

    def gain(n=d):
        return {"g": ((n,), ONES)}

    def layer(moe: bool):
        lp = {
            "ln1": gain(), "ln2": gain(),
            "wq": ((d, h * (nope + rope)), s_emb),
            "wkv_a": ((d, rkv + rope), s_emb), "kv_a_norm": gain(rkv),
            "wkv_b": ((rkv, h * (nope + dv)), 1.0 / math.sqrt(rkv)),
            "wo": ((h * dv, d), 1.0 / math.sqrt(h * dv)
                   / math.sqrt(2 * cfg["n_layers"])),
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

    return {
        "embed": ((v, d), s_emb), "head": ((v, d), s_emb), "ln_f": gain(),
        "layers": [layer(i >= cfg["first_dense"])
                   for i in range(cfg["n_layers"])],
        "vision": vision_plan(cfg["vision"], d),
    }


def vision_plan(vc: dict, d_out: int):
    d, f = vc["d_model"], vc["d_ff"]
    m = vc["merge"] ** 2 * d
    s = 1.0 / math.sqrt(d)

    def ln(n=d):
        return {"g": ((n,), ONES), "b": ((n,), ZEROS)}

    def layer():
        return {"ln1": ln(), "ln2": ln(),
                "wqkv": ((d, 3 * d), s), "bqkv": ((3 * d,), ZEROS),
                "wo": ((d, d), s / math.sqrt(2 * vc["n_layers"])),
                "bo": ((d,), ZEROS),
                "w1": ((d, f), s), "b1": ((f,), ZEROS),
                "w2": ((f, d), 1.0 / math.sqrt(f)), "b2": ((d,), ZEROS)}

    return {
        "patch": {"w": ((vc["patch_dim"], d),
                        1.0 / math.sqrt(vc["patch_dim"])),
                  "b": ((d,), ZEROS)},
        "pos": ((*vc["pos_grid"], d), 0.02),
        "layers": [layer() for _ in range(vc["n_layers"])],
        "ln_f": ln(),
        "proj": {"ln": ln(), "w1": ((m, m), 1.0 / math.sqrt(m)),
                 "b1": ((m,), ZEROS), "w2": ((m, d_out), 1.0 / math.sqrt(m)),
                 "b2": ((d_out,), ZEROS)},
    }


def _plan_leaves(cfg: dict, seed: int):
    import jax

    leaves, treedef = jax.tree.flatten(
        plan(cfg), is_leaf=lambda t: isinstance(t, tuple))
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
    """weights_glm5.delta_norms for this tree: per leaf of `now`, the
    float32 norm of (leaf - the seed's leaf), the seed's tree made
    again one leaf at a time."""
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


# -- the batch's packing, on the host ---------------------------------------------

def layout(images: list, text_run: int, seq: int):
    """(is_image [seq] bool, per image the positions of its merged
    rows) of ONE document: each image's merged rows, then `text_run`
    text positions, image after image."""
    import numpy as np

    is_image, places, at = np.zeros(seq, bool), [], 0
    for rows, cols in images:
        n = rows * cols // 4
        places.append(np.arange(at, at + n))
        is_image[at:at + n] = True
        at += n + text_run
    if at != seq:
        raise ValueError(f"the document fills {at} positions, not {seq}")
    return is_image, places


def _cubic(x, a=-0.75):
    import numpy as np

    x = np.abs(x)
    return np.where(x <= 1, ((a + 2) * x - (a + 3)) * x * x + 1,
                    np.where(x < 2, (((x - 5) * x + 8) * x - 4) * a, 0.0))


def _axis_taps(out: int, size: int):
    """Bicubic resize of `size` samples to `out` along one axis
    (torch's ``align_corners=False``: centres aligned, a = -0.75, edge
    samples repeated): (index [out, 4], weight [out, 4])."""
    import numpy as np

    src = (np.arange(out) + 0.5) * (size / out) - 0.5
    idx = np.floor(src).astype(np.int64)[:, None] - 1 + np.arange(4)[None]
    return np.clip(idx, 0, size - 1), _cubic(src[:, None] - idx)


def packing(images: list, places: list, grid: tuple, merge: int = 2) -> dict:
    """The packing leaves (numpy) for `images` [(rows, columns)] back
    to back in one row of patches, raster order inside an image."""
    import numpy as np

    ids, pos, index, weight, merged, first = [], [], [], [], [], 0
    for i, (r, c) in enumerate(images):
        ids.append(np.full(r * c, i, np.int32))
        pos.append(np.stack(np.divmod(np.arange(r * c), c), 1))
        ri, rw = _axis_taps(r, grid[0])
        ci, cw = _axis_taps(c, grid[1])
        index.append((ri[:, None, :, None] * grid[1]
                      + ci[None, :, None, :]).reshape(r * c, 16))
        weight.append((rw[:, None, :, None]
                       * cw[None, :, None, :]).reshape(r * c, 16))
        rr = (np.arange(r // merge) * merge)[:, None, None, None] \
            + np.arange(merge)[None, None, :, None]
        cc = (np.arange(c // merge) * merge)[None, :, None, None] \
            + np.arange(merge)[None, None, None, :]
        merged.append((first + rr * c + cc).reshape(-1, merge * merge))
        first += r * c
    return {"image_ids": np.concatenate(ids),
            "patch_pos": np.concatenate(pos).astype(np.int32),
            "pos_index": np.concatenate(index).astype(np.int32),
            "pos_weight": np.concatenate(weight).astype(np.float32),
            "merge_index": np.concatenate(merged).astype(np.int32),
            "image_positions": np.concatenate(places).astype(np.int32)}


def batches(cfg: dict, traffic: dict, seed: int):
    """n batches from --seed as two lists: the step's dicts (ids
    uniform over the vocabulary slice, pixels standard normal in the
    activations' type, the cell's packing — the same in every batch)
    and the labels [batch, seq] (the next token where it is a text
    token, else -1)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    n, batch, seq = traffic["n_batches"], traffic["batch"], traffic["seq"]
    if batch != 1:
        raise ValueError("one document a step")
    images = [tuple(g) for g in traffic["images"]]
    is_image, places = layout(images, traffic["text_run"], seq)
    pack = {k: jnp.asarray(v) for k, v in packing(
        images, places, tuple(cfg["vision"]["pos_grid"]),
        cfg["vision"]["merge"]).items()}
    patches = sum(r * c for r, c in images)
    next_is_text = jnp.asarray(~np.roll(is_image, -1))
    next_is_text = next_is_text.at[seq - 1].set(False)

    def make(k):
        k1, k2 = jax.random.split(k)
        tok = jax.random.randint(k1, (n, batch, seq), 0, cfg["vocab"],
                                 jnp.int32)
        lab = jnp.where(next_is_text[None, None], jnp.roll(tok, -1, -1), -1)
        pix = jax.random.normal(
            k2, (n, patches, cfg["vision"]["patch_dim"]),
            jnp.float32).astype(jnp.bfloat16)
        return tok, lab, pix

    tok, lab, pix = jax.jit(make)(jax.random.fold_in(seed_key(seed), 1))
    return ([dict(pack, tokens=tok[i], patches=pix[i]) for i in range(n)],
            [lab[i] for i in range(n)])
