"""A native-resolution vision tower in front of the decoder.

A decoder of ``models/transformer.py`` whose ``Config.vision`` is set
(Kimi-VL's; reference ``benchmark/reference/kimivl_decoder.py``) reads
images: a MoonViT-style encoder turns each image's 14 x 14 patches into
rows, a projector merges every 2 x 2 neighbourhood of them into ONE row
of the decoder's width, and those rows take the place of the
embedding's rows at the image positions of the sequence. Imported only
by a job whose ``Config.vision`` is set (a :class:`VisionConfig`).

*Packing is data.* A step's images lie back to back in ONE row of `P`
patches, each image at its own resolution. Everything that says which
patch belongs where is an INPUT of the step, not a constant of its
trace — so another mix of grids with the same `P` runs the same
executable. A batch is a dict (:func:`pack` builds the packing leaves
on the host from the list of grids):

- ``tokens`` [B, T] int32: the ids (anything at an image position);
- ``patches`` [P, patch_dim]: the pixels, image by image in raster order;
- ``image_ids`` [P] int32: the image a patch belongs to, never
  decreasing along the row — the attention's segment mask;
- ``patch_pos`` [P, 2] int32: a patch's (row, column) in its image;
- ``pos_index`` [P, 16] int32, ``pos_weight`` [P, 16] float32: the
  bicubic taps of the learned position table for that patch (the table
  is resized to the image's own grid, ``align_corners=False``, a = -0.75,
  edges clamped);
- ``merge_index`` [P / m^2, m^2] int32: the patches of each merged
  neighbourhood, (0, 0), (0, 1), (1, 0), (1, 1) for m = 2, the
  neighbourhoods in raster order image by image;
- ``image_positions`` [P / m^2] int32: where in the flattened [B * T]
  sequence each merged row goes.

The equations (:func:`tower`): ``h = patches W + b + table resized``;
`n_layers` pre-LayerNorm blocks — ``q, k, v = LN(h) Wqkv + b`` in heads
of `head_dim`, 2-D RoPE on q and k (a head's complex pairs turned
alternately by the patch's column and its row, `head_dim` / 4
frequencies each, theta ** (-4 j / head_dim)), attention both ways over
the image's own patches and no others (``ops.attention.attention`` with
the image ids as its segment mask: the repo's segment kernels on the
TPU, tiles between two images skipped), ``h += o Wo + b``, ``h += gelu_tanh(LN(h) W1 + b)
W2 + b`` —; a final LayerNorm; the merge; the projector
``gelu(LN(x) per patch, merged, W1 + b) W2 + b`` into the decoder's
width. Biases everywhere, as the source has them.

Names on the device, all INSIDE the decoder's ``embed`` scope (what the
tower makes IS the embedding of the image positions): ``vision`` >
``vit_embed`` (patch product + position table), ``vit_<i>`` > {``ln``,
``attn_proj`` > ``rope2d``, ``attn_core``, ``mlp``}, ``vit_merge``
(final norm, merge, projector; the scatter into the sequence is the
caller's, under ``embed/vision/vit_merge`` too). With ``Config.remat``
every block is recomputed in the backward pass (models/remat.py's
`Recomputed`) but for the named values the rule chose for the trace:
the tower's 27 applications enter it with `application`'s costs beside
the decoder's layers.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ompi_tpu.core import pvar
from ompi_tpu.models.remat import (ATTN_PROJ_OUT, MLP_UP, Application,
                                   Recomputed)
from ompi_tpu.ops import attention as att


@dataclasses.dataclass(frozen=True)
class VisionConfig:
    """The tower and its projector, by their widths."""
    d_model: int = 1152
    n_layers: int = 27
    n_heads: int = 16
    d_ff: int = 4304
    #: a patch's pixels: 14 x 14 x 3
    patch_dim: int = 588
    #: the learned position table's (rows, columns)
    pos_grid: Tuple[int, int] = (64, 64)
    #: side of the neighbourhood merged into one row
    merge: int = 2
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def merged_dim(self) -> int:
        return self.merge * self.merge * self.d_model


def init_params(rng: np.random.Generator, vc: VisionConfig, d_out: int,
                pdt) -> Dict:
    """The tower's and the projector's parameters (host numpy), at
    transformer.init_params' scales; `d_out` is the decoder's width."""
    def normal(*shape, scale):
        return np.asarray(rng.standard_normal(shape) * scale, dtype=pdt)

    def ln(n):
        return {"g": np.ones(n, pdt), "b": np.zeros(n, pdt)}

    d, f, m = vc.d_model, vc.d_ff, vc.merged_dim
    s = 1.0 / math.sqrt(d)

    def layer():
        return {"ln1": ln(d), "ln2": ln(d),
                "wqkv": normal(d, 3 * d, scale=s), "bqkv": np.zeros(3 * d, pdt),
                "wo": normal(d, d, scale=s / math.sqrt(2 * vc.n_layers)),
                "bo": np.zeros(d, pdt),
                "w1": normal(d, f, scale=s), "b1": np.zeros(f, pdt),
                "w2": normal(f, d, scale=1.0 / math.sqrt(f)),
                "b2": np.zeros(d, pdt)}

    return {
        "patch": {"w": normal(vc.patch_dim, d,
                              scale=1.0 / math.sqrt(vc.patch_dim)),
                  "b": np.zeros(d, pdt)},
        "pos": normal(*vc.pos_grid, d, scale=0.02),
        "layers": [layer() for _ in range(vc.n_layers)],
        "ln_f": ln(d),
        "proj": {"ln": ln(d), "w1": normal(m, m, scale=1.0 / math.sqrt(m)),
                 "b1": np.zeros(m, pdt),
                 "w2": normal(m, d_out, scale=1.0 / math.sqrt(m)),
                 "b2": np.zeros(d_out, pdt)},
    }


def like_params(vc: VisionConfig, leaf) -> Dict:
    """A tree of init_params' structure with `leaf` everywhere (the
    tower is replicated: no axis shards it yet)."""
    ln = {"g": leaf, "b": leaf}
    layer = dict(ln1=ln, ln2=ln, **{n: leaf for n in (
        "wqkv", "bqkv", "wo", "bo", "w1", "b1", "w2", "b2")})
    return {"patch": {"w": leaf, "b": leaf}, "pos": leaf,
            "layers": [layer] * vc.n_layers, "ln_f": ln,
            "proj": dict(ln=ln, w1=leaf, b1=leaf, w2=leaf, b2=leaf)}


# -- the packing, on the host --------------------------------------------------

def _cubic(x, a: float = -0.75):
    """The cubic convolution kernel (Keys), |x| < 2."""
    x = np.abs(x)
    return np.where(
        x <= 1, ((a + 2) * x - (a + 3)) * x * x + 1,
        np.where(x < 2, (((x - 5) * x + 8) * x - 4) * a, 0.0))


def _axis_taps(out: int, size: int):
    """(index [out, 4], weight [out, 4]) of a bicubic resize of `size`
    samples to `out` along one axis: sample centres aligned
    (``align_corners=False``), indices clamped at the edges."""
    src = (np.arange(out) + 0.5) * (size / out) - 0.5
    first = np.floor(src).astype(np.int64) - 1
    idx = first[:, None] + np.arange(4)[None, :]
    weight = _cubic(src[:, None] - idx)
    return np.clip(idx, 0, size - 1), weight


def bicubic_taps(rows: int, cols: int, grid: Tuple[int, int]):
    """(index [rows * cols, 16] into the flattened table, weight
    [rows * cols, 16]) of the position table resized to an image of
    `rows` x `cols` patches, raster order."""
    ri, rw = _axis_taps(rows, grid[0])
    ci, cw = _axis_taps(cols, grid[1])
    index = ri[:, None, :, None] * grid[1] + ci[None, :, None, :]
    weight = rw[:, None, :, None] * cw[None, :, None, :]
    return (index.reshape(rows * cols, 16).astype(np.int32),
            weight.reshape(rows * cols, 16).astype(np.float32))


def pack(grids: Sequence[Tuple[int, int]], positions, vc: VisionConfig):
    """The packing leaves of a batch (numpy; everything but ``tokens``
    and ``patches``) for images of `grids` [(rows, columns)] back to
    back in one row; `positions`: per image, the places in the
    flattened [B * T] sequence of its merged rows, raster order."""
    m = vc.merge
    ids, pos, index, weight, merged = [], [], [], [], []
    first = 0
    for i, (r, c) in enumerate(grids):
        if r % m or c % m:
            raise ValueError(f"a {r} x {c} grid does not merge {m} x {m}")
        ids.append(np.full(r * c, i, np.int32))
        pos.append(np.stack(np.divmod(np.arange(r * c), c), 1))
        a, b = bicubic_taps(r, c, vc.pos_grid)
        index.append(a)
        weight.append(b)
        # neighbourhood (R, C) holds patches (m R + dr, m C + dc)
        rr = (np.arange(r // m) * m)[:, None, None, None] \
            + np.arange(m)[None, None, :, None]
        cc = (np.arange(c // m) * m)[None, :, None, None] \
            + np.arange(m)[None, None, None, :]
        merged.append((first + rr * c + cc).reshape(-1, m * m))
        first += r * c
    where = np.concatenate([np.asarray(p) for p in positions])
    out = {"image_ids": np.concatenate(ids),
           "patch_pos": np.concatenate(pos).astype(np.int32),
           "pos_index": np.concatenate(index),
           "pos_weight": np.concatenate(weight),
           "merge_index": np.concatenate(merged).astype(np.int32),
           "image_positions": where.astype(np.int32)}
    if len(where) != len(out["merge_index"]):
        raise ValueError(f"{len(where)} positions for "
                         f"{len(out['merge_index'])} merged rows")
    return out


# -- the tower ------------------------------------------------------------------

def _ln(x, p, eps: float):
    with jax.named_scope("ln"):
        x = x.astype(jnp.float32)
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) * lax.rsqrt(var + eps) * p["g"] + p["b"]


def rope2d_angles(patch_pos, vc: VisionConfig):
    """float32 [P, head_dim / 2]: the angle of each complex pair of a
    head at each patch — pair 2j turns by column x f_j, pair 2j + 1 by
    row x f_j, f_j = theta ** (-4 j / head_dim)."""
    n = vc.head_dim // 4
    freq = vc.rope_theta ** (-4.0 * jnp.arange(n, dtype=jnp.float32)
                             / vc.head_dim)
    pos = patch_pos.astype(jnp.float32)
    col, row = pos[:, 1:2] * freq[None], pos[:, 0:1] * freq[None]
    return jnp.stack([col, row], axis=-1).reshape(pos.shape[0], 2 * n)


def rope2d(x, angles):
    """x [P, H, Dh] float32 turned pair by pair (dimension 2p with
    2p + 1) by `angles` [P, Dh / 2]."""
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    pairs = x.reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def position_rows(table, index, weight):
    """float32 [P, d]: the position table resized to every image's
    grid, as ONE product — the taps become a [P, rows x columns] matrix
    (16 non-zeros a row) and the table's gradient its transpose's
    product, not a scatter."""
    flat = table.reshape(-1, table.shape[-1]).astype(jnp.float32)
    cells = lax.broadcasted_iota(jnp.int32, (index.shape[0], flat.shape[0]),
                                 1)
    taps = sum(jnp.where(index[:, k, None] == cells, weight[:, k, None], 0.0)
               for k in range(index.shape[1]))
    return jnp.dot(taps, flat, precision=lax.Precision.HIGHEST)


def _embed(vp, batch, dt):
    with jax.named_scope("vit_embed"):
        x = jnp.dot(batch["patches"].astype(dt), vp["patch"]["w"].astype(dt),
                    preferred_element_type=jnp.float32)
        x = x + vp["patch"]["b"].astype(jnp.float32) + position_rows(
            vp["pos"], batch["pos_index"], batch["pos_weight"])
        return x.astype(dt)


def layer_forward(lp, h, angles, image_ids, vc: VisionConfig, dt):
    """One block of the tower on the packed row h [P, d]."""
    p, d = h.shape
    heads, dh = vc.n_heads, vc.head_dim
    x = _ln(h, lp["ln1"], vc.norm_eps).astype(dt)
    with jax.named_scope("attn_proj"):
        qkv = (x @ lp["wqkv"].astype(dt) + lp["bqkv"].astype(dt)).reshape(
            p, 3, heads, dh)
        with jax.named_scope("rope2d"):
            # 1 / sqrt(Dh) goes in where q is float32: rounded once
            q = (rope2d(qkv[:, 0].astype(jnp.float32), angles)
                 * dh ** -0.5).astype(dt)
            k = rope2d(qkv[:, 1].astype(jnp.float32), angles).astype(dt)
    with jax.named_scope("attn_core"):
        o = att.attention(q[None], k[None], qkv[None, :, 2], causal=False,
                          scale=1.0, segments=image_ids[None])[0]
    with jax.named_scope("attn_proj"):
        h = h + checkpoint_name(
            o.reshape(p, d) @ lp["wo"].astype(dt) + lp["bo"].astype(dt),
            ATTN_PROJ_OUT)
    x = _ln(h, lp["ln2"], vc.norm_eps).astype(dt)
    with jax.named_scope("mlp"):
        u = checkpoint_name(x @ lp["w1"].astype(dt) + lp["b1"].astype(dt),
                            MLP_UP)
        return h + (jax.nn.gelu(u, approximate=True) @ lp["w2"].astype(dt)
                    + lp["b2"].astype(dt))


def _merge(vp, h, merge_index, vc: VisionConfig, dt):
    with jax.named_scope("vit_merge"):
        x = _ln(h, vp["ln_f"], vc.norm_eps).astype(dt)
        pp = vp["proj"]
        x = _ln(x, pp["ln"], vc.norm_eps).astype(dt)
        x = x[merge_index].reshape(merge_index.shape[0], vc.merged_dim)
        u = jax.nn.gelu(x @ pp["w1"].astype(dt) + pp["b1"].astype(dt),
                        approximate=False)
        return u @ pp["w2"].astype(dt) + pp["b2"].astype(dt)


def tower(vp, batch, vc: VisionConfig, dt, remat: bool = False,
          keep: Tuple[str, ...] = ()):
    """The merged, projected rows [P / m^2, d_out] of a batch's packed
    images, in `dt`. Where `remat`, every block is one application of
    the recomputation rule, keeping `keep`, and the embedding and the
    merge are recomputed whole. Counted once per traced step: pvars
    ``vision_patches``, ``vision_image_positions``, and per application
    what the attention counts of itself and the rule of its own."""
    pvar.record("vision_patches", batch["patches"].shape[0])
    pvar.record("vision_image_positions", batch["image_positions"].shape[0])

    def block(lp, h, angles, image_ids):
        return layer_forward(lp, h, angles, image_ids, vc, dt)

    def whole(fn):  # recomputed from its input; no application of the rule
        return Recomputed(fn, ()) if remat else fn

    if remat:
        block = Recomputed(block, keep, application(
            vc, batch["patches"].shape[0], jnp.dtype(dt).itemsize).sizes)
    with jax.named_scope("vision"):
        h = whole(lambda vp, b: _embed(vp, b, dt))(
            {"patch": vp["patch"], "pos": vp["pos"]},
            {n: batch[n] for n in ("patches", "pos_index", "pos_weight")})
        angles = rope2d_angles(batch["patch_pos"], vc)
        for i, lp in enumerate(vp["layers"]):
            with jax.named_scope(f"vit_{i}"):
                h = block(lp, h, angles, batch["image_ids"])
        return whole(lambda vp, h, index: _merge(vp, h, index, vc, dt))(
            {"ln_f": vp["ln_f"], "proj": vp["proj"]}, h,
            batch["merge_index"])


def place(h, rows, image_positions):
    """h [B, T, d] with the tower's `rows` in the place of the
    embedding's at `image_positions` of the flattened sequence."""
    with jax.named_scope("vision"), jax.named_scope("vit_merge"):
        b, t, d = h.shape
        return h.reshape(b * t, d).at[image_positions].set(
            rows.astype(h.dtype), unique_indices=True).reshape(b, t, d)


# -- the recomputation rule's layer kind -----------------------------------------

def application(vc: VisionConfig, patches: int, itemsize: int) -> Application:
    """What ONE application of a tower block over `patches` rows costs
    the recomputation rule. Bytes: q, k, v and the output as the
    segment kernels hold them — ``[heads, patches, head_dim]``, where
    the device's tiled layout gives every row of a head whole lanes (72
    lie in 128) though no copy pads them — and the per-row log-sum-exp
    as ``[heads, patches]`` float32. Spared operations: which patches
    share an image is data, so attention's is reckoned over HALF the
    packed row's square, as a causal layer's is (the cell's four images
    fill 0.345 of it)."""
    n, d, wide = patches, vc.d_model, att.lanes(vc.head_dim)
    return Application(
        {ATTN_PROJ_OUT: n * d * itemsize, MLP_UP: n * vc.d_ff * itemsize,
         att.ATTN_OUT: n * vc.n_heads * (wide * itemsize + 4),
         att.QKV: 3 * n * vc.n_heads * wide * itemsize},
        {ATTN_PROJ_OUT: 2 * n * d * d, MLP_UP: 2 * n * d * vc.d_ff,
         att.ATTN_OUT: 2 * n * n * d, att.QKV: 3 * 2 * n * d * d},
        n * d * itemsize)


# -- a set-up probe ------------------------------------------------------------

def vision_stats(batch) -> Dict[str, float]:
    """What a batch's packing holds, read on the host outside any timed
    window: patches, images, image positions, and the share of the
    packed row's (query, key) pairs that lie on the block diagonal
    (`diag_share`: what a mask-blind kernel would waste is the rest),
    the largest image's share of those. Counted into the always-on
    counters `vision_images`, `vision_diag_pairs`, `vision_row_pairs`."""
    ids = np.asarray(batch["image_ids"])
    sizes = np.bincount(ids - ids.min()).astype(np.int64)
    sizes = sizes[sizes > 0]
    diag, row = int((sizes ** 2).sum()), int(ids.size) ** 2
    pvar.record("vision_images", len(sizes))
    pvar.record("vision_diag_pairs", diag)
    pvar.record("vision_row_pairs", row)
    return {"patches": int(ids.size), "images": len(sizes),
            "image_positions": int(np.asarray(
                batch["image_positions"]).size),
            "diag_pairs": diag, "row_pairs": row, "diag_share": diag / row,
            "largest_image_share": float(sizes.max() ** 2 / diag)}
