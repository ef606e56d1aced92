"""Kimi-VL-A3B's vision tower, projector and decoder, their training loss
and one SGD step, written plainly.

Straightforward jax.numpy in float32 under
jax.default_matmul_precision("highest"); no kernels, no packing trick
(the tower's attention runs ONE IMAGE AT A TIME over that image's own
patches, so there is no mask to get wrong; positions come from each
image's grid, not from index arrays; the decoder's mask is a dense
[T, T] one), nothing imported from the program. The decoder's shared
mathematics — RMSNorm, interleaved RoPE, attention one head at a time,
the sigmoid `noaux_tc` router, the experts one at a time, the gated
FFN, the head's loss, the update — are glm5_decoder.py's functions,
used here as they are.

It follows `moonshotai/Kimi-VL-A3B-Instruct` (`config.json`; the
public modeling files `modeling_kimi_vl.py`: MoonVitPretrainedModel,
KimiVLMultiModalProjector, DeepseekV3 decoder), `h` the residual
stream:

- *Tower* (MoonViT, widths of SigLIP-SO400M): a patch is 14 x 14 x 3 =
  588 numbers; `h = patch W + b + E(row, col)`, `E` the learned
  [64, 64, 1152] table resized BICUBICALLY to the image's own
  (rows, cols) grid (torch `F.interpolate(mode="bicubic")`:
  align_corners False, a = -0.75, edge samples repeated). 27 blocks,
  pre-LayerNorm (eps 1e-5, gain and bias): `[q|k|v] = LN(h) Wqkv + b`
  (fused: the output reshaped [3, heads, 72]); 2-D RoPE on q and k —
  the head's 36 complex pairs (dims 2p, 2p + 1) turn by angles
  `col * f_j` (p = 2j) and `row * f_j` (p = 2j + 1), `f_j = 10000 **
  (-4 j / 72)`, j < 18; softmax attention both ways over the image's
  patches, scale 72 ** -0.5; `h += o Wo + b`; `h += gelu_tanh(LN(h) W1
  + b) W2 + b` (4304 wide). A final LayerNorm.
- *Merge and projector.* Each 2 x 2 neighbourhood's four rows, in the
  order (0,0), (0,1), (1,0), (1,1), become ONE row of 4608 after a
  LayerNorm (eps 1e-5) on each 1152-wide row; `gelu(x W1 + b) W2 + b`
  (exact GELU; 4608 -> 4608 -> 2048). The merged rows, image by image
  in raster order, REPLACE the embedding's rows at the image positions
  of the sequence.
- *Decoder* (DeepseekV3 shape, no query latent: `q_lora_rank` null):
  `x = norm1(h)`; `q = x Wq`, per head `[q_nope 128 | q_rope 64]`;
  `[c_kv 512 | k_r 64] = x W_kva`, `c_kv = norm(c_kv)`; per head
  `[k_nope 128 | v 128] = c_kv W_kvb`; interleaved RoPE (theta 8e5) on
  `q_rope` and the ONE `k_r` all heads share; causal softmax, scale
  192 ** -0.5; `h += concat_heads(o) Wo`. Layer 0 has a SiLU-gated FFN
  of 11264; the others a router `s = sigmoid(x Wg)` over 64 experts,
  the 6 largest of `s + b` chosen, `g = 2.446 s / sum s` over the
  chosen, the chosen experts THIS chip holds (1408 wide) and one
  shared gated FFN of 2816. Final RMSNorm, untied head.
- loss = mean cross-entropy over the positions whose label is not -1
  (labels are the next token where it is a text token).

Departures from the public files, all shared with the program
(benchmark/configs/kimi-vl-a3b.json, `assumed`): the tower's widths are
the family's (the catalog's config holds the language model's keys
only); `seq_aux`'s loss weight is not in the config: 0; the selection
bias `b` is held fixed; images arrive as patches already (the
pre-processor's resize and normalisation are the host's); the public
attention calls flash-attention with cumulative sequence lengths,
which is the per-image attention here; plain SGD.

The step is taken a layer at a time (forward keeping each layer's
input, then one vjp per layer backwards, updating that layer at once),
as glm5_decoder.py does and for its reason. Precision as the
configuration states it: parameters STORED in `param_dtype`, the
gradient reaches the optimizer in that type, the update is computed in
float32 and rounded back; everything else float32. `quantize` puts the
control in the reference's place: every matmul operand the
configuration states as bfloat16 (the tower's, the projector's and the
decoder's products, the attention products, the head — not the router,
not the position table's resize) is rounded to a lower-precision type
first, with a straight-through gradient.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import glm5_decoder as g

F32 = jnp.float32
HIGHEST = "highest"


class Spec(NamedTuple):
    """What the equations need beside the weights' shapes."""
    n_heads: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_head_dim: int
    top_k: int
    #: ((rows, cols), ...) of the document's images, in order
    images: Tuple[Tuple[int, int], ...]
    #: text positions after each image
    text_run: int
    vit_heads: int = 16
    held_first: int = 0
    routed_scale: float = 2.446
    rope_theta: float = 8e5
    rms_eps: float = 1e-5
    vit_theta: float = 1e4
    vit_eps: float = 1e-5


# -- the tower ---------------------------------------------------------------------

def _cubic(x, a=-0.75):
    x = abs(x)
    if x <= 1:
        return ((a + 2) * x - (a + 3)) * x * x + 1
    if x < 2:
        return (((x - 5) * x + 8) * x - 4) * a
    return 0.0


def resize_matrix(out: int, size: int) -> np.ndarray:
    """[out, size]: row r holds the weights of the `size` samples in
    output sample r of a bicubic resize (centres aligned; a sample past
    an edge is the edge's)."""
    m = np.zeros((out, size))
    for r in range(out):
        src = (r + 0.5) * size / out - 0.5
        for tap in range(int(np.floor(src)) - 1, int(np.floor(src)) + 3):
            m[r, min(max(tap, 0), size - 1)] += _cubic(src - tap)
    return m.astype(np.float32)


def position_rows(table, rows: int, cols: int):
    """[rows * cols, d]: the table resized to a rows x cols grid,
    raster order (the resize is separable: rows, then columns)."""
    up = jnp.asarray(resize_matrix(rows, table.shape[0]))
    across = jnp.asarray(resize_matrix(cols, table.shape[1]))
    return jnp.einsum("ra,cb,abd->rcd", up, across, table).reshape(
        rows * cols, -1)


def rope_2d(x, rows: int, cols: int, theta: float):
    """x [heads, rows * cols, dh] of one image, raster order: complex
    pair p of a head turns by col * f_j (p = 2j) or row * f_j
    (p = 2j + 1)."""
    dh = x.shape[-1]
    freq = 1.0 / theta ** (jnp.arange(0, dh, 4, dtype=F32)[:dh // 4] / dh)
    col = jnp.tile(jnp.arange(cols, dtype=F32), rows)
    row = jnp.repeat(jnp.arange(rows, dtype=F32), cols)
    ang = jnp.stack([col[:, None] * freq, row[:, None] * freq],
                    axis=-1).reshape(rows * cols, dh // 2)
    re, im = x[..., 0::2], x[..., 1::2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return jnp.stack([re * cos - im * sin, re * sin + im * cos],
                     axis=-1).reshape(x.shape)


def _spans(spec: Spec):
    """[(first patch, the one past the last, rows, cols)] an image."""
    out, at = [], 0
    for r, c in spec.images:
        out.append((at, at + r * c, r, c))
        at += r * c
    return out


def vit_embed(patch, table, patches, spec: Spec, quantize=None):
    """The packed row [P, d] before the first block."""
    x = g._mm(patches.astype(F32), patch["w"].astype(F32), quantize) \
        + patch["b"].astype(F32)
    table = table.astype(F32)
    return x + jnp.concatenate([position_rows(table, r, c)
                                for _, _, r, c in _spans(spec)])


def vit_attention(lp, x, spec: Spec, quantize=None):
    """Attention of one block over the normed packed row x, image by
    image, one head at a time."""
    heads = spec.vit_heads
    qkv = g._mm(x, lp["wqkv"], quantize) + lp["bqkv"]
    dh = qkv.shape[-1] // (3 * heads)
    qkv = qkv.reshape(-1, 3, heads, dh)
    outs = []
    for a, b, r, c in _spans(spec):
        q, k, v = (jnp.moveaxis(qkv[a:b, i], 1, 0) for i in range(3))
        q = rope_2d(q, r, c, spec.vit_theta)
        k = rope_2d(k, r, c, spec.vit_theta)
        q, k, v = (g._q(t[None], quantize) for t in (q, k, v))
        o, _ = g.attend(q, k, v, jnp.ones((1, b - a, b - a), bool))
        outs.append(jnp.moveaxis(o[0], 0, 1).reshape(b - a, heads * dh))
    return g._mm(jnp.concatenate(outs), lp["wo"], quantize) + lp["bo"]


def vit_layer(lp, h, spec: Spec, quantize=None):
    lp = jax.tree.map(lambda a: a.astype(F32), lp)
    x = g.layer_norm(h, lp["ln1"]["g"], lp["ln1"]["b"], spec.vit_eps)
    h = h + vit_attention(lp, x, spec, quantize)
    x = g.layer_norm(h, lp["ln2"]["g"], lp["ln2"]["b"], spec.vit_eps)
    u = jax.nn.gelu(g._mm(x, lp["w1"], quantize) + lp["b1"],
                    approximate=True)
    return h + g._mm(u, lp["w2"], quantize) + lp["b2"]


def merge_project(ln_f, proj, h, spec: Spec, quantize=None):
    """The decoder-wide rows [P / 4, d_out] of the tower's output."""
    ln_f, proj = (jax.tree.map(lambda a: a.astype(F32), t)
                  for t in (ln_f, proj))
    x = g.layer_norm(h, ln_f["g"], ln_f["b"], spec.vit_eps)
    x = g.layer_norm(x, proj["ln"]["g"], proj["ln"]["b"], spec.vit_eps)
    merged = []
    for a, b, r, c in _spans(spec):
        img = x[a:b].reshape(r // 2, 2, c // 2, 2, -1)
        merged.append(img.transpose(0, 2, 1, 3, 4).reshape(
            (r // 2) * (c // 2), -1))
    x = jnp.concatenate(merged)
    u = jax.nn.gelu(g._mm(x, proj["w1"], quantize) + proj["b1"],
                    approximate=False)
    return g._mm(u, proj["w2"], quantize) + proj["b2"]


def image_positions(spec: Spec) -> np.ndarray:
    """Where in the sequence the merged rows go: each image's, then
    `text_run` text positions."""
    out, at = [], 0
    for r, c in spec.images:
        n = r * c // 4
        out.append(np.arange(at, at + n))
        at += n + spec.text_run
    return np.concatenate(out)


def embed_forward(embed, tokens, rows, spec: Spec):
    """[1, T, d]: the embedding's rows, the tower's at the image
    positions."""
    h = embed[tokens].astype(F32)
    return h.at[0, jnp.asarray(image_positions(spec))].set(rows)


# -- the decoder -------------------------------------------------------------------

def mla_project(lp, x, spec: Spec, quantize=None):
    """x [B, T, d] -> q, k [B, H, T, nope + rope], v [B, H, T, dv]."""
    b, t, _ = x.shape
    h, nope, rope = spec.n_heads, spec.qk_nope_dim, spec.qk_rope_dim
    q = g._mm(x, lp["wq"], quantize).reshape(b, t, h, nope + rope)
    q = jnp.moveaxis(q, 2, 1)
    kv_a = g._mm(x, lp["wkv_a"], quantize)
    rkv = kv_a.shape[-1] - rope
    c_kv = g.rms_norm(kv_a[..., :rkv], lp["kv_a_norm"]["g"], spec.rms_eps)
    kv = jnp.moveaxis(g._mm(c_kv, lp["wkv_b"], quantize).reshape(
        b, t, h, nope + spec.v_head_dim), 2, 1)
    k_r = g.rope_pairs(kv_a[..., rkv:], spec.rope_theta)
    q = jnp.concatenate(
        [q[..., :nope], g.rope_pairs(q[..., nope:], spec.rope_theta)], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r[:, None], (b, h, t, rope))],
        -1)
    return q, k, kv[..., nope:]


def attention_block(lp, h, spec: Spec, quantize=None):
    b, t, _ = h.shape
    x = g.rms_norm(h, lp["ln1"]["g"], spec.rms_eps)
    q, k, v = (g._q(a, quantize) for a in mla_project(lp, x, spec, quantize))
    o, _ = g.attend(q, k, v, g.causal(t)[None])
    o = jnp.moveaxis(o, 1, 2).reshape(b, t, -1)
    return h + g._mm(o, lp["wo"], quantize)


def layer_forward(lp, h, spec: Spec, quantize=None):
    lp = jax.tree.map(lambda a: a.astype(F32), lp)
    return g.ffn_block(lp, attention_block(lp, h, spec, quantize), spec,
                       quantize)


def head_loss(ln_f, head, h, labels, spec: Spec, quantize=None):
    mask = (labels >= 0).astype(F32)
    return g.head_loss(ln_f, head, h, jnp.maximum(labels, 0), mask, spec,
                       quantize)


# -- whole model at once (tests, probes) ------------------------------------------

def vision_rows(params, batch, spec: Spec, quantize=None):
    """The projector's rows for a batch (whole tower at once)."""
    with jax.default_matmul_precision(HIGHEST):
        vp = params["vision"]
        h = vit_embed(vp["patch"], vp["pos"], batch["patches"], spec,
                      quantize)
        for lp in vp["layers"]:
            h = vit_layer(lp, h, spec, quantize)
        return merge_project(vp["ln_f"], vp["proj"], h, spec, quantize)


def loss(params, batch, labels, spec: Spec, quantize=None):
    """The training loss (whole model at once: for tests at sizes where
    it fits)."""
    with jax.default_matmul_precision(HIGHEST):
        rows = vision_rows(params, batch, spec, quantize)
        h = embed_forward(params["embed"], batch["tokens"], rows, spec)
        for lp in params["layers"]:
            h = layer_forward(lp, h, spec, quantize)
        return head_loss(params["ln_f"], params["head"], h, labels, spec,
                         quantize)


@functools.partial(jax.jit, static_argnames=("spec", "quantize"))
def chosen_experts(params, batch, spec: Spec, quantize=None):
    """bool [T, E]: the first expert layer's chosen experts for a
    batch (tokens flattened)."""
    with jax.default_matmul_precision(HIGHEST):
        rows = vision_rows(params, batch, spec, quantize)
        h = embed_forward(params["embed"], batch["tokens"], rows, spec)
        for lp in params["layers"]:
            lp = jax.tree.map(lambda a: a.astype(F32), lp)
            if "wg" in lp:
                h = attention_block(lp, h, spec, quantize)
                x = g.rms_norm(h, lp["ln2"]["g"], spec.rms_eps)
                return g.route(x.reshape(-1, x.shape[-1]), lp["wg"],
                               lp["wg_bias"], spec)[0]
            h = layer_forward(lp, h, spec, quantize)
    raise ValueError("no expert layer")


# -- one SGD step, a layer at a time ----------------------------------------------

_J = functools.partial(jax.jit, static_argnames=("spec", "quantize"))


@_J
def _fwd_vit_embed(patch, table, patches, spec, quantize):
    with jax.default_matmul_precision(HIGHEST):
        return vit_embed(patch, table, patches, spec, quantize)


@_J
def _fwd_vit_layer(lp, h, spec, quantize):
    with jax.default_matmul_precision(HIGHEST):
        return vit_layer(lp, h, spec, quantize)


@_J
def _fwd_merge(ln_f, proj, h, spec, quantize):
    with jax.default_matmul_precision(HIGHEST):
        return merge_project(ln_f, proj, h, spec, quantize)


@functools.partial(jax.jit, static_argnames=("spec",))
def _fwd_embed(embed, tokens, rows, spec):
    return embed_forward(embed, tokens, rows, spec)


@_J
def _fwd_layer(lp, h, spec, quantize):
    with jax.default_matmul_precision(HIGHEST):
        return layer_forward(lp, h, spec, quantize)


@_J
def _head(ln_f, head, h, labels, spec, quantize):
    with jax.default_matmul_precision(HIGHEST):
        val, vjp = jax.vjp(
            lambda a, e, x: head_loss(a, e, x, labels, spec, quantize),
            ln_f, head, h)
        return (val,) + vjp(jnp.ones((), F32))


@functools.partial(jax.jit, static_argnames=("spec", "quantize"),
                   donate_argnums=(0,))
def _bwd_layer(lp, h, g_out, lr, spec, quantize):
    with jax.default_matmul_precision(HIGHEST):
        _, vjp = jax.vjp(lambda p, x: layer_forward(p, x, spec, quantize),
                         lp, h)
        g_lp, g_h = vjp(g_out)
        return g._sgd_tree(lp, g_lp, lr), g_h


@functools.partial(jax.jit, static_argnames=("spec",),
                   donate_argnums=(0, 1, 2))
def _bwd_ends(embed, head, ln_f, g_ln, g_head, g_h0, tokens, rows, lr, spec):
    """The embedding's, the head's and the final norm's updates, and
    the gradient of the tower's rows."""
    _, vjp = jax.vjp(lambda e, r: embed_forward(e, tokens, r, spec), embed,
                     rows)
    g_embed, g_rows = vjp(g_h0)
    return (g._sgd(embed, g_embed, lr), g._sgd(head, g_head, lr),
            g._sgd_tree(ln_f, g_ln, lr), g_rows)


@functools.partial(jax.jit, static_argnames=("spec", "quantize"),
                   donate_argnums=(0, 1))
def _bwd_merge(ln_f, proj, h, g_rows, lr, spec, quantize):
    with jax.default_matmul_precision(HIGHEST):
        _, vjp = jax.vjp(
            lambda a, p, x: merge_project(a, p, x, spec, quantize), ln_f,
            proj, h)
        g_ln, g_proj, g_h = vjp(g_rows)
        return g._sgd_tree(ln_f, g_ln, lr), g._sgd_tree(proj, g_proj, lr), g_h


@functools.partial(jax.jit, static_argnames=("spec", "quantize"),
                   donate_argnums=(0,))
def _bwd_vit_layer(lp, h, g_out, lr, spec, quantize):
    with jax.default_matmul_precision(HIGHEST):
        _, vjp = jax.vjp(lambda p, x: vit_layer(p, x, spec, quantize), lp, h)
        g_lp, g_h = vjp(g_out)
        return g._sgd_tree(lp, g_lp, lr), g_h


@functools.partial(jax.jit, static_argnames=("spec", "quantize"),
                   donate_argnums=(0, 1))
def _bwd_vit_embed(patch, table, patches, g_h, lr, spec, quantize):
    with jax.default_matmul_precision(HIGHEST):
        _, vjp = jax.vjp(
            lambda p, t: vit_embed(p, t, patches, spec, quantize), patch,
            table)
        g_patch, g_table = vjp(g_h)
        return g._sgd_tree(patch, g_patch, lr), g._sgd(table, g_table, lr)


def sgd_step(params, batch, labels, lr: float, spec: Spec, quantize=None):
    """(params, loss) after one step of plain SGD on the training
    loss. `params` is consumed (its buffers are donated layer by
    layer)."""
    lr = jnp.asarray(lr, F32)
    vp = params["vision"]
    hs = [_fwd_vit_embed(vp["patch"], vp["pos"], batch["patches"], spec,
                         quantize)]
    for lp in vp["layers"]:
        hs.append(_fwd_vit_layer(lp, hs[-1], spec, quantize))
    rows = _fwd_merge(vp["ln_f"], vp["proj"], hs[-1], spec, quantize)
    xs = [_fwd_embed(params["embed"], batch["tokens"], rows, spec)]
    for lp in params["layers"]:
        xs.append(_fwd_layer(lp, xs[-1], spec, quantize))
    val, g_ln, g_head, g_x = _head(params["ln_f"], params["head"], xs.pop(),
                                   labels, spec, quantize)
    layers = list(params["layers"])
    for i in reversed(range(len(layers))):
        layers[i], g_x = _bwd_layer(layers[i], xs.pop(), g_x, lr, spec,
                                    quantize)
    embed, head, ln_f, g_rows = _bwd_ends(
        params["embed"], params["head"], params["ln_f"], g_ln, g_head, g_x,
        batch["tokens"], rows, lr, spec)
    v_ln, proj, g_h = _bwd_merge(vp["ln_f"], vp["proj"], hs.pop(), g_rows,
                                 lr, spec, quantize)
    blocks = list(vp["layers"])
    for i in reversed(range(len(blocks))):
        blocks[i], g_h = _bwd_vit_layer(blocks[i], hs.pop(), g_h, lr, spec,
                                        quantize)
    patch, table = _bwd_vit_embed(vp["patch"], vp["pos"], batch["patches"],
                                  g_h, lr, spec, quantize)
    vision = {"patch": patch, "pos": table, "layers": blocks, "ln_f": v_ln,
              "proj": proj}
    return {"embed": embed, "head": head, "ln_f": ln_f, "layers": layers,
            "vision": vision}, val
