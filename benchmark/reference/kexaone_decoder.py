"""K-EXAONE's decoder (K-EXAONE-236B-A23B, `model_type` exaone_moe), its
training losses and one SGD step, written plainly.

Straightforward jax.numpy in float32 under
jax.default_matmul_precision("highest"); no kernel, no tile, no sort, no
grouped matmul (a choice of experts is a [T, E] matrix of weights),
nothing imported from the program. Every layer is `h <- h +
attention(RMSNorm(h))`, `h <- h + ffn(RMSNorm(h))` (eps 1e-5, a gain,
no bias); after the last layer the final RMSNorm and the untied head.
No product has a bias.

- *attention, both kinds*: `q = x W_q` (`n_heads` heads of `head_dim`),
  `k = x W_k`, `v = x W_v` (`n_kv_heads` heads). PER HEAD, after the
  split: `q_i <- RMSNorm(q_i) g_q`, `k_j <- RMSNorm(k_j) g_k`, two gains
  of `[head_dim]` shared by every head (eps 1e-5). Query head i attends
  with key head `i // (n_heads / n_kv_heads)` — INDEXED, one query head
  at a time, never repeated —; softmax at scale `head_dim ** -0.5`;
  `o W_o`. The mask is a dense boolean matrix over a block of query
  rows against ALL keys, so that it shares no tile arithmetic with the
  program:
  - `sliding_attention` (window W): query t sees key s iff
    `(s <= t) & (s > t - W)`: itself and the W - 1 before it. RoPE on q
    and k after the norm, rotate-half pairing (dimension i with i +
    head_dim / 2), `inv_freq_i = theta^(-i / (head_dim / 2))`.
  - `full_attention`: every `s <= t`, and NO rotation: the full layers
    carry no positions of their own.
- *feed-forward part*: layer 0 (`first_dense` layers) one SiLU-gated
  FFN `(silu(x W1) * (x W3)) W2` of the dense width. Every later layer:
  `s = sigmoid(x W_g)` over ALL experts; chosen = the `top_k` largest
  of `s + b` (`b` a selection bias without gradient); `g =
  routed_scale * s[chosen] / sum s[chosen]`; `h += sum over the chosen
  experts THIS chip holds of g_e FFN_e(x) + FFN_shared(x)`. Every held
  expert is computed on every token and weighted by a [T, E] matrix
  that is zero where the expert was not chosen: no token can be
  dropped.
- *multi-token prediction* (depth 1, DeepSeek-V3's form): `h' =
  [norm_h(h_L[i]) ; norm_e(Emb(t[i+1]))] W_eh`, one more EXPERT layer
  whose attention is of the kind `mtp_layer_type` names
  (`full_attention`: the whole triangle, no rotation — whatever the
  trunk's last layer is), the final norm and head SHARED with the main
  model, cross-entropy on `t[i+2]` (the last position has none).
- loss = mean next-token cross-entropy + `mtp_weight` x the module's
  (no router loss: the catalog's row carries no coefficient).

Departures from the published description, all shared with the program
(benchmark/configs/k-exaone-236b-a23b.json, `assumed`): the per-head
QK-norm, the rotation on the sliding layers only and the pre-norm
placement have no key in the config and are the EXAONE 4.0 family's
convention (arXiv:2507.11407) read as that file says; the experts other
chips hold (all but the `w1`'s leading dimension from `held_first`) add
nothing; `b` is held fixed; the MTP loss's weight is not in the config;
plain SGD. Bookkeeping, not departures: attention takes one query head
and a block of query rows at a time and the experts one at a time (each
made again in the backward pass), and the step is taken layer by layer
(forward keeping each layer's input, then one `jax.vjp` per layer
backwards, updating that layer at once; the MTP module's merge, layer
and head likewise), as olmoe_decoder.py does and for its reason: the
float32 copies fit one 16 GB chip beside the stored parameters at the
published widths and 8,192 positions.

Precision as the configuration states it: parameters STORED in
`param_dtype`, the gradient reaches the optimizer in that type, the SGD
update is computed in float32 and rounded back. Everything else is
float32. `quantize` puts the control in the reference's place: every
matmul operand the configuration states as bfloat16 (the projections,
the attention products, the FFNs, the merge, the head — not the router,
which it states as float32) is rounded to a lower-precision type first
(float8_e4m3fn is the step below bfloat16), with a straight-through
gradient.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = "highest"
NEG = -jnp.inf
SLIDING, FULL = "sliding_attention", "full_attention"


class Spec(NamedTuple):
    """What the equations need beside the weights' shapes."""
    layer_types: Tuple[str, ...]
    mtp_layer_type: str
    n_heads: int
    n_kv_heads: int
    top_k: int
    window: int
    theta: float
    held_first: int = 0
    routed_scale: float = 2.5
    rms_eps: float = 1e-5
    mtp_weight: float = 0.1
    #: bookkeeping: query rows of attention at a time
    q_rows: int = 512


def _q(x, quantize):
    """Round to `quantize` and back (straight-through), or nothing."""
    if quantize is None:
        return x
    lo = x.astype(quantize).astype(x.dtype)
    return x + jax.lax.stop_gradient(lo - x)


def _mm(x, w, quantize):
    return _q(x, quantize) @ _q(w, quantize)


def rms_norm(x, g, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _divisor(t: int, want: int) -> int:
    """The largest block no longer than `want` that divides t."""
    return next(b for b in range(min(want, t), 0, -1) if t % b == 0)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


# -- attention -----------------------------------------------------------------

def rotate(x, theta: float):
    """[B, T, H, Dh] rotated at positions 0 .. T - 1."""
    t, half = x.shape[1], x.shape[-1] // 2
    inv_freq = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(lp, x, kind: str, spec: Spec, quantize=None):
    """x [B, T, d] (normed) -> the mixer's output [B, T, d]."""
    b, t, _ = x.shape
    h, kv = spec.n_heads, spec.n_kv_heads
    dh = lp["wq"].shape[1] // h
    q = rms_norm(_mm(x, lp["wq"], quantize).reshape(b, t, h, dh),
                 lp["q_norm"]["g"], spec.rms_eps)
    k = rms_norm(_mm(x, lp["wk"], quantize).reshape(b, t, kv, dh),
                 lp["k_norm"]["g"], spec.rms_eps)
    v = _mm(x, lp["wv"], quantize).reshape(b, t, kv, dh)
    if kind == SLIDING:  # the full layers carry no positions
        q, k = rotate(q, spec.theta), rotate(k, spec.theta)
    q, k, v = (_q(u, quantize) for u in (q, k, v))
    rows = _divisor(t, spec.q_rows)
    keys = jnp.arange(t)[None, :]

    def head(i):
        """Query head i over its key head, a block of rows at a time."""
        mine = i // (h // kv)
        kh = jax.lax.dynamic_index_in_dim(k, mine, 2, keepdims=False)
        vh = jax.lax.dynamic_index_in_dim(v, mine, 2, keepdims=False)
        qh = jax.lax.dynamic_index_in_dim(q, i, 2, keepdims=False)

        @jax.checkpoint
        def block(first, qb):
            s = jnp.einsum("bqd,bkd->bqk", qb, kh) * dh ** -0.5
            at = (first + jnp.arange(rows))[:, None]
            seen = keys <= at
            if kind == SLIDING:
                seen = seen & (keys > at - spec.window)
            p = jax.nn.softmax(jnp.where(seen[None], s, NEG), axis=-1)
            return jnp.einsum("bqk,bkd->bqd", p, vh)

        o = jax.lax.map(lambda a: block(*a), (
            jnp.arange(0, t, rows),
            jnp.moveaxis(qh.reshape(b, t // rows, rows, dh), 1, 0)))
        return jnp.moveaxis(o, 0, 1).reshape(b, t, dh)

    o = jnp.moveaxis(jax.lax.map(head, jnp.arange(h)), 0, 2)  # [B, T, H, Dh]
    return _mm(o.reshape(b, t, h * dh), lp["wo"], quantize)


# -- the feed-forward part -------------------------------------------------------

def ffn(x, w1, w3, w2, quantize=None):
    return _mm(jax.nn.silu(_mm(x, w1, quantize)) * _mm(x, w3, quantize), w2,
               quantize)


def route(x, wg, bias, spec: Spec):
    """x [T, d] -> (chosen [T, E] bool, weights [T, E]: g of the
    chosen, 0 elsewhere)."""
    s = jax.nn.sigmoid(x @ wg)
    idx = jax.lax.top_k(s + jax.lax.stop_gradient(bias), spec.top_k)[1]
    chosen = jax.nn.one_hot(idx, s.shape[-1], dtype=F32).sum(1) > 0
    kept = jnp.where(chosen, s, 0.0)
    return chosen, spec.routed_scale * kept / kept.sum(-1, keepdims=True)


def experts_sum(x, weights, w1, w3, w2, quantize=None):
    """sum_e weights[:, e] * FFN_e(x), every held expert on every
    token, one expert at a time."""
    def one(m, a, g, c):
        return m[:, None] * ffn(x, a, g, c, quantize)

    def body(y, ws):
        return y + jax.checkpoint(one)(*ws), None

    return jax.lax.scan(body, jnp.zeros_like(x),
                        (weights.T, w1, w3, w2))[0]


def ffn_part(lp, x, spec: Spec, quantize=None):
    """x [B, T, d] (normed) -> the feed-forward part's output."""
    b, t, d = x.shape
    x = x.reshape(b * t, d)
    if "wg" not in lp:  # the leading dense layer
        return ffn(x, lp["w1"], lp["w3"], lp["w2"], quantize).reshape(b, t, d)
    _, weights = route(x, lp["wg"], lp["wg_bias"], spec)
    held = lp["w1"].shape[0]
    weights = weights[:, spec.held_first:spec.held_first + held]
    y = experts_sum(x, weights, lp["w1"], lp["w3"], lp["w2"], quantize) \
        + ffn(x, lp["ws1"], lp["ws3"], lp["ws2"], quantize)
    return y.reshape(b, t, d)


# -- the layer, the ends, the losses ---------------------------------------------

def layer_forward(lp, h, kind: str, spec: Spec, quantize=None):
    """h after the block; lp in float32."""
    h = h + attention(lp, rms_norm(h, lp["ln1"]["g"], spec.rms_eps), kind,
                      spec, quantize)
    return h + ffn_part(lp, rms_norm(h, lp["ln2"]["g"], spec.rms_eps), spec,
                        quantize)


def embed_forward(embed, tokens):
    return embed[tokens].astype(F32)


def head_loss(ln_f, head, h, labels, mask, spec: Spec, quantize=None):
    """Mean cross-entropy over the masked positions through the untied
    head."""
    x = rms_norm(h, ln_f["g"].astype(F32), spec.rms_eps)
    logits = _mm(x, head.astype(F32).T, quantize)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return ((logz - gold) * mask).sum() / mask.sum()


def mtp_labels(labels):
    """(the labels of the second prediction: token i + 2 at position
    i, its mask: every position but the last)."""
    t = labels.shape[1]
    mask = jnp.broadcast_to((jnp.arange(t) < t - 1).astype(F32)[None],
                            labels.shape)
    return jnp.roll(labels, -1, axis=1), mask


def mtp_merge(mp, embed, h, labels, spec: Spec, quantize=None):
    """The module's input: position i holds h_L[i] and the embedding
    of its label, token i + 1. mp in float32."""
    both = jnp.concatenate(
        [rms_norm(h, mp["hnorm"]["g"], spec.rms_eps),
         rms_norm(embed_forward(embed, labels), mp["enorm"]["g"],
                  spec.rms_eps)], axis=-1)
    return _mm(both, mp["eh_proj"], quantize)


def mtp_forward(mp, embed, ln_f, head, h, labels, spec: Spec, quantize=None):
    """The second prediction's mean cross-entropy."""
    mp = _f32(mp)
    h2 = layer_forward(mp, mtp_merge(mp, embed, h, labels, spec, quantize),
                       spec.mtp_layer_type, spec, quantize)
    labels2, mask2 = mtp_labels(labels)
    return head_loss(ln_f, head, h2, labels2, mask2, spec, quantize)


def losses(params, tokens, labels, spec: Spec, quantize=None):
    """(cross-entropy, the MTP module's cross-entropy or 0.0), whole
    model at once: for tests at sizes where it fits."""
    with jax.default_matmul_precision(HIGHEST):
        h = embed_forward(params["embed"], tokens)
        for kind, lp in zip(spec.layer_types, params["layers"]):
            h = layer_forward(_f32(lp), h, kind, spec, quantize)
        ce = head_loss(params["ln_f"], params["head"], h, labels,
                       jnp.ones(labels.shape, F32), spec, quantize)
        mtp = jnp.zeros((), F32)
        for mp in params.get("mtp", ()):
            mtp = mtp_forward(mp, params["embed"], params["ln_f"],
                              params["head"], h, labels, spec, quantize)
        return ce, mtp


def loss(params, tokens, labels, spec: Spec, quantize=None):
    """The training loss (whole model at once)."""
    ce, mtp = losses(params, tokens, labels, spec, quantize)
    return ce + spec.mtp_weight * mtp


@functools.partial(jax.jit, static_argnames=("layer", "spec", "quantize"))
def attention_out(params, tokens, layer: int, spec: Spec, quantize=None):
    """Layer `layer`'s attention mixer (its norm, its weights, its
    kind) on the EMBEDDED batch [B, T, d] — layer `len(layers)` is the
    MTP module's —: every layer read on the stream entering layer 0, so
    that a reading carries nothing of the layers in front."""
    with jax.default_matmul_precision(HIGHEST):
        trunk = len(params["layers"])
        lp, kind = (params["mtp"][0], spec.mtp_layer_type) \
            if layer == trunk else (params["layers"][layer],
                                    spec.layer_types[layer])
        lp = _f32(lp)
        h = embed_forward(params["embed"], tokens)
        return attention(lp, rms_norm(h, lp["ln1"]["g"], spec.rms_eps),
                         kind, spec, quantize)


@functools.partial(jax.jit, static_argnames=("spec", "quantize"))
def chosen_experts(params, tokens, spec: Spec, quantize=None):
    """bool [T, E]: the first expert layer's chosen experts for a
    batch (tokens flattened)."""
    with jax.default_matmul_precision(HIGHEST):
        h = embed_forward(params["embed"], tokens)
        for kind, lp in zip(spec.layer_types, params["layers"]):
            lp = _f32(lp)
            if "wg" in lp:
                h = h + attention(lp, rms_norm(h, lp["ln1"]["g"],
                                               spec.rms_eps), kind, spec,
                                  quantize)
                x = rms_norm(h, lp["ln2"]["g"], spec.rms_eps)
                return route(x.reshape(-1, x.shape[-1]), lp["wg"],
                             lp["wg_bias"], spec)[0]
            h = layer_forward(lp, h, kind, spec, quantize)
    raise ValueError("no expert layer")


# -- one SGD step, a layer at a time ----------------------------------------------

def _sgd(p, g, lr):
    """The update as the configuration states it: the gradient in the
    parameters' type, the arithmetic in float32, the result stored."""
    return (p.astype(F32) - lr * g.astype(p.dtype).astype(F32)
            ).astype(p.dtype)


def _sgd_tree(tree, grads, lr):
    return jax.tree.map(lambda p, g: _sgd(p, g, lr), tree, grads)


_fwd_embed = jax.jit(embed_forward)


@functools.partial(jax.jit, static_argnames=("kind", "spec", "quantize"))
def _fwd_layer(lp, h, kind, spec, quantize):
    with jax.default_matmul_precision(HIGHEST):
        return layer_forward(_f32(lp), h, kind, spec, quantize)


@functools.partial(jax.jit, static_argnames=("spec", "quantize"))
def _head(ln_f, head, h, labels, mask, weight, spec, quantize):
    """A head's loss over the masked positions and its gradients
    (float32) at `weight`."""
    with jax.default_matmul_precision(HIGHEST):
        val, vjp = jax.vjp(
            lambda a, e, x: head_loss(a, e, x, labels, mask, spec, quantize),
            ln_f, head, h)
        return (val,) + vjp(jnp.asarray(weight, F32))


#: the leaves of an MTP module that are not its layer's
MERGE = ("enorm", "hnorm", "eh_proj")


@functools.partial(jax.jit, static_argnames=("spec", "quantize"))
def _fwd_merge(merge, embed, h, labels, spec, quantize):
    with jax.default_matmul_precision(HIGHEST):
        return mtp_merge(_f32(merge), embed, h, labels, spec, quantize)


@functools.partial(jax.jit, static_argnames=("spec", "quantize"),
                   donate_argnums=(0,))
def _bwd_merge(merge, embed, h, g_out, labels, lr, spec, quantize):
    """The merge's update, and what it sends to the embedding and to
    h_L."""
    with jax.default_matmul_precision(HIGHEST):
        _, vjp = jax.vjp(
            lambda m, e, x: mtp_merge(m, e, x, labels, spec, quantize),
            _f32(merge), embed, h)
        g_merge, g_embed, g_h = vjp(g_out)
        return _sgd_tree(merge, g_merge, lr), g_embed.astype(F32), g_h


@functools.partial(jax.jit, static_argnames=("kind", "spec", "quantize"),
                   donate_argnums=(0,))
def _bwd_layer(lp, h, g_out, lr, kind, spec, quantize):
    with jax.default_matmul_precision(HIGHEST):
        _, vjp = jax.vjp(
            lambda p, x: layer_forward(p, x, kind, spec, quantize),
            _f32(lp), h)
        g_lp, g_h = vjp(g_out)
        return _sgd_tree(lp, g_lp, lr), g_h


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _bwd_ends(embed, head, ln_f, g_embed, g_ln, g_head, g_h0, tokens, lr):
    """g_embed / g_ln / g_head: what the heads sent (float32 sums);
    g_h0 reaches the embedding through the lookup."""
    _, vjp = jax.vjp(lambda e: embed_forward(e, tokens), embed)
    g_embed = g_embed + vjp(g_h0)[0].astype(F32)
    return (_sgd(embed, g_embed, lr), _sgd(head, g_head, lr),
            _sgd_tree(ln_f, g_ln, lr))


def sgd_step(params, tokens, labels, lr: float, spec: Spec, quantize=None):
    """(params, loss) after one step of plain SGD on the training
    loss. `params` is consumed (its buffers are donated layer by
    layer)."""
    lr = jnp.asarray(lr, F32)
    hs = [_fwd_embed(params["embed"], tokens)]
    for kind, lp in zip(spec.layer_types, params["layers"]):
        hs.append(_fwd_layer(lp, hs[-1], kind, spec, quantize))
    h_last = hs.pop()
    val, g_ln, g_head, g_h = _head(
        params["ln_f"], params["head"], h_last, labels,
        jnp.ones(labels.shape, F32), 1.0, spec, quantize)
    g_embed = jnp.zeros(params["embed"].shape, F32)
    new = {}
    if params.get("mtp"):
        # the module as the trunk is taken: its merge, its layer and
        # its head each a program of their own, so that the float32
        # copies of one of them are alive at a time
        (mp,) = params["mtp"]
        merge = {k: mp[k] for k in MERGE}
        layer = {k: v for k, v in mp.items() if k not in MERGE}
        kind = spec.mtp_layer_type
        x2 = _fwd_merge(merge, params["embed"], h_last, labels, spec,
                        quantize)
        ce2, ln2, head2, g_x2 = _head(
            params["ln_f"], params["head"],
            _fwd_layer(layer, x2, kind, spec, quantize), *mtp_labels(labels),
            spec.mtp_weight, spec, quantize)
        layer, g_x2 = _bwd_layer(layer, x2, g_x2, lr, kind, spec, quantize)
        merge, e2, h2 = _bwd_merge(merge, params["embed"], h_last, g_x2,
                                   labels, lr, spec, quantize)
        new["mtp"] = [dict(layer, **merge)]
        val = val + spec.mtp_weight * ce2
        g_embed, g_head, g_h = g_embed + e2, g_head + head2, g_h + h2
        g_ln = jax.tree.map(jnp.add, g_ln, ln2)
    del h_last
    layers = list(params["layers"])
    for i in reversed(range(len(layers))):
        layers[i], g_h = _bwd_layer(layers[i], hs.pop(), g_h, lr,
                                    spec.layer_types[i], spec, quantize)
    embed, head, ln_f = _bwd_ends(
        params["embed"], params["head"], params["ln_f"], g_embed, g_ln,
        g_head, g_h, tokens, lr)
    return dict(new, embed=embed, head=head, ln_f=ln_f, layers=layers), val
