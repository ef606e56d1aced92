"""Mellum 2's decoder (Mellum2-12B-A2.5B-Instruct, `model_type` mellum),
its training loss and one SGD step, written plainly.

Straightforward jax.numpy in float32 under
jax.default_matmul_precision("highest"); no kernel, no tile, no sort, no
grouped matmul (a choice of experts is a [T, E] matrix of weights),
nothing imported from the program. Every layer is `h <- h +
attention(RMSNorm(h))`, `h <- h + experts(RMSNorm(h))` (eps 1e-6, a
gain, no bias); after the last layer the final RMSNorm and the untied
head. No product has a bias.

- *attention, both kinds*: `q = x W_q` (`n_heads` heads of `head_dim`),
  `k = x W_k`, `v = x W_v` (`n_kv_heads` heads); RoPE on q and k in the
  rotate-half pairing (dimension i with i + head_dim / 2) over the whole
  head; query head i attends with key head `i // (n_heads /
  n_kv_heads)` — INDEXED, one query head at a time, never repeated —;
  softmax at scale `head_dim ** -0.5`; `o W_o`. The mask is a dense
  boolean matrix over a block of query rows against ALL keys, so that
  it shares no tile arithmetic with the program:
  - `sliding_attention` (window W): query t sees key s iff
    `(s <= t) & (s > t - W)`: itself and the W - 1 before it. RoPE
    `default`: `inv_freq_i = theta^(-i / (head_dim / 2))`.
  - `full_attention`: every `s <= t`. RoPE `yarn` (Peng et al.,
    arXiv:2309.00071, as the public `_compute_yarn_parameters` writes
    it): with `e_i` the default frequencies, `corr(n) = head_dim *
    ln(original / (2 pi n)) / (2 ln theta)`, `low = floor(corr(
    beta_fast))`, `high = ceil(corr(beta_slow))` held inside [0,
    head_dim - 1], `r_i = clip((i - low) / (high - low), 0, 1)`:
    `inv_freq_i = e_i (1 - r_i) + (e_i / factor) r_i`; cos and sin are
    both multiplied by `attention_factor`.
- *experts*: router logits `x W_g` and their softmax over ALL experts;
  the `top_k` largest; their probabilities divided by their sum
  (`norm_topk_prob`); expert e is `(silu(x W1_e) * (x W3_e)) W2_e`;
  `h += sum_k p_k expert_k(x)`. Every expert is computed on every token
  and weighted by a [T, E] matrix that is zero where the expert was not
  chosen: no token can be dropped.
- loss = mean next-token cross-entropy (no router loss: the catalog's
  row carries no coefficient).

Departures from the public modeling file, all shared with the program
(benchmark/configs/mellum2-12b-a2.5b.json, `assumed`): no QK-norm (the
config has no key for one), no multi-token-prediction head (no key
either), no attention sink, no dropout; plain SGD. Bookkeeping, not
departures: attention takes one query head and a block of query rows
at a time and the experts one at a time (each made again in the
backward pass), and the step is taken layer by layer (forward keeping
each layer's input, then one `jax.vjp` per layer backwards, updating
that layer at once), as olmoe_decoder.py does and for its reason: the
float32 copies fit one 16 GB chip beside nothing at the published
widths and 16,384 positions.

Precision as the configuration states it: parameters STORED in
`param_dtype`, the gradient reaches the optimizer in that type, the SGD
update is computed in float32 and rounded back. Everything else is
float32. `quantize` puts the control in the reference's place: every
matmul operand the configuration states as bfloat16 (the projections,
the attention products, the experts, the head — not the router, which
it states as float32) is rounded to a lower-precision type first
(float8_e4m3fn is the step below bfloat16), with a straight-through
gradient.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = "highest"
NEG = -jnp.inf
SLIDING, FULL = "sliding_attention", "full_attention"


class Yarn(NamedTuple):
    """The `yarn` entry of the source's `rope_parameters`."""
    theta: float
    factor: float
    original: int
    beta_fast: float
    beta_slow: float
    attention_factor: float


class Spec(NamedTuple):
    """What the equations need beside the weights' shapes."""
    layer_types: Tuple[str, ...]
    n_heads: int
    n_kv_heads: int
    top_k: int
    window: int
    sliding_theta: float
    yarn: Yarn
    rms_eps: float = 1e-6
    norm_topk_prob: bool = True
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


# -- positions -----------------------------------------------------------------

def yarn_pairs(head_dim: int, yarn: Yarn) -> Tuple[int, int]:
    """(low, high): the first pair of the ramp and its last."""
    def corr(turns):
        return head_dim * math.log(yarn.original / (turns * 2 * math.pi)) \
            / (2 * math.log(yarn.theta))

    return (max(math.floor(corr(yarn.beta_fast)), 0),
            min(math.ceil(corr(yarn.beta_slow)), head_dim - 1))


def inv_freq(head_dim: int, kind: str, spec: Spec):
    """float32 [head_dim / 2]: a layer kind's frequencies."""
    half = head_dim // 2
    i = jnp.arange(half, dtype=F32)
    if kind == SLIDING:
        return 1.0 / spec.sliding_theta ** (i / half)
    yarn = spec.yarn
    e = 1.0 / yarn.theta ** (i / half)
    low, high = yarn_pairs(head_dim, yarn)
    if low == high:
        high += 0.001  # the public file's guard against a ramp of no width
    r = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return e * (1.0 - r) + e / yarn.factor * r


def rotate(x, kind: str, spec: Spec):
    """[B, T, H, Dh] rotated at positions 0 .. T - 1, cos and sin times
    the kind's attention factor."""
    t, half = x.shape[1], x.shape[-1] // 2
    ang = jnp.arange(t, dtype=F32)[:, None] * inv_freq(x.shape[-1], kind,
                                                       spec)[None, :]
    scale = spec.yarn.attention_factor if kind == FULL else 1.0
    cos = (jnp.cos(ang) * scale)[None, :, None]
    sin = (jnp.sin(ang) * scale)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


# -- attention -----------------------------------------------------------------

def attention(lp, x, kind: str, spec: Spec, quantize=None):
    """x [B, T, d] (normed) -> the mixer's output [B, T, d]."""
    b, t, _ = x.shape
    h, kv = spec.n_heads, spec.n_kv_heads
    dh = lp["wq"].shape[1] // h
    q = rotate(_mm(x, lp["wq"], quantize).reshape(b, t, h, dh), kind, spec)
    k = rotate(_mm(x, lp["wk"], quantize).reshape(b, t, kv, dh), kind, spec)
    v = _mm(x, lp["wv"], quantize).reshape(b, t, kv, dh)
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


# -- experts -------------------------------------------------------------------

def route(x, wg, spec: Spec):
    """x [T, d] -> (weights [T, E], zero where an expert is not among a
    token's `top_k`; chosen [T, E] bool)."""
    p = jax.nn.softmax(x @ wg, axis=-1)
    top, experts = jax.lax.top_k(p, spec.top_k)
    if spec.norm_topk_prob:
        top = top / top.sum(-1, keepdims=True)
    onehot = jax.nn.one_hot(experts, p.shape[-1], dtype=F32)  # [T, k, E]
    return (onehot * top[..., None]).sum(1), onehot.sum(1) > 0


def experts(lp, x, spec: Spec, quantize=None):
    """x [B, T, d] (normed) -> sum over a token's experts of weight x
    expert(x), every expert on every token, one expert at a time."""
    b, t, d = x.shape
    x = x.reshape(b * t, d)
    weights, _ = route(x, lp["wg"], spec)

    def one(m, w1, w3, w2):
        u = jax.nn.silu(_mm(x, w1, quantize)) * _mm(x, w3, quantize)
        return m[:, None] * _mm(u, w2, quantize)

    def body(y, ws):
        return y + jax.checkpoint(one)(*ws), None

    y = jax.lax.scan(body, jnp.zeros_like(x),
                     (weights.T, lp["w1"], lp["w3"], lp["w2"]))[0]
    return y.reshape(b, t, d)


# -- the layer, the ends, the loss ---------------------------------------------

def layer_forward(lp, h, kind: str, spec: Spec, quantize=None):
    """h after the block; lp in float32."""
    h = h + attention(lp, rms_norm(h, lp["ln1"]["g"], spec.rms_eps), kind,
                      spec, quantize)
    return h + experts(lp, rms_norm(h, lp["ln2"]["g"], spec.rms_eps), spec,
                       quantize)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def embed_forward(embed, tokens):
    return embed[tokens].astype(F32)


def head_loss(ln_f, head, h, labels, spec: Spec, quantize=None):
    """Mean next-token cross-entropy through the untied head."""
    x = rms_norm(h, ln_f["g"].astype(F32), spec.rms_eps)
    logits = _mm(x, head.astype(F32).T, quantize)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return (logz - gold).mean()


def loss(params, tokens, labels, spec: Spec, quantize=None):
    """The training loss, whole model at once: for tests at sizes
    where it fits."""
    with jax.default_matmul_precision(HIGHEST):
        h = embed_forward(params["embed"], tokens)
        for kind, lp in zip(spec.layer_types, params["layers"]):
            h = layer_forward(_f32(lp), h, kind, spec, quantize)
        return head_loss(params["ln_f"], params["head"], h, labels, spec,
                         quantize)


@functools.partial(jax.jit, static_argnames=("layer", "spec", "quantize"))
def attention_out(params, tokens, layer: int, spec: Spec, quantize=None):
    """Layer `layer`'s attention mixer (its norm, its weights, its
    kind) on the EMBEDDED batch [B, T, d]: every layer read on the
    stream entering layer 0, so that a reading carries nothing of the
    layers in front."""
    with jax.default_matmul_precision(HIGHEST):
        lp = _f32(params["layers"][layer])
        h = embed_forward(params["embed"], tokens)
        return attention(lp, rms_norm(h, lp["ln1"]["g"], spec.rms_eps),
                         spec.layer_types[layer], spec, quantize)


@functools.partial(jax.jit, static_argnames=("spec", "quantize"))
def chosen_experts(params, tokens, spec: Spec, quantize=None):
    """bool [T, E]: layer 0's chosen experts for a batch (tokens
    flattened)."""
    with jax.default_matmul_precision(HIGHEST):
        lp = _f32(params["layers"][0])
        h = embed_forward(params["embed"], tokens)
        h = h + attention(lp, rms_norm(h, lp["ln1"]["g"], spec.rms_eps),
                          spec.layer_types[0], spec, quantize)
        x = rms_norm(h, lp["ln2"]["g"], spec.rms_eps)
        return route(x.reshape(-1, x.shape[-1]), lp["wg"], spec)[1]


# -- one SGD step, a layer at a time ------------------------------------------

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
def _head(ln_f, head, h, labels, spec, quantize):
    """The head's loss and its gradients (float32)."""
    with jax.default_matmul_precision(HIGHEST):
        val, vjp = jax.vjp(
            lambda a, e, x: head_loss(a, e, x, labels, spec, quantize),
            ln_f, head, h)
        return (val,) + vjp(jnp.ones((), F32))


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
def _bwd_ends(embed, head, ln_f, g_ln, g_head, g_h0, tokens, lr):
    _, vjp = jax.vjp(lambda e: embed_forward(e, tokens), embed)
    return (_sgd(embed, vjp(g_h0)[0], lr), _sgd(head, g_head, lr),
            _sgd_tree(ln_f, g_ln, lr))


def sgd_step(params, tokens, labels, lr: float, spec: Spec, quantize=None):
    """(params, loss) after one step of plain SGD on the mean loss.
    `params` is consumed (its buffers are donated layer by layer)."""
    lr = jnp.asarray(lr, F32)
    hs = [_fwd_embed(params["embed"], tokens)]
    for kind, lp in zip(spec.layer_types, params["layers"]):
        hs.append(_fwd_layer(lp, hs[-1], kind, spec, quantize))
    val, g_ln, g_head, g_h = _head(params["ln_f"], params["head"], hs.pop(),
                                   labels, spec, quantize)
    layers = list(params["layers"])
    for i in reversed(range(len(layers))):
        layers[i], g_h = _bwd_layer(layers[i], hs.pop(), g_h, lr,
                                    spec.layer_types[i], spec, quantize)
    embed, head, ln_f = _bwd_ends(params["embed"], params["head"],
                                  params["ln_f"], g_ln, g_head, g_h, tokens,
                                  lr)
    return dict(embed=embed, head=head, ln_f=ln_f, layers=layers), val
