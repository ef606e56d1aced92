"""OLMoE's decoder, its training loss and one SGD step, written plainly.

Straightforward jax.numpy in float32 under
jax.default_matmul_precision("highest"); no kernels, no sort, no grouped
matmul, nothing imported from the program. It follows OLMoE-1B-7B
(Muennighoff et al., arXiv:2409.02060; `model_type` olmoe) layer by
layer, `h` the residual stream, every norm an RMSNorm with a learned
gain and no bias:

- `x = norm1(h)`; `q = x Wq`, `k = x Wk`, `v = x Wv`; QK-norm over the
  WHOLE projection (gains of width hidden_size, before the split into
  heads); split into heads; RoPE on q and k (rotate-half: dimension i
  pairs with i + head_dim / 2); causal softmax attention with scale
  1 / sqrt(head_dim); `h = h + o Wo`.
- `x = norm2(h)`; router logits `r = x Wg`; `p = softmax(r)` over all
  experts; the `top_k` largest p and their experts; the weights are
  those probabilities as they are (not renormalised unless
  `norm_topk_prob`); expert e is `(silu(x W1_e) * (x W3_e)) W2_e`;
  `h = h + sum_k p_k expert_k(x)`. Every expert is computed on every
  token and weighted by a [T, E] mask that is zero where the expert was
  not chosen: no token can be dropped.
- `logits = norm_f(h) W_head` (its own matrix); mean next-token
  cross-entropy; plus, per layer and averaged over layers,
  `balance_weight * E * sum_e f_e P_e` (f_e the share of the
  token-expert assignments that went to e, P_e the mean of p_e) and
  `z_weight * mean(logsumexp(r) ** 2)`.

The step is taken layer by layer (forward keeping each layer's input,
then one vjp per layer backwards, updating that layer at once), as
opt_decoder.py does and for its reason; attention runs one head at a
time and the experts one at a time (each recomputed in the backward
pass), so that the float32 copies fit one 16 GB chip at the published
widths and 4,096 positions.

Precision as the configuration states it: parameters STORED in
`param_dtype`, the gradient reaches the optimizer in that type, the SGD
update is computed in float32 and rounded back. Everything else is
float32.

`quantize` puts the control in the reference's place: every matmul
operand the configuration states as bfloat16 (the projections, the
attention products, the experts, the head — not the router, which the
configuration states as float32) is rounded to a lower-precision type
first (float8_e4m3fn is the step below bfloat16), with a
straight-through gradient.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = "highest"


class Spec(NamedTuple):
    """What the equations need beside the weights' shapes."""
    n_heads: int
    top_k: int
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    norm_topk_prob: bool = False
    balance_weight: float = 0.01
    z_weight: float = 0.001


def _q(x, quantize):
    """Round to `quantize` and back (straight-through), or nothing."""
    if quantize is None:
        return x
    lo = x.astype(quantize).astype(x.dtype)
    return x + jax.lax.stop_gradient(lo - x)


def rms_norm(x, g, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * g


def rope(x, theta, offset=0):
    """[B, T, H, Dh] rotated at positions offset .. offset + T - 1."""
    t, half = x.shape[1], x.shape[-1] // 2
    freq = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = (offset + jnp.arange(t, dtype=F32))[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(q, k, v):
    """[B, T, H, Dh] causal softmax attention, one head at a time."""
    t, scale = q.shape[1], 1.0 / jnp.sqrt(F32(q.shape[-1]))
    mask = jnp.tril(jnp.ones((t, t), bool))

    def head(qkv):
        qh, kh, vh = qkv  # [B, T, Dh]
        s = jnp.einsum("bqd,bkd->bqk", qh, kh) * scale
        s = jnp.where(mask[None], s, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, axis=-1), vh)

    heads = jax.lax.map(head, tuple(jnp.moveaxis(a, 2, 0)
                                    for a in (q, k, v)))
    return jnp.moveaxis(heads, 0, 2)


def route(x, wg, spec: Spec):
    """x [T, D] -> (experts [T, k], weights [T, k], probabilities
    [T, E], logsumexp [T])."""
    r = x @ wg
    lse = jax.nn.logsumexp(r, axis=-1)
    p = jnp.exp(r - lse[:, None])
    weights, experts = jax.lax.top_k(p, spec.top_k)
    if spec.norm_topk_prob:
        weights = weights / weights.sum(-1, keepdims=True)
    return experts, weights, p, lse


def experts_sum(x, mask, w1, w3, w2, quantize=None):
    """sum_e mask[:, e] * expert_e(x), every expert on every token,
    one expert at a time. `w3 is None`: an ungated ReLU expert (the
    Switch layer)."""
    gated = w3 is not None

    def one(m, a, b, g=None):
        u = _q(x, quantize) @ _q(a, quantize)
        u = jax.nn.silu(u) * (_q(x, quantize) @ _q(g, quantize)) \
            if gated else jnp.maximum(u, 0.0)
        return m[:, None] * (_q(u, quantize) @ _q(b, quantize))

    def body(y, ws):
        return y + jax.checkpoint(one)(*ws), None

    ws = (mask.T, w1, w2) + ((w3,) if gated else ())
    return jax.lax.scan(body, jnp.zeros_like(x), ws)[0]


def moe(x, lp, spec: Spec, quantize=None):
    """x [T, D] -> (the layer's FFN output, its weighted router
    losses)."""
    experts, weights, p, lse = route(x, lp["wg"], spec)
    n_experts = p.shape[-1]
    onehot = jax.nn.one_hot(experts, n_experts, dtype=F32)  # [T, k, E]
    mask = (onehot * weights[..., None]).sum(1)             # [T, E]
    f = jax.lax.stop_gradient(onehot.sum((0, 1)) / onehot.sum())
    balance = n_experts * jnp.sum(f * p.mean(0))
    z = jnp.mean(lse ** 2)
    y = experts_sum(x, mask, lp["w1"], lp.get("w3"), lp["w2"], quantize)
    return y, spec.balance_weight * balance + spec.z_weight * z


def attention_block(lp, h, spec: Spec, quantize=None, offset=0):
    """h after the attention half of a block (lp in float32)."""
    b, t, d = h.shape
    mm = lambda x, w: _q(x, quantize) @ _q(w, quantize)  # noqa: E731
    x = rms_norm(h, lp["ln1"]["g"], spec.rms_eps)
    q, k, v = (mm(x, lp[w]) for w in ("wq", "wk", "wv"))
    q = rms_norm(q, lp["q_norm"]["g"], spec.rms_eps)
    k = rms_norm(k, lp["k_norm"]["g"], spec.rms_eps)
    split = lambda a: a.reshape(b, t, spec.n_heads,  # noqa: E731
                                d // spec.n_heads)
    q = rope(split(q), spec.rope_theta, offset)
    k = rope(split(k), spec.rope_theta, offset)
    o = attention(_q(q, quantize), _q(k, quantize), _q(split(v), quantize))
    return h + mm(o.reshape(b, t, d), lp["wo"])


def layer_forward(lp, h, spec: Spec, quantize=None, offset=0):
    """(h after the block, the block's weighted router losses)."""
    lp = jax.tree.map(lambda a: a.astype(F32), lp)
    b, t, d = h.shape
    h = attention_block(lp, h, spec, quantize, offset)
    x = rms_norm(h, lp["ln2"]["g"], spec.rms_eps)
    y, aux = moe(x.reshape(b * t, d), lp, spec, quantize)
    return h + y.reshape(b, t, d), aux


def embed_forward(embed, tokens):
    return embed[tokens].astype(F32)


def head_logits(ln_f, head, h, spec: Spec, quantize=None):
    x = rms_norm(h, ln_f["g"].astype(F32), spec.rms_eps)
    return _q(x, quantize) @ _q(head.astype(F32), quantize).T


def head_loss(ln_f, head, h, labels, spec: Spec, quantize=None):
    """Mean next-token cross-entropy through the untied head."""
    logits = head_logits(ln_f, head, h, spec, quantize)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return (logz - gold).mean()


def _trunk(params, tokens, spec: Spec):
    h = embed_forward(params["embed"], tokens)
    aux = 0.0
    for lp in params["layers"]:
        h, a = layer_forward(lp, h, spec)
        aux = aux + a / len(params["layers"])
    return h, aux


def logits(params, tokens, spec: Spec):
    """The whole forward pass, for tests at sizes where it fits."""
    with jax.default_matmul_precision(HIGHEST):
        h, _ = _trunk(params, tokens, spec)
        return head_logits(params["ln_f"], params["head"], h, spec)


def loss(params, tokens, labels, spec: Spec):
    """Cross-entropy plus the weighted router losses (whole model)."""
    with jax.default_matmul_precision(HIGHEST):
        h, aux = _trunk(params, tokens, spec)
        return head_loss(params["ln_f"], params["head"], h, labels,
                         spec) + aux


def chosen_experts(params, tokens, spec: Spec, quantize=None):
    """int32 [T, k]: layer 0's chosen experts for a batch, sorted per
    token (for the share of assignments a program agrees on)."""
    with jax.default_matmul_precision(HIGHEST):
        lp = jax.tree.map(lambda a: a.astype(F32), params["layers"][0])
        h = attention_block(lp, embed_forward(params["embed"], tokens),
                            spec, quantize)
        x = rms_norm(h, lp["ln2"]["g"], spec.rms_eps)
        return jnp.sort(route(x.reshape(-1, x.shape[-1]), lp["wg"],
                              spec)[0], -1)


def _sgd(p, g, lr):
    """The update as the configuration states it: the gradient in the
    parameters' type, the arithmetic in float32, the result stored."""
    return (p.astype(F32) - lr * g.astype(p.dtype).astype(F32)
            ).astype(p.dtype)


_fwd_embed = jax.jit(embed_forward)


@functools.partial(jax.jit, static_argnames=("spec", "quantize"))
def _fwd_layer(lp, h, spec, quantize):
    with jax.default_matmul_precision(HIGHEST):
        return layer_forward(lp, h, spec, quantize)


@functools.partial(jax.jit, static_argnames=("spec", "quantize"))
def _head(ln_f, head, h, labels, spec, quantize):
    with jax.default_matmul_precision(HIGHEST):
        val, vjp = jax.vjp(
            lambda a, e, x: head_loss(a, e, x, labels, spec, quantize),
            ln_f, head, h)
        g_ln, g_head, g_h = vjp(jnp.ones((), F32))
        return val, g_ln, g_head, g_h


@functools.partial(jax.jit, static_argnames=("spec", "quantize"),
                   donate_argnums=(0,))
def _bwd_layer(lp, h, g_out, aux_weight, lr, spec, quantize):
    with jax.default_matmul_precision(HIGHEST):
        _, vjp = jax.vjp(
            lambda p, x: layer_forward(p, x, spec, quantize), lp, h)
        g_lp, g_h = vjp((g_out, aux_weight))
        return jax.tree.map(lambda p, g: _sgd(p, g, lr), lp, g_lp), g_h


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _bwd_ends(embed, head, ln_f, g_ln, g_head, g_h0, tokens, lr):
    _, vjp = jax.vjp(lambda e: embed_forward(e, tokens), embed)
    (g_embed,) = vjp(g_h0)
    return (_sgd(embed, g_embed, lr), _sgd(head, g_head, lr),
            jax.tree.map(lambda p, g: _sgd(p, g, lr), ln_f, g_ln))


def sgd_step(params, tokens, labels, lr: float, spec: Spec,
             quantize=None):
    """(params, loss) after one step of plain SGD on the mean loss
    with its router losses. `params` is consumed (its buffers are
    donated layer by layer)."""
    lr = jnp.asarray(lr, F32)
    n = len(params["layers"])
    hs, val = [_fwd_embed(params["embed"], tokens)], 0.0
    for lp in params["layers"]:
        h, aux = _fwd_layer(lp, hs[-1], spec, quantize)
        hs.append(h)
        val = val + aux / n
    ce, g_ln, g_head, g_h = _head(params["ln_f"], params["head"],
                                  hs.pop(), labels, spec, quantize)
    layers = list(params["layers"])
    for i in reversed(range(n)):
        layers[i], g_h = _bwd_layer(layers[i], hs.pop(), g_h,
                                    jnp.asarray(1.0 / n, F32), lr, spec,
                                    quantize)
    embed, head, ln_f = _bwd_ends(
        params["embed"], params["head"], params["ln_f"], g_ln, g_head,
        g_h, tokens, lr)
    return {"embed": embed, "head": head, "ln_f": ln_f,
            "layers": layers}, ce + val
