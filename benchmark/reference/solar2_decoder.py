"""Solar-Open2's hybrid decoder (upstage/Solar-Open2-250B, `model_type`
solar_open2), its training loss and one SGD step, written plainly.

Straightforward jax.numpy in float32 under
jax.default_matmul_precision("highest"); no kernel, no chunk, no WY
form, no sort, no grouped matmul (a choice of experts is a [T, E]
mask), nothing imported from the program. Every layer is `h <- h +
mixer(RMSNorm(h))`, `h <- h + experts(RMSNorm(h))` (eps 1e-5, a gain,
no bias); after the last layer the final RMSNorm and the untied head.
No product has a bias and nothing knows a position.

- *G, gated attention* (the layers of `gqa_layers`): `q = x W_q` (heads
  of `head_dim`), `k = x W_k`, `v = x W_v` (`n_kv_heads` heads, each
  repeated for its `n_heads / n_kv_heads` queries), no rotation, a
  dense causal softmax at scale `head_dim ** -0.5`, then `o <- o *
  sigmoid(x W_a)` elementwise on the same normed x, `o W_o`.
- *K, Kimi Delta Attention* (every other layer; arXiv:2510.26692; H
  heads, keys and values K wide). `q, k, v = silu(conv4(x W_q))`, ...:
  four shifted sums over time with zeros before the sequence, one
  filter a channel, no bias; q and k divided by the root of their
  summed squares over a head (+ 1e-6). `g = -exp(A_log) softplus((x
  W_fa) W_fb + dt_bias)` one a CHANNEL, `beta = 2 sigmoid(x W_b)` one a
  head. Per head, `S_0 = 0`: `S' = Diag(exp(g_t)) S_{t-1}`, `S_t = S' +
  beta_t k_t (v_t - k_t^T S')^T`, `o_t = S_t^T q_t K^-0.5` — computed
  TOKEN BY TOKEN, `S_t` from `S_{t-1}`, so that it shares nothing with
  the program's chunked form. `y = RMSNorm_head(o) * gain * sigmoid((x
  W_ga) W_gb)` (one gain [K] for all heads), `out = y W_o`.
- *experts* (every layer): `s = sigmoid(x W_g)` over ALL experts;
  chosen = the `top_k` largest of `s + b` (`b` a buffer without
  gradient); weights `scale * s[chosen] / sum s[chosen]`; `h += sum
  over the chosen experts THIS chip holds of g_e (silu(x W1_e) * (x
  W3_e)) W2_e + the shared expert's (silu(x Ws1) * (x Ws3)) Ws2`.
- loss = mean next-token cross-entropy (no router loss in the config).

Departures from the source, all shared with the program
(benchmark/configs/solar-open2-250b.json, `assumed`): the two
bottlenecks' rank is `head_dim`; the attention gate is elementwise;
the router is the sigmoid / noaux_tc family's with `b` held fixed; the
norm comes before the gate on a delta-rule layer's output; the experts
other chips hold (all but `w1.shape[0]` from `held_first`) add nothing;
one sequence has no document boundary, so no state or tap is ever
reset; plain SGD. Bookkeeping, not departures: the token loop is nested
in blocks of `block` tokens under `jax.checkpoint`, a few heads at a
time (`head_block`), so that its gradient holds a carried state per
block and one block's states inside instead of 8,192 states of 64
heads; attention takes a block of query rows at a time and the experts
one at a time (each made again in the backward pass); the step is
taken sub-layer by sub-layer (forward keeping each sub-layer's input,
then one vjp per sub-layer backwards, updating its leaves at once: an
expert part's float32 copy and gradient are 2.6 GB each), as
glm5_decoder.py does layer by layer.

Precision as the configuration states it: parameters STORED in
`param_dtype`, the gradient reaches the optimizer in that type, the SGD
update is computed in float32 and rounded back. Everything else is
float32. `quantize` puts the control in the reference's place: every
matmul operand the configuration states as bfloat16 (the projections,
the attention products, what enters the recurrence — q, k, v —, the
FFNs, the head; not the router, the decay or beta, which it states as
float32) is rounded to a lower-precision type first, with a
straight-through gradient.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = "highest"
NEG = -jnp.inf
#: the leaves of a layer's first sub-layer (its mixer and the norm in
#: front); every other leaf of a layer is the expert part's
GQA = ("ln1", "wq", "wk", "wv", "wa", "wo")
KDA = ("ln1", "wq", "wk", "wv", "conv_q", "conv_k", "conv_v", "w_fa",
       "w_fb", "dt_bias", "A_log", "w_b", "o_norm", "w_ga", "w_gb", "wo")


class Spec(NamedTuple):
    """What the equations need beside the weights' shapes."""
    gqa_layers: Tuple[int, ...]
    n_heads: int
    n_kv_heads: int
    kda_heads: int
    top_k: int
    held_first: int = 0
    routed_scale: float = 1.0
    rms_eps: float = 1e-5
    l2_eps: float = 1e-6
    #: bookkeeping: tokens a checkpointed block of the token loop, heads
    #: of it at a time, query rows of attention at a time
    block: int = 128
    head_block: int = 16
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


# -- K: Kimi Delta Attention ----------------------------------------------------

def short_conv(x, w):
    """x [T, C], w [C, K]: K shifted sums, zeros before the sequence,
    then SiLU; no bias."""
    t, k = x.shape[0], w.shape[1]
    out = jnp.zeros_like(x)
    for j in range(k):
        back = k - 1 - j  # tap j reads the token `back` places earlier
        shifted = jnp.concatenate(
            [jnp.zeros((back, x.shape[1]), x.dtype), x[:t - back]])
        out = out + w[:, j] * shifted
    return jax.nn.silu(out)


def delta_recurrence(q, k, v, g, beta, block: int):
    """A few heads of one sequence, token by token. q, k, g [T, H, K],
    v [T, H, V], beta [T, H] -> (o [T, H, V], the state after the last
    token [H, K, V])."""
    t, h, width = k.shape
    block = _divisor(t, block)

    def token(s, now):
        q_t, k_t, v_t, g_t, b_t = now
        s = jnp.exp(g_t)[:, :, None] * s
        miss = v_t - jnp.einsum("hk,hkv->hv", k_t, s)
        s = s + (b_t[:, None] * k_t)[:, :, None] * miss[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    @jax.checkpoint
    def tokens_of_a_block(s, blk):
        return jax.lax.scan(token, s, blk)

    blocks = tuple(a.reshape(t // block, block, *a.shape[1:])
                   for a in (q, k, v, g, beta))
    last, o = jax.lax.scan(tokens_of_a_block,
                           jnp.zeros((h, width, v.shape[-1]), F32), blocks)
    return o.reshape(t, h, -1), last


def kda_mixer(lp, x, spec: Spec, quantize=None):
    """x [B, T, d] (normed) -> (the mixer's output [B, T, d], the state
    after the last token [B, H, K, K]); lp in float32. A few heads at a
    time from the products to the heads' rows of `wo` (a head reads
    nothing of another's), each such run made again in the backward
    pass: the sum of the runs' partial products is `y W_o`."""
    heads = spec.kda_heads
    width = lp["wo"].shape[0] // heads
    each = _divisor(heads, spec.head_block)

    def l2(a):
        return a / jnp.sqrt((a * a).sum(-1, keepdims=True) + spec.l2_eps)

    def of(a, first, axis):  # the run's heads' channels of a leaf
        return jax.lax.dynamic_slice_in_dim(a, first * width, each * width,
                                            axis)

    def one(x):
        t = x.shape[0]
        decay_low = _mm(x, lp["w_fa"], quantize)
        gate_low = _mm(x, lp["w_ga"], quantize)
        beta = 2.0 * jax.nn.sigmoid(_mm(x, lp["w_b"], quantize))

        @jax.checkpoint
        def some_heads(first):
            def made(name):
                a = short_conv(_mm(x, of(lp["w" + name], first, 1), quantize),
                               of(lp["conv_" + name], first, 0))
                return a.reshape(t, each, width)

            q = _q(l2(made("q")) * width ** -0.5, quantize)
            k, v = _q(l2(made("k")), quantize), _q(made("v"), quantize)
            step = jax.nn.softplus(
                _mm(decay_low, of(lp["w_fb"], first, 1), quantize)
                + of(lp["dt_bias"], first, 0))
            a_log = jax.lax.dynamic_slice_in_dim(lp["A_log"], first, each)
            g = -jnp.exp(a_log)[:, None] * step.reshape(t, each, width)
            o, last = delta_recurrence(
                q, k, v, g,
                jax.lax.dynamic_slice_in_dim(beta, first, each, 1),
                spec.block)
            gate = jax.nn.sigmoid(
                _mm(gate_low, of(lp["w_gb"], first, 1), quantize))
            y = rms_norm(o, lp["o_norm"]["g"], spec.rms_eps).reshape(t, -1)
            return _mm(y * gate, of(lp["wo"], first, 0), quantize), last

        outs, last = jax.lax.map(some_heads, jnp.arange(0, heads, each))
        return outs.sum(0), last.reshape(heads, width, width)

    return jax.vmap(one)(x)


# -- G: gated attention ----------------------------------------------------------

def attention(lp, x, spec: Spec, quantize=None):
    """x [B, T, d] (normed) -> [B, T, d]: dense causal softmax over the
    repeated key heads, a block of query rows at a time, the output
    gated elementwise."""
    b, t, _ = x.shape
    h, kv = spec.n_heads, spec.n_kv_heads
    dh = lp["wq"].shape[1] // h
    q = _mm(x, lp["wq"], quantize).reshape(b, t, h, dh)
    k = _mm(x, lp["wk"], quantize).reshape(b, t, kv, dh)
    v = _mm(x, lp["wv"], quantize).reshape(b, t, kv, dh)
    k, v = (jnp.repeat(u, h // kv, axis=2) for u in (k, v))
    q, k, v = (_q(u, quantize) for u in (q, k, v))
    rows = _divisor(t, spec.q_rows)

    @jax.checkpoint
    def block(first, qb):
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * dh ** -0.5
        seen = (first + jnp.arange(rows))[:, None] >= jnp.arange(t)[None]
        p = jax.nn.softmax(jnp.where(seen, s, NEG), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    o = jax.lax.map(lambda a: block(*a), (
        jnp.arange(0, t, rows),
        jnp.moveaxis(q.reshape(b, t // rows, rows, h, dh), 1, 0)))
    o = jnp.moveaxis(o, 0, 1).reshape(b, t, h * dh)
    o = o * jax.nn.sigmoid(_mm(x, lp["wa"], quantize))
    return _mm(o, lp["wo"], quantize)


# -- experts ----------------------------------------------------------------------

def ffn(x, w1, w3, w2, quantize=None):
    """(silu(x W1) * (x W3)) W2."""
    return _mm(jax.nn.silu(_mm(x, w1, quantize)) * _mm(x, w3, quantize),
               w2, quantize)


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
    def one(m, a, b, c):
        return m[:, None] * ffn(x, a, b, c, quantize)

    def body(y, ws):
        return y + jax.checkpoint(one)(*ws), None

    return jax.lax.scan(body, jnp.zeros_like(x), (weights.T, w1, w3, w2))[0]


def experts(lp, x, spec: Spec, quantize=None):
    b, t, d = x.shape
    x = x.reshape(b * t, d)
    _, weights = route(x, lp["wg"], lp["wg_bias"], spec)
    held = lp["w1"].shape[0]
    weights = weights[:, spec.held_first:spec.held_first + held]
    y = experts_sum(x, weights, lp["w1"], lp["w3"], lp["w2"], quantize) \
        + ffn(x, lp["ws1"], lp["ws3"], lp["ws2"], quantize)
    return y.reshape(b, t, d)


# -- the sub-layers, the ends, the loss ------------------------------------------

def mixer_forward(lp, h, gqa: bool, spec: Spec, quantize=None):
    """h + mixer(norm(h)); lp (the mixer's leaves) in float32."""
    x = rms_norm(h, lp["ln1"]["g"], spec.rms_eps)
    if gqa:
        return h + attention(lp, x, spec, quantize)
    return h + kda_mixer(lp, x, spec, quantize)[0]


def experts_forward(lp, h, spec: Spec, quantize=None):
    """h + experts(norm(h)); lp (the expert part's leaves) in float32."""
    return h + experts(lp, rms_norm(h, lp["ln2"]["g"], spec.rms_eps), spec,
                       quantize)


def parts(lp, gqa: bool):
    """A layer's leaves as (its mixer's, its expert part's)."""
    first = GQA if gqa else KDA
    return ({n: lp[n] for n in first},
            {n: v for n, v in lp.items() if n not in first})


def layer_forward(lp, h, gqa: bool, spec: Spec, quantize=None):
    mixer, rest = parts(lp, gqa)
    return experts_forward(rest, mixer_forward(mixer, h, gqa, spec, quantize),
                           spec, quantize)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def embed_forward(embed, tokens):
    return embed[tokens].astype(F32)


def head_loss(ln_f, head, h, labels, spec: Spec, quantize=None):
    """Mean cross-entropy through the untied head."""
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
        for i, lp in enumerate(params["layers"]):
            h = layer_forward(_f32(lp), h, i in spec.gqa_layers, spec,
                              quantize)
        return head_loss(params["ln_f"], params["head"], h, labels, spec,
                         quantize)


@functools.partial(jax.jit, static_argnames=("layer", "spec", "quantize"))
def mixer_out(params, tokens, layer: int, spec: Spec, quantize=None):
    """Layer `layer`'s mixer on the EMBEDDED batch (the stream entering
    layer 0, as the program's attn_probe / kda_probe read it): a
    delta-rule layer's (output [B, T, d], state after the last token
    [B, H, K, K]), gated attention's output [B, T, d]."""
    with jax.default_matmul_precision(HIGHEST):
        gqa = layer in spec.gqa_layers
        lp = _f32(parts(params["layers"][layer], gqa)[0])
        x = rms_norm(embed_forward(params["embed"], tokens),
                     lp["ln1"]["g"], spec.rms_eps)
        return attention(lp, x, spec, quantize) if gqa \
            else kda_mixer(lp, x, spec, quantize)


@functools.partial(jax.jit, static_argnames=("spec", "quantize"))
def chosen_experts(params, tokens, spec: Spec, quantize=None):
    """bool [T, E]: layer 0's chosen experts for a batch (tokens
    flattened)."""
    with jax.default_matmul_precision(HIGHEST):
        gqa = 0 in spec.gqa_layers
        mixer, rest = parts(_f32(params["layers"][0]), gqa)
        h = mixer_forward(mixer, embed_forward(params["embed"], tokens),
                          gqa, spec, quantize)
        x = rms_norm(h, rest["ln2"]["g"], spec.rms_eps)
        return route(x.reshape(-1, x.shape[-1]), rest["wg"],
                     rest["wg_bias"], spec)[0]


# -- one SGD step, a sub-layer at a time ----------------------------------------

def _sgd(p, g, lr):
    """The update as the configuration states it: the gradient in the
    parameters' type, the arithmetic in float32, the result stored."""
    return (p.astype(F32) - lr * g.astype(p.dtype).astype(F32)
            ).astype(p.dtype)


def _sgd_tree(tree, grads, lr):
    return jax.tree.map(lambda p, g: _sgd(p, g, lr), tree, grads)


def _sub_forward(lp, h, which, spec, quantize):
    """`which`: "gqa" / "kda" (the mixer's sub-layer) or "experts"."""
    if which == "experts":
        return experts_forward(lp, h, spec, quantize)
    return mixer_forward(lp, h, which == "gqa", spec, quantize)


_fwd_embed = jax.jit(embed_forward)


@functools.partial(jax.jit, static_argnames=("which", "spec", "quantize"))
def _fwd_sub(lp, h, which, spec, quantize):
    with jax.default_matmul_precision(HIGHEST):
        return _sub_forward(_f32(lp), h, which, spec, quantize)


@functools.partial(jax.jit, static_argnames=("spec", "quantize"))
def _head(ln_f, head, h, labels, spec, quantize):
    """The head's loss and its gradients (float32)."""
    with jax.default_matmul_precision(HIGHEST):
        val, vjp = jax.vjp(
            lambda a, e, x: head_loss(a, e, x, labels, spec, quantize),
            ln_f, head, h)
        return (val,) + vjp(jnp.ones((), F32))


@functools.partial(jax.jit, static_argnames=("which", "spec", "quantize"),
                   donate_argnums=(0,))
def _bwd_sub(lp, h, g_out, lr, which, spec, quantize):
    """(the updated sub-layer, the gradient of its input). The vjp is
    taken through the cast of the stored leaves, so a leaf's gradient
    arrives in the parameters' type, as the configuration states it."""
    with jax.default_matmul_precision(HIGHEST):
        _, vjp = jax.vjp(
            lambda p, x: _sub_forward(_f32(p), x, which, spec, quantize),
            lp, h)
        g_lp, g_h = vjp(g_out)
        return _sgd_tree(lp, g_lp, lr), g_h


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _bwd_ends(embed, head, ln_f, g_ln, g_head, g_h0, tokens, lr):
    _, vjp = jax.vjp(lambda e: embed_forward(e, tokens), embed)
    return (_sgd(embed, vjp(g_h0)[0], lr), _sgd(head, g_head, lr),
            _sgd_tree(ln_f, g_ln, lr))


def sgd_step(params, tokens, labels, lr: float, spec: Spec, quantize=None):
    """(params, loss) after one step of plain SGD. `params` is
    consumed (its buffers are donated sub-layer by sub-layer)."""
    lr = jnp.asarray(lr, F32)
    subs = []  # (layer, which, the sub-layer's leaves), in forward order
    for i, lp in enumerate(params["layers"]):
        gqa = i in spec.gqa_layers
        mixer, rest = parts(lp, gqa)
        subs += [(i, "gqa" if gqa else "kda", mixer), (i, "experts", rest)]
    hs = [_fwd_embed(params["embed"], tokens)]
    for _, which, lp in subs:
        hs.append(_fwd_sub(lp, hs[-1], which, spec, quantize))
    val, g_ln, g_head, g_h = _head(params["ln_f"], params["head"], hs.pop(),
                                   labels, spec, quantize)
    layers = [{} for _ in params["layers"]]
    for i, which, lp in reversed(subs):
        new, g_h = _bwd_sub(lp, hs.pop(), g_h, lr, which, spec, quantize)
        layers[i].update(new)
    embed, head, ln_f = _bwd_ends(params["embed"], params["head"],
                                  params["ln_f"], g_ln, g_head, g_h, tokens,
                                  lr)
    return dict(embed=embed, head=head, ln_f=ln_f, layers=layers), val
