"""Nemotron-H's hybrid decoder (NVIDIA-Nemotron-3-Nano-30B-A3B,
`model_type` nemotron_h), its training loss and one SGD step, written
plainly.

Straightforward jax.numpy in float32 under
jax.default_matmul_precision("highest"); no kernel, no chunk algebra,
no sort, no grouped matmul (a choice of experts is a [T, E] mask),
nothing imported from the program. Every layer is `h <- h +
mixer(RMSNorm(h))` (eps 1e-5, a gain, no bias), the mixer by the
letter of `hybrid_override_pattern`; after the last layer the final
RMSNorm and the untied head. No product has a bias but the
convolution.

- *`M`, Mamba-2* (Dao & Gu, arXiv:2405.21060; H heads of P, G groups
  of N, inner width H P). `[z | xBC | dt] = x W_in` (widths H P | H P +
  2 G N | H). `xBC_t <- silu(b + sum_j w[:, j] xBC_{t-3+j})`, zeros
  before the sequence: four shifted sums. `xBC = [x | B | C]`, head h
  reads group `h // (H / G)`. `dt = softplus(dt + dt_bias)`, `A =
  -exp(A_log)`. Per head, `S_0 = 0`:
  `S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`, `y_t = S_t C_t + D x_t`
  — computed TOKEN BY TOKEN, `S_t` from `S_{t-1}`, so that it shares
  nothing with the program's chunked form. `y <- RMSNorm_groups(y *
  silu(z)) * g` (the gate first, the norm over each group's H P / G
  channels on its own), `out = y W_out`.
- *`*`, attention*: `q = x W_q` (heads of `head_dim`), `k = x W_k`, `v
  = x W_v` (`n_kv_heads` heads, each repeated for its `n_heads /
  n_kv_heads` queries), NO rotary embedding and no position table, a
  dense causal softmax at scale `head_dim ** -0.5`, `o W_o`.
- *`E`, experts*: `s = sigmoid(x W_g)` over ALL experts; chosen = the
  `top_k` largest of `s + b` (`b` a buffer without gradient); weights
  `scale * s[chosen] / sum s[chosen]`; `h += sum over the chosen
  experts THIS chip holds of g_e relu(x W1_e)^2 W2_e + relu(x Ws1)^2
  Ws2` — no gate matrix anywhere.
- loss = mean next-token cross-entropy (no router loss in the config).

Departures from the public modeling file, all shared with the program
(benchmark/configs/nemotron-3-nano-30b-a3b.json, `assumed`): the
config's `rope_theta` / `partial_rotary_factor` are not read (the
family's attention applies no rotary embedding); the experts other
chips hold (all but `w1.shape[0]` from `held_first`) add nothing; `b`
is held fixed; no dt clamp (`time_step_limit` is (0, inf)); one
sequence has no document boundary, so no state is ever reset; plain
SGD. Bookkeeping, not departures: the token loop is nested in blocks
of `block` tokens under `jax.checkpoint`, so that its gradient holds a
carried state per block and one block's states inside (64 x 2.1 MB and
128 x 2.1 MB at 8,192 tokens) instead of 8,192 states; attention takes
a block of query rows at a time and the experts one at a time (each
made again in the backward pass); the step is taken layer by layer
(forward keeping each layer's input, then one vjp per layer backwards,
updating that layer at once), as glm5_decoder.py does.

Precision as the configuration states it: parameters STORED in
`param_dtype`, the gradient reaches the optimizer in that type, the SGD
update is computed in float32 and rounded back. Everything else is
float32. `quantize` puts the control in the reference's place: every
matmul operand the configuration states as bfloat16 (the projections,
the attention products, what enters the scan — x, B, C —, the FFNs,
the head; not the router, which it states as float32) is rounded to a
lower-precision type first, with a straight-through gradient.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = "highest"
NEG = -jnp.inf
#: a state-space layer's leaves whose gradients exist only through the
#: scan, the convolution and the gated norm
SSM_SMALL = ("A_log", "dt_bias", "D", "conv_w", "conv_b", "ssm_norm")


class Spec(NamedTuple):
    """What the equations need beside the weights' shapes."""
    pattern: str
    n_heads: int
    n_kv_heads: int
    ssm_heads: int
    ssm_groups: int
    ssm_state: int
    top_k: int
    held_first: int = 0
    routed_scale: float = 2.5
    rms_eps: float = 1e-5
    #: bookkeeping: tokens a checkpointed block of the token loop,
    #: query rows of attention at a time
    block: int = 128
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


# -- M: the state-space mixer -------------------------------------------------

def causal_conv(xbc, w, b):
    """xbc [T, C], w [C, K], b [C]: K shifted sums, zeros before the
    sequence, then SiLU."""
    t, k = xbc.shape[0], w.shape[1]
    out = b
    for j in range(k):
        back = k - 1 - j  # tap j reads the token `back` places earlier
        shifted = jnp.concatenate(
            [jnp.zeros((back, xbc.shape[1]), xbc.dtype), xbc[:t - back]])
        out = out + w[:, j] * shifted
    return jax.nn.silu(out)


def recurrence(x, dt, a, bm, cm, block: int):
    """One sequence, token by token. x [T, H, P], dt [T, H], a [H],
    bm, cm [T, H, N] (each head's group already chosen) -> (y [T, H,
    P] without the D term, the state after the last token [H, P, N])."""
    t, h, p = x.shape
    n = bm.shape[-1]
    block = _divisor(t, block)

    def token(s, now):
        x_t, dt_t, b_t, c_t = now
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, c_t)

    @jax.checkpoint
    def tokens_of_a_block(s, blk):
        return jax.lax.scan(token, s, blk)

    blocks = tuple(v.reshape(t // block, block, *v.shape[1:])
                   for v in (x, dt, bm, cm))
    last, y = jax.lax.scan(tokens_of_a_block, jnp.zeros((h, p, n), F32),
                           blocks)
    return y.reshape(t, h, p), last


def ssm_mixer(lp, x, spec: Spec, quantize=None):
    """x [B, T, d] (normed) -> (the mixer's output [B, T, d], the state
    after the last token [B, H, P, N]); lp in float32."""
    heads, g, n = spec.ssm_heads, spec.ssm_groups, spec.ssm_state
    inner = lp["out_proj"].shape[0]
    p = inner // heads
    a = -jnp.exp(lp["A_log"])

    def one(x):
        t = x.shape[0]
        zxd = _mm(x, lp["in_proj"], quantize)
        z, xbc, dt = (zxd[:, :inner], zxd[:, inner:2 * inner + 2 * g * n],
                      zxd[:, 2 * inner + 2 * g * n:])
        xbc = _q(causal_conv(xbc, lp["conv_w"], lp["conv_b"]), quantize)
        xs = xbc[:, :inner].reshape(t, heads, p)
        group_of = jnp.arange(heads) // (heads // g)
        bm = xbc[:, inner:inner + g * n].reshape(t, g, n)[:, group_of]
        cm = xbc[:, inner + g * n:].reshape(t, g, n)[:, group_of]
        dt = jax.nn.softplus(dt + lp["dt_bias"])
        y, last = recurrence(xs, dt, a, bm, cm, spec.block)
        y = (y + lp["D"][:, None] * xs).reshape(t, inner)
        gated = (y * jax.nn.silu(z)).reshape(t, g, inner // g)
        y = rms_norm(gated, 1.0, spec.rms_eps).reshape(t, inner) \
            * lp["ssm_norm"]["g"]
        return _mm(y, lp["out_proj"], quantize), last

    return jax.vmap(one)(x)


# -- *: attention ---------------------------------------------------------------

def attention(lp, x, spec: Spec, quantize=None):
    """x [B, T, d] (normed) -> [B, T, d]: dense causal softmax over the
    repeated key heads, a block of query rows at a time."""
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
    return _mm(o, lp["wo"], quantize)


# -- E: experts -------------------------------------------------------------------

def ffn(x, w1, w2, quantize=None):
    """relu(x W1)^2 W2: no gate matrix."""
    return _mm(jnp.square(jax.nn.relu(_mm(x, w1, quantize))), w2, quantize)


def route(x, wg, bias, spec: Spec):
    """x [T, d] -> (chosen [T, E] bool, weights [T, E]: g of the
    chosen, 0 elsewhere)."""
    s = jax.nn.sigmoid(x @ wg)
    idx = jax.lax.top_k(s + jax.lax.stop_gradient(bias), spec.top_k)[1]
    chosen = jax.nn.one_hot(idx, s.shape[-1], dtype=F32).sum(1) > 0
    kept = jnp.where(chosen, s, 0.0)
    return chosen, spec.routed_scale * kept / kept.sum(-1, keepdims=True)


def experts_sum(x, weights, w1, w2, quantize=None):
    """sum_e weights[:, e] * FFN_e(x), every held expert on every
    token, one expert at a time."""
    def one(m, a, c):
        return m[:, None] * ffn(x, a, c, quantize)

    def body(y, ws):
        return y + jax.checkpoint(one)(*ws), None

    return jax.lax.scan(body, jnp.zeros_like(x), (weights.T, w1, w2))[0]


def experts(lp, x, spec: Spec, quantize=None):
    b, t, d = x.shape
    x = x.reshape(b * t, d)
    _, weights = route(x, lp["wg"], lp["wg_bias"], spec)
    held = lp["w1"].shape[0]
    weights = weights[:, spec.held_first:spec.held_first + held]
    y = experts_sum(x, weights, lp["w1"], lp["w2"], quantize) \
        + ffn(x, lp["ws1"], lp["ws2"], quantize)
    return y.reshape(b, t, d)


# -- the layer, the ends, the loss ---------------------------------------------

def layer_forward(lp, h, kind: str, spec: Spec, quantize=None):
    """h + mixer(norm(h)); lp in float32."""
    x = rms_norm(h, lp["ln"]["g"], spec.rms_eps)
    if kind == "M":
        return h + ssm_mixer(lp, x, spec, quantize)[0]
    if kind == "*":
        return h + attention(lp, x, spec, quantize)
    if kind == "E":
        return h + experts(lp, x, spec, quantize)
    raise ValueError(f"no layer kind {kind!r}")


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
        for kind, lp in zip(spec.pattern, params["layers"]):
            h = layer_forward(_f32(lp), h, kind, spec, quantize)
        return head_loss(params["ln_f"], params["head"], h, labels, spec,
                         quantize)


def _up_to(params, tokens, kind: str, spec: Spec, quantize):
    """(the first layer of `kind`'s float32 leaves, its normed input)."""
    h = embed_forward(params["embed"], tokens)
    for k, lp in zip(spec.pattern, params["layers"]):
        lp = _f32(lp)
        if k == kind:
            return lp, rms_norm(h, lp["ln"]["g"], spec.rms_eps)
        h = layer_forward(lp, h, k, spec, quantize)
    raise ValueError(f"no layer {kind!r} in {spec.pattern!r}")


@functools.partial(jax.jit, static_argnames=("spec", "quantize"))
def first_ssm(params, tokens, spec: Spec, quantize=None):
    """(the first state-space layer's mixer output [B, T, d], its state
    after the last token [B, H, P, N]) for a batch."""
    with jax.default_matmul_precision(HIGHEST):
        return ssm_mixer(*_up_to(params, tokens, "M", spec, quantize), spec,
                         quantize)


@functools.partial(jax.jit, static_argnames=("spec", "quantize"))
def first_attention(params, tokens, spec: Spec, quantize=None):
    """The first attention layer's mixer output [B, T, d] for a
    batch."""
    with jax.default_matmul_precision(HIGHEST):
        return attention(*_up_to(params, tokens, "*", spec, quantize), spec,
                         quantize)


@functools.partial(jax.jit, static_argnames=("spec", "quantize"))
def chosen_experts(params, tokens, spec: Spec, quantize=None):
    """bool [T, E]: the first expert layer's chosen experts for a
    batch (tokens flattened)."""
    with jax.default_matmul_precision(HIGHEST):
        lp, x = _up_to(params, tokens, "E", spec, quantize)
        return route(x.reshape(-1, x.shape[-1]), lp["wg"], lp["wg_bias"],
                     spec)[0]


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
    """(the updated layer, the gradient of its input, the float32
    norms of the gradients of its `SSM_SMALL` leaves: zeros for a layer
    that is no state-space layer)."""
    with jax.default_matmul_precision(HIGHEST):
        _, vjp = jax.vjp(
            lambda p, x: layer_forward(p, x, kind, spec, quantize),
            _f32(lp), h)
        g_lp, g_h = vjp(g_out)
        small = jnp.stack([
            jnp.sqrt(sum(jnp.sum(a * a) for a in jax.tree.leaves(g_lp[n])))
            for n in SSM_SMALL]) if kind == "M" else jnp.zeros(
                len(SSM_SMALL), F32)
        return _sgd_tree(lp, g_lp, lr), g_h, small


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _bwd_ends(embed, head, ln_f, g_ln, g_head, g_h0, tokens, lr):
    _, vjp = jax.vjp(lambda e: embed_forward(e, tokens), embed)
    return (_sgd(embed, vjp(g_h0)[0], lr), _sgd(head, g_head, lr),
            _sgd_tree(ln_f, g_ln, lr))


def sgd_step(params, tokens, labels, lr: float, spec: Spec, quantize=None):
    """(params, loss, the float32 gradient norms of the state-space
    layers' `SSM_SMALL` leaves [M layers, 6]) after one step of plain
    SGD. `params` is consumed (its buffers are donated layer by
    layer)."""
    lr = jnp.asarray(lr, F32)
    hs = [_fwd_embed(params["embed"], tokens)]
    for kind, lp in zip(spec.pattern, params["layers"]):
        hs.append(_fwd_layer(lp, hs[-1], kind, spec, quantize))
    val, g_ln, g_head, g_h = _head(params["ln_f"], params["head"], hs.pop(),
                                   labels, spec, quantize)
    layers, small = list(params["layers"]), {}
    for i in reversed(range(len(layers))):
        layers[i], g_h, small[i] = _bwd_layer(
            layers[i], hs.pop(), g_h, lr, spec.pattern[i], spec, quantize)
    embed, head, ln_f = _bwd_ends(params["embed"], params["head"],
                                  params["ln_f"], g_ln, g_head, g_h, tokens,
                                  lr)
    return (dict(embed=embed, head=head, ln_f=ln_f, layers=layers), val,
            jnp.stack([small[i] for i, kind in enumerate(spec.pattern)
                       if kind == "M"]))
