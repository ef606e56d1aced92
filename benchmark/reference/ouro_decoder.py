"""Ouro's looped decoder, its training loss and one SGD step, written
plainly.

Straightforward jax.numpy in float32 under
jax.default_matmul_precision("highest"); no kernels, no recomputation
that changes arithmetic, nothing imported from the program. It follows
Ouro-2.6B (`model_type` ouro; the LoopLM of "Scaling Latent Reasoning
via Looped Language Models", arXiv:2510.25741) as this writer reads the
published description and `modeling_ouro.py` — there is no network
here, so each line that no config key states is marked ASSUMED and is
an `assumed` entry of benchmark/configs/ouro-2.6b.json. `x` is the
residual stream, every norm an RMSNorm with a learned gain and no bias:

    x = E[tokens]
    for s in 0 .. loops - 1:             # total_ut_steps: the SAME layers
        for l in layers:                 # and the same weights, each pass
            x = x + RMS_l,1b( Attn_l( RMS_l,1a(x) ) )
            u = RMS_l,2a(x);  x = x + RMS_l,2b( W2 (silu(W1 u) * (W3 u)) )
        h_s = RMS_f(x);  x = h_s
        nll_s[i]    = CE( h_s[i] W_head^T, label[i] )
        lambda_s[i] = sigmoid( h_s[i] . w_g + b_g )
    p_0 = lambda_0;  p_s = lambda_s prod_{j<s} (1 - lambda_j);
    p_last = prod_{j<last} (1 - lambda_j)
    loss = mean_i [ sum_s p_s[i] nll_s[i] - beta H(p[i]) ],
    H(p) = - sum_s p_s log p_s

- Attention: causal softmax over 16 equal heads (num_key_value_heads =
  num_attention_heads: full MHA), scale 1 / sqrt(head_dim), rotate-half
  RoPE (dimension i pairs with i + head_dim / 2) at `rope_theta`, no
  bias, no QK-norm, no sliding window (config keys).
- ASSUMED, sandwich norm: an RMSNorm before AND after each sub-layer
  (four a layer), the second on the sub-layer's output before it joins
  the stream.
- ASSUMED, the final norm is INSIDE the loop: pass s + 1 starts from the
  normed state, and every exit reads that same normed state.
- ASSUMED, the gate is Linear(hidden -> 1) with a bias, read through a
  sigmoid; the last pass takes what no earlier exit took.
- ASSUMED, the objective is the paper's Stage-I loss: the expected task
  loss under the learned exit distribution less `beta` times its
  entropy (a uniform prior), beta 0.1; gradients flow to the model
  through every nll_s and to the gate through p.

The step is taken an application at a time (forward keeping each
application's input, then one vjp per application backwards, the four
contributions to a layer's gradient SUMMED in float32 before the one
update), attention one head at a time, so that the float32 copies fit
one 16 GB chip at the published widths and 4,096 positions.

Precision as the configuration states it: parameters STORED in
`param_dtype`, the gradient reaches the optimizer in that type, the SGD
update is computed in float32 and rounded back. Everything else is
float32.

`quantize` puts the control in the reference's place: every matmul
operand the configuration states as bfloat16 (the projections, the
attention products, the FFN, the head — not the gate, which the
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
    loops: int = 4
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    beta: float = 0.1


def _q(x, quantize):
    """Round to `quantize` and back (straight-through), or nothing."""
    if quantize is None:
        return x
    lo = x.astype(quantize).astype(x.dtype)
    return x + jax.lax.stop_gradient(lo - x)


def rms_norm(x, g, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * g


def rope(x, theta):
    """[B, T, H, Dh] rotated at positions 0 .. T - 1."""
    t, half = x.shape[1], x.shape[-1] // 2
    freq = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(t, dtype=F32)[:, None] * freq[None, :]
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


def layer_forward(lp, x, spec: Spec, quantize=None):
    """x after one application of the block (sandwich norm)."""
    lp = jax.tree.map(lambda a: a.astype(F32), lp)
    b, t, d = x.shape
    mm = lambda a, w: _q(a, quantize) @ _q(w, quantize)  # noqa: E731
    split = lambda a: a.reshape(b, t, spec.n_heads,  # noqa: E731
                                d // spec.n_heads)
    u = rms_norm(x, lp["ln1"]["g"], spec.rms_eps)
    q, k, v = (split(mm(u, lp[w])) for w in ("wq", "wk", "wv"))
    o = attention(_q(rope(q, spec.rope_theta), quantize),
                  _q(rope(k, spec.rope_theta), quantize), _q(v, quantize))
    x = x + rms_norm(mm(o.reshape(b, t, d), lp["wo"]),
                     lp["ln1_post"]["g"], spec.rms_eps)
    u = rms_norm(x, lp["ln2"]["g"], spec.rms_eps)
    y = mm(jax.nn.silu(mm(u, lp["w1"])) * mm(u, lp["w3"]), lp["w2"])
    return x + rms_norm(y, lp["ln2_post"]["g"], spec.rms_eps)


def embed_forward(embed, tokens):
    return embed[tokens].astype(F32)


def final_norm(ln_f, x, spec: Spec):
    return rms_norm(x, ln_f["g"].astype(F32), spec.rms_eps)


def exit_nll(head, h, labels, quantize=None):
    """Cross-entropy at each position [B, T] of the shared head on the
    normed state h of one pass."""
    logits = _q(h, quantize) @ _q(head.astype(F32), quantize).T
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return logz - gold


def exit_lambda(gate, h):
    """The gate of one pass at each position [B, T]."""
    return jax.nn.sigmoid(h @ gate["w"].astype(F32)
                          + gate["b"].astype(F32))


def exit_distribution(lambdas):
    """[loops, B, T] from the first loops - 1 gates [loops - 1, B, T]:
    exit s takes lambda_s of what the earlier ones left, the last all
    that is left."""
    p, left = [], jnp.ones_like(lambdas[0])
    for lam in lambdas:
        p.append(lam * left)
        left = left * (1.0 - lam)
    return jnp.stack(p + [left])


def combine(nlls, lambdas, spec: Spec):
    """The loss from every exit's cross-entropy [loops, B, T] and the
    gates [loops - 1, B, T]: the mean over positions of the expected
    cross-entropy less beta x the exit distribution's entropy."""
    p = exit_distribution(lambdas)
    entropy = -(p * jnp.log(p)).sum(0)
    return ((p * nlls).sum(0) - spec.beta * entropy).mean()


def _passes(params, tokens, spec: Spec, quantize=None, per_pass=None):
    """The normed state after each pass, [loops] x [B, T, D].
    `per_pass`: a list of `loops` layer lists to run instead of
    params["layers"] every time (for the test that a shared layer's
    gradient is the sum over its passes)."""
    x = embed_forward(params["embed"], tokens)
    states = []
    for s in range(spec.loops):
        for lp in (per_pass[s] if per_pass else params["layers"]):
            x = layer_forward(lp, x, spec, quantize)
        x = final_norm(params["ln_f"], x, spec)
        states.append(x)
    return states


def exits(params, tokens, labels, spec: Spec, quantize=None, per_pass=None):
    """(every exit's cross-entropy [loops, B, T], the exit distribution
    [loops, B, T]) of the whole model, for tests and for the comparison
    of the exits at sizes where it fits."""
    with jax.default_matmul_precision(HIGHEST):
        states = _passes(params, tokens, spec, quantize, per_pass)
        nlls = jnp.stack([exit_nll(params["head"], h, labels, quantize)
                          for h in states])
        lambdas = jnp.stack([exit_lambda(params["exit_gate"], h)
                             for h in states[:-1]])
        return nlls, exit_distribution(lambdas), lambdas


def loss(params, tokens, labels, spec: Spec, quantize=None, per_pass=None):
    """The Stage-I objective (whole model, plain autodiff)."""
    nlls, _, lambdas = exits(params, tokens, labels, spec, quantize,
                             per_pass)
    return combine(nlls, lambdas, spec)


def _sgd(p, g, lr):
    """The update as the configuration states it: the gradient in the
    parameters' type, the arithmetic in float32, the result stored."""
    return (p.astype(F32) - lr * g.astype(p.dtype).astype(F32)
            ).astype(p.dtype)


_fwd_embed = jax.jit(embed_forward)


@functools.partial(jax.jit, static_argnames=("spec", "quantize"))
def _fwd_layer(lp, x, spec, quantize):
    with jax.default_matmul_precision(HIGHEST):
        return layer_forward(lp, x, spec, quantize)


@functools.partial(jax.jit, static_argnames=("spec",))
def _fwd_norm(ln_f, x, spec):
    return final_norm(ln_f, x, spec)


@functools.partial(jax.jit, static_argnames=("quantize",))
def _fwd_exit(head, gate, h, labels, quantize):
    with jax.default_matmul_precision(HIGHEST):
        return exit_nll(head, h, labels, quantize), exit_lambda(gate, h)


@functools.partial(jax.jit, static_argnames=("spec",))
def _combine(nlls, lambdas, spec):
    """(loss, its gradient to every exit's cross-entropy and gate)."""
    return jax.value_and_grad(
        lambda a, b: combine(a, b, spec), argnums=(0, 1))(nlls, lambdas)


def _add(acc, g):
    return jax.tree.map(lambda a, b: a + b.astype(F32), acc, g)


@functools.partial(jax.jit, static_argnames=("quantize",),
                   donate_argnums=(0,))
def _bwd_exit(acc, head, gate, h, labels, g_nll, g_lam, quantize):
    """One exit backwards: (its contribution added to the float32
    gradients of (head, gate), the gradient to its normed state).
    `g_lam` None: the last exit, whose gate nothing reads."""
    with jax.default_matmul_precision(HIGHEST):
        _, vjp = jax.vjp(lambda w, x: exit_nll(w, x, labels, quantize),
                         head, h)
        g_head, g_h = vjp(g_nll)
        g_gate = jax.tree.map(jnp.zeros_like, gate)
        if g_lam is not None:
            _, vjp = jax.vjp(exit_lambda, gate, h)
            g_gate, more = vjp(g_lam)
            g_h = g_h + more
        return _add(acc, (g_head, g_gate)), g_h


@functools.partial(jax.jit, static_argnames=("spec",), donate_argnums=(0,))
def _bwd_norm(acc, ln_f, x, g_h, spec):
    _, vjp = jax.vjp(lambda p, a: final_norm(p, a, spec), ln_f, x)
    g_ln, g_x = vjp(g_h)
    return _add(acc, g_ln), g_x


@functools.partial(jax.jit, static_argnames=("spec", "quantize"),
                   donate_argnums=(0,))
def _bwd_layer(acc, lp, x, g_out, spec, quantize):
    """One application backwards: (its contribution added to the
    layer's float32 gradient, the gradient to its input)."""
    with jax.default_matmul_precision(HIGHEST):
        _, vjp = jax.vjp(
            lambda p, a: layer_forward(p, a, spec, quantize), lp, x)
        g_lp, g_x = vjp(g_out)
        return _add(acc, g_lp), g_x


@functools.partial(jax.jit, donate_argnums=(0,))
def _update(tree, grads, lr):
    return jax.tree.map(lambda p, g: _sgd(p, g, lr), tree, grads)


@functools.partial(jax.jit, donate_argnums=(0,))
def _update_embed(embed, g_x0, tokens, lr):
    _, vjp = jax.vjp(lambda e: embed_forward(e, tokens), embed)
    return _sgd(embed, vjp(g_x0)[0], lr)


def _zeros(tree):
    return jax.tree.map(lambda a: jnp.zeros(a.shape, F32), tree)


def sgd_step(params, tokens, labels, lr: float, spec: Spec, quantize=None):
    """(params, loss) after one step of plain SGD on the Stage-I
    objective. `params` is consumed (its buffers are donated)."""
    lr = jnp.asarray(lr, F32)
    layers, n = list(params["layers"]), spec.loops
    head, gate, ln_f = params["head"], params["exit_gate"], params["ln_f"]
    # forward, keeping every application's input and every pass's
    # state before and after the final norm
    x = _fwd_embed(params["embed"], tokens)
    inputs, before, states = [], [], []
    for _ in range(n):
        for lp in layers:
            inputs.append(x)
            x = _fwd_layer(lp, x, spec, quantize)
        before.append(x)
        x = _fwd_norm(ln_f, x, spec)
        states.append(x)
    outs = [_fwd_exit(head, gate, h, labels, quantize) for h in states]
    val, (g_nlls, g_lams) = _combine(
        jnp.stack([o[0] for o in outs]),
        jnp.stack([o[1] for o in outs[:-1]]), spec)
    del outs
    # backward: pass by pass from the last; a pass's normed state feeds
    # its exit AND the next pass
    g_ends, g_ln = _zeros((head, gate)), _zeros(ln_f)
    g_layers = [_zeros(lp) for lp in layers]
    g_x = None
    for s in reversed(range(n)):
        g_ends, g_h = _bwd_exit(
            g_ends, head, gate, states.pop(), labels, g_nlls[s],
            g_lams[s] if s < n - 1 else None, quantize)
        if g_x is not None:
            g_h = g_h + g_x
        g_ln, g_x = _bwd_norm(g_ln, ln_f, before.pop(), g_h, spec)
        for i in reversed(range(len(layers))):
            g_layers[i], g_x = _bwd_layer(g_layers[i], layers[i],
                                          inputs.pop(), g_x, spec, quantize)
    embed = _update_embed(params["embed"], g_x, tokens, lr)
    head, gate = _update((head, gate), g_ends, lr)
    ln_f = _update(ln_f, g_ln, lr)
    layers = [_update(lp, g, lr) for lp, g in zip(layers, g_layers)]
    return {"embed": embed, "head": head, "exit_gate": gate, "ln_f": ln_f,
            "layers": layers}, val


def exit_means(params, tokens, labels, spec: Spec, quantize=None):
    """(mean cross-entropy at each exit [loops], mean probability of
    leaving at each exit [loops]) from the given state, a pass at a
    time (forward only)."""
    x = _fwd_embed(params["embed"], tokens)
    nlls, lambdas = [], []
    for _ in range(spec.loops):
        for lp in params["layers"]:
            x = _fwd_layer(lp, x, spec, quantize)
        x = _fwd_norm(params["ln_f"], x, spec)
        nll, lam = _fwd_exit(params["head"], params["exit_gate"], x, labels,
                             quantize)
        nlls.append(nll.mean())
        lambdas.append(lam)
    p = exit_distribution(jnp.stack(lambdas[:-1]))
    return jnp.stack(nlls), p.mean((1, 2))
