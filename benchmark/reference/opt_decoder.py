"""OPT's decoder, its next-token loss and one SGD step, written plainly.

Straightforward jax.numpy in float32 under
jax.default_matmul_precision("highest"); no kernels, no cache, nothing
imported from the program. It follows the OPT paper's decoder
(pre-LayerNorm, ReLU MLP, full multi-head causal attention, learned
positions, output head tied to the embedding) with the departures the
configuration file lists: no biases on the linear maps, no position
offset of 2, no dropout.

The step is taken layer by layer (forward keeping each layer's input,
then one vjp per layer backwards, updating that layer at once) so that
the float32 copies of one layer at a time are all that is live beside
the stored parameters; at OPT-30B's widths a whole-model float32
gradient does not fit beside them on a 16 GB chip.

What the configuration states about precision is kept, because it is
the program's contract and not its implementation: parameters are
STORED in `param_dtype`, the gradient reaches the optimizer in that
type, and the SGD update is computed in float32 and rounded back to
it. Everything else is float32.

`quantize` puts the control in the reference's place: every matmul
operand is rounded to a lower-precision type first (float8_e4m3fn is
the step below bfloat16), with a straight-through gradient.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = "highest"


def _q(x, quantize):
    """Round to `quantize` and back (straight-through), or nothing."""
    if quantize is None:
        return x
    lo = x.astype(quantize).astype(x.dtype)
    return x + jax.lax.stop_gradient(lo - x)


def layer_norm(x, g, b):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-5) * g + b


def attention(q, k, v):
    """[B,T,H,Dh] causal softmax attention."""
    t = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(q.shape[-1]))
    mask = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(mask[None, None], s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


def embed_forward(embed, pos, tokens):
    t = tokens.shape[1]
    return embed[tokens].astype(F32) + pos[:t].astype(F32)[None]


def layer_forward(lp, h, n_heads: int, quantize=None):
    lp = jax.tree.map(lambda a: a.astype(F32), lp)
    b, t, d = h.shape
    mm = lambda x, w: _q(x, quantize) @ _q(w, quantize)  # noqa: E731
    x = layer_norm(h, lp["ln1"]["g"], lp["ln1"]["b"])
    split = lambda a: a.reshape(b, t, n_heads, d // n_heads)  # noqa: E731
    q, k, v = (split(mm(x, lp[w])) for w in ("wq", "wk", "wv"))
    o = attention(_q(q, quantize), _q(k, quantize), _q(v, quantize))
    h = h + mm(o.reshape(b, t, d), lp["wo"])
    x = layer_norm(h, lp["ln2"]["g"], lp["ln2"]["b"])
    return h + mm(jnp.maximum(mm(x, lp["w1"]), 0.0), lp["w2"])


def head_loss(ln_f, embed, h, labels, quantize=None):
    """Mean next-token cross-entropy through the tied head."""
    x = layer_norm(h, ln_f["g"].astype(F32), ln_f["b"].astype(F32))
    logits = _q(x, quantize) @ _q(embed.astype(F32), quantize).T
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return (logz - gold).mean()


def logits(params, tokens, n_heads: int):
    """The whole forward pass, for tests at sizes where it fits."""
    with jax.default_matmul_precision(HIGHEST):
        h = embed_forward(params["embed"], params["pos"], tokens)
        for lp in params["layers"]:
            h = layer_forward(lp, h, n_heads)
        x = layer_norm(h, params["ln_f"]["g"].astype(F32),
                       params["ln_f"]["b"].astype(F32))
        return x @ params["embed"].astype(F32).T


def loss(params, tokens, labels, n_heads: int):
    with jax.default_matmul_precision(HIGHEST):
        h = embed_forward(params["embed"], params["pos"], tokens)
        for lp in params["layers"]:
            h = layer_forward(lp, h, n_heads)
        return head_loss(params["ln_f"], params["embed"], h, labels)


def _sgd(p, g, lr):
    """The update as the configuration states it: the gradient in the
    parameters' type, the arithmetic in float32, the result stored."""
    return (p.astype(F32) - lr * g.astype(p.dtype).astype(F32)
            ).astype(p.dtype)


_fwd_embed = jax.jit(embed_forward)


@functools.partial(jax.jit, static_argnames=("n_heads", "quantize"))
def _fwd_layer(lp, h, n_heads, quantize):
    with jax.default_matmul_precision(HIGHEST):
        return layer_forward(lp, h, n_heads, quantize)


@functools.partial(jax.jit, static_argnames=("quantize",))
def _head(ln_f, embed, h, labels, quantize):
    with jax.default_matmul_precision(HIGHEST):
        val, vjp = jax.vjp(
            lambda a, e, x: head_loss(a, e, x, labels, quantize),
            ln_f, embed, h)
        g_ln, g_embed, g_h = vjp(jnp.ones((), F32))
        return val, g_ln, g_embed.astype(F32), g_h


@functools.partial(jax.jit, static_argnames=("n_heads", "quantize"),
                   donate_argnums=(0,))
def _bwd_layer(lp, h, g_out, lr, n_heads, quantize):
    with jax.default_matmul_precision(HIGHEST):
        _, vjp = jax.vjp(
            lambda p, x: layer_forward(p, x, n_heads, quantize), lp, h)
        g_lp, g_h = vjp(g_out)
        return jax.tree.map(lambda p, g: _sgd(p, g, lr), lp, g_lp), g_h


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _bwd_embed(embed, pos, ln_f, g_ln, g_embed_head, g_h0, tokens, lr):
    _, vjp = jax.vjp(lambda e, p: embed_forward(e, p, tokens), embed, pos)
    g_embed, g_pos = vjp(g_h0)
    g_embed = g_embed.astype(F32) + g_embed_head
    return (_sgd(embed, g_embed, lr), _sgd(pos, g_pos, lr),
            jax.tree.map(lambda p, g: _sgd(p, g, lr), ln_f, g_ln))


def sgd_step(params, tokens, labels, lr: float, n_heads: int,
             quantize=None):
    """(params, loss) after one step of plain SGD on the mean loss.
    `params` is consumed (its buffers are donated layer by layer)."""
    lr = jnp.asarray(lr, F32)
    hs = [_fwd_embed(params["embed"], params["pos"], tokens)]
    for lp in params["layers"]:
        hs.append(_fwd_layer(lp, hs[-1], n_heads, quantize))
    val, g_ln, g_embed_head, g_h = _head(
        params["ln_f"], params["embed"], hs.pop(), labels, quantize)
    layers = list(params["layers"])
    for i in reversed(range(len(layers))):
        layers[i], g_h = _bwd_layer(layers[i], hs.pop(), g_h, lr,
                                    n_heads, quantize)
    embed, pos, ln_f = _bwd_embed(
        params["embed"], params["pos"], params["ln_f"], g_ln,
        g_embed_head, g_h, tokens, lr)
    return {"embed": embed, "pos": pos, "ln_f": ln_f,
            "layers": layers}, val
