"""GLM-5's decoder, its training losses and one SGD step, written plainly.

Straightforward jax.numpy in float32 under
jax.default_matmul_precision("highest"); no kernels, no sort, no grouped
matmul, no gather in the layers (a selection is a [T, T] mask, a choice
of experts a [T, E] mask), nothing imported from the program. It
follows GLM-5 (`model_type` glm_moe_dsa, zai-org/GLM-5 `config.json`)
and the two reports its code follows — DeepSeek-V3 (arXiv:2412.19437:
latent attention, the sigmoid `noaux_tc` router, the multi-token
prediction module) and DeepSeek-V3.2 (the sparse-attention indexer and
its training) — layer by layer, `h` the residual stream, every norm an
RMSNorm (eps 1e-5, a gain, no bias) but the indexer key's LayerNorm:

- *Latent attention.* `x = norm1(h)`; `c_q = norm(x W_qa)`; `q = c_q
  W_qb`, per head `[q_nope | q_rope]`; `[c_kv | k_r] = x W_kva`, `c_kv
  = norm(c_kv)`; per head `[k_nope | v] = c_kv W_kvb`; RoPE (theta
  1e6, INTERLEAVED: dimension 2i pairs with 2i + 1) on `q_rope` and on
  the ONE `k_r` all heads share; `k = [k_nope | RoPE(k_r)]`; scores
  scaled by `(nope + rope) ** -0.5`; `h = h + concat_heads(o) W_o`.
- *The indexer.* `qI = c_q W_Iq` (heads of `index_dim`), `kI =
  LayerNorm(x W_Ik)` (one for all heads), RoPE on the first `rope`
  dimensions of each; `w = x W_Iw * (heads * index_dim) ** -0.5`;
  `I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])` for `s <= t`; `S_t`
  = the `index_topk` largest of row t (all causal keys while there are
  no more; a score equal to the last chosen one is chosen too). The
  main attention's softmax runs over `S_t` only, the same set for every
  head. Where the sequence is no longer than `index_topk` the indexer
  does not run. It reads `x` and `c_q` with their gradient stopped and
  is trained by `L_I = mean_t KL(p_t || softmax(I[t, S_t]))` alone,
  `p_t` the main attention's probabilities summed over the heads and
  L1-normalised, a constant; the main model gets no gradient through
  the selection.
- *Experts.* `x = norm2(h)`; `s = sigmoid(x W_g)` over ALL experts;
  chosen = the `top_k` largest of `s + b` (`b` a buffer without
  gradient); `g = scale * s[chosen] / sum s[chosen]`; `h = h + sum over
  the chosen experts THIS chip holds of g_e FFN_e(x) + FFN_shared(x)`,
  `FFN(x) = (silu(x W1) * (x W3)) W2`. The leading `first_dense` layers
  have one FFN of the dense width instead.
- *Multi-token prediction* (depth 1): `h' = [norm_h(h_L[i]) ;
  norm_e(Emb(t[i+1]))] W_eh`, one more expert layer, the final norm
  and head SHARED with the main model, cross-entropy on `t[i+2]` (the
  last position has none).
- loss = mean next-token cross-entropy + `mtp_weight` x the MTP's +
  `index_weight` x the mean of `L_I` over the layers that select.

Departures from the published description, all shared with the program
(benchmark/configs/glm-5.json, `assumed`): the inference code's
Hadamard rotation of `qI`, `kI` (it leaves their dot products as they
are) and fp8 quantisation of the indexer are left out; the experts
other chips hold (all but `held_count` from `held_first`, read from the
shape of `w1`) add nothing; `b` is held fixed; the weights of `L_I`
(1) and of the MTP loss (0.1) are not in the config; plain SGD.

The step is taken layer by layer (forward keeping each layer's input,
then one vjp per layer backwards, updating that layer at once), as
olmoe_decoder.py does and for its reason; attention and the indexer run
one head at a time and the experts one at a time (each made again in
the backward pass), so that the float32 copies fit one 16 GB chip at
the published widths and 4,096 positions.

Precision as the configuration states it: parameters STORED in
`param_dtype`, the gradient reaches the optimizer in that type, the SGD
update is computed in float32 and rounded back. Everything else is
float32. `quantize` puts the control in the reference's place: every
matmul operand the configuration states as bfloat16 (the projections,
the attention and indexer products, the FFNs, the head — not the
router nor the indexer's head weights, which it states as float32) is
rounded to a lower-precision type first, with a straight-through
gradient.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = "highest"
NEG = -jnp.inf


class Spec(NamedTuple):
    """What the equations need beside the weights' shapes."""
    n_heads: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_head_dim: int
    index_heads: int
    index_topk: int
    top_k: int
    held_first: int = 0
    routed_scale: float = 2.5
    rope_theta: float = 1e6
    rms_eps: float = 1e-5
    index_weight: float = 1.0
    mtp_weight: float = 0.1


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


def layer_norm(x, g, b, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(((x - mu) ** 2).mean(-1, keepdims=True)
                               + eps) * g + b


def rope_pairs(x, theta):
    """[..., T, Dr] at positions 0 .. T - 1, INTERLEAVED: (x[2i],
    x[2i+1]) turns by the angle position * theta ** (-2i / Dr)."""
    t, dr = x.shape[-2], x.shape[-1]
    freq = 1.0 / theta ** (jnp.arange(0, dr, 2, dtype=F32) / dr)
    ang = jnp.arange(t, dtype=F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape)


def causal(t):
    return jnp.tril(jnp.ones((t, t), bool))


# -- attention -----------------------------------------------------------------

def mla_project(lp, x, spec: Spec, quantize=None):
    """x [B, T, d] -> q, k [B, H, T, nope + rope], v [B, H, T, dv],
    c_q [B, T, rq]."""
    b, t, _ = x.shape
    h, nope, rope = spec.n_heads, spec.qk_nope_dim, spec.qk_rope_dim
    c_q = rms_norm(_mm(x, lp["wq_a"], quantize), lp["q_a_norm"]["g"],
                   spec.rms_eps)
    q = _mm(c_q, lp["wq_b"], quantize).reshape(b, t, h, nope + rope)
    q = jnp.moveaxis(q, 2, 1)
    kv_a = _mm(x, lp["wkv_a"], quantize)
    rkv = kv_a.shape[-1] - rope
    c_kv = rms_norm(kv_a[..., :rkv], lp["kv_a_norm"]["g"], spec.rms_eps)
    kv = _mm(c_kv, lp["wkv_b"], quantize).reshape(
        b, t, h, nope + spec.v_head_dim)
    kv = jnp.moveaxis(kv, 2, 1)
    k_r = rope_pairs(kv_a[..., rkv:], spec.rope_theta)       # [B, T, rope]
    q = jnp.concatenate(
        [q[..., :nope], rope_pairs(q[..., nope:], spec.rope_theta)], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r[:, None], (b, h, t, rope))], -1)
    return q, k, kv[..., nope:], c_q


def index_scores(lp, x, c_q, spec: Spec, quantize=None):
    """[B, T, T]: I[t, s], -inf where s > t; one indexer head at a
    time. x and c_q are read as constants."""
    x, c_q = jax.lax.stop_gradient(x), jax.lax.stop_gradient(c_q)
    b, t, _ = x.shape
    hi, rope = spec.index_heads, spec.qk_rope_dim
    qi = _mm(c_q, lp["wi_q"], quantize).reshape(b, t, hi, -1)
    di = qi.shape[-1]
    qi = jnp.moveaxis(qi, 2, 0)                              # [Hi, B, T, Di]
    ki = layer_norm(_mm(x, lp["wi_k"], quantize), lp["wi_k_norm"]["g"],
                    lp["wi_k_norm"]["b"])
    turn = lambda a: jnp.concatenate(  # noqa: E731
        [rope_pairs(a[..., :rope], spec.rope_theta), a[..., rope:]], -1)
    qi, ki = turn(qi), turn(ki)
    w = (x @ lp["wi_w"]) * (hi * di) ** -0.5                 # [B, T, Hi]

    @jax.checkpoint
    def head(total, qw):
        qh, wh = qw
        s = jnp.einsum("bqd,bkd->bqk", _q(qh, quantize), _q(ki, quantize))
        return total + wh[..., None] * jnp.maximum(s, 0.0), None

    total = jax.lax.scan(head, jnp.zeros((b, t, t), F32),
                         (qi, jnp.moveaxis(w, 2, 0)))[0]
    return jnp.where(causal(t)[None], total, NEG)


def select(scores, topk: int):
    """bool [B, T, T]: per query its `topk` best causal keys."""
    t = scores.shape[-1]
    kth = jax.lax.top_k(scores, min(topk, t))[0][..., -1:]
    return (scores >= kth) & causal(t)[None]


def attend(q, k, v, keep):
    """Softmax attention over the kept keys, one head at a time:
    (o [B, H, T, dv], the probabilities summed over the heads
    [B, T, T], a constant)."""
    scale = q.shape[-1] ** -0.5

    @jax.checkpoint
    def head(total, qkv):
        qh, kh, vh = qkv
        s = jnp.einsum("bqd,bkd->bqk", qh, kh) * scale
        p = jax.nn.softmax(jnp.where(keep, s, NEG), axis=-1)
        return total + jax.lax.stop_gradient(p), \
            jnp.einsum("bqk,bkd->bqd", p, vh)

    total, o = jax.lax.scan(
        head, jnp.zeros(q.shape[:1] + keep.shape[1:], F32),
        tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v)))
    return jnp.moveaxis(o, 0, 1), total


def index_loss(scores, keep, p_heads):
    """mean_t KL(p_t || softmax(I[t, S_t]))."""
    p = jax.lax.stop_gradient(p_heads / p_heads.sum(-1, keepdims=True))
    logq = jax.nn.log_softmax(jnp.where(keep, scores, NEG), axis=-1)
    seen = keep & (p > 0)
    term = p * (jnp.log(jnp.where(seen, p, 1.0))
                - jnp.where(seen, logq, 0.0))
    return jnp.where(seen, term, 0.0).sum(-1).mean()


def attention_block(lp, h, spec: Spec, quantize=None):
    """(h after the attention half of a block, the indexer's loss or
    0.0, the selection [B, T, T] or None); lp in float32."""
    b, t, _ = h.shape
    x = rms_norm(h, lp["ln1"]["g"], spec.rms_eps)
    q, k, v, c_q = mla_project(lp, x, spec, quantize)
    q, k, v = (_q(a, quantize) for a in (q, k, v))
    if spec.index_topk and t > spec.index_topk:
        scores = index_scores(lp, x, c_q, spec, quantize)
        keep = select(jax.lax.stop_gradient(scores), spec.index_topk)
        o, p_heads = attend(q, k, v, keep)
        loss = index_loss(scores, keep, p_heads)
    else:
        keep, loss = None, jnp.zeros((), F32)
        o, _ = attend(q, k, v, causal(t)[None])
    o = jnp.moveaxis(o, 1, 2).reshape(b, t, -1)
    return h + _mm(o, lp["wo"], quantize), loss, keep


# -- the FFNs ------------------------------------------------------------------

def ffn(x, w1, w3, w2, quantize=None):
    u = jax.nn.silu(_mm(x, w1, quantize)) * _mm(x, w3, quantize)
    return _mm(u, w2, quantize)


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


def ffn_block(lp, h, spec: Spec, quantize=None):
    b, t, d = h.shape
    x = rms_norm(h, lp["ln2"]["g"], spec.rms_eps).reshape(b * t, d)
    if "wg" not in lp:  # a leading dense layer
        return h + ffn(x, lp["w1"], lp["w3"], lp["w2"],
                       quantize).reshape(b, t, d)
    _, weights = route(x, lp["wg"], lp["wg_bias"], spec)
    held = lp["w1"].shape[0]
    weights = weights[:, spec.held_first:spec.held_first + held]
    y = experts_sum(x, weights, lp["w1"], lp["w3"], lp["w2"], quantize) \
        + ffn(x, lp["ws1"], lp["ws3"], lp["ws2"], quantize)
    return h + y.reshape(b, t, d)


def layer_forward(lp, h, spec: Spec, quantize=None):
    """(h after the block, the block's indexer loss)."""
    lp = jax.tree.map(lambda a: a.astype(F32), lp)
    h, loss, _ = attention_block(lp, h, spec, quantize)
    return ffn_block(lp, h, spec, quantize), loss


# -- the ends --------------------------------------------------------------------

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


def mtp_forward(mp, embed, ln_f, head, h, labels, spec: Spec, quantize=None):
    """(the second prediction's mean cross-entropy, its layer's
    indexer loss); position i holds h_L[i] and the embedding of its
    label, token i + 1."""
    mp = jax.tree.map(lambda a: a.astype(F32), mp)
    e = embed_forward(embed, labels)
    both = jnp.concatenate(
        [rms_norm(h, mp["hnorm"]["g"], spec.rms_eps),
         rms_norm(e, mp["enorm"]["g"], spec.rms_eps)], axis=-1)
    h2, index = layer_forward(mp, _mm(both, mp["eh_proj"], quantize), spec,
                              quantize)
    labels2, mask2 = mtp_labels(labels)
    return head_loss(ln_f, head, h2, labels2, mask2, spec, quantize), index


def _selecting(params, t: int, spec: Spec) -> int:
    """How many layers' indexers select at this length."""
    if not spec.index_topk or t <= spec.index_topk:
        return 1  # their losses are all zero: any divisor
    return len(params["layers"]) + len(params.get("mtp", ()))


def losses(params, tokens, labels, spec: Spec, quantize=None):
    """(cross-entropy, the MTP's cross-entropy or 0.0, the mean indexer
    loss), whole model at once: for tests at sizes where it fits."""
    with jax.default_matmul_precision(HIGHEST):
        h = embed_forward(params["embed"], tokens)
        index = jnp.zeros((), F32)
        for lp in params["layers"]:
            h, a = layer_forward(lp, h, spec, quantize)
            index = index + a
        ones = jnp.ones(labels.shape, F32)
        ce = head_loss(params["ln_f"], params["head"], h, labels, ones, spec,
                       quantize)
        mtp = jnp.zeros((), F32)
        for mp in params.get("mtp", ()):
            mtp, a = mtp_forward(mp, params["embed"], params["ln_f"],
                                 params["head"], h, labels, spec, quantize)
            index = index + a
        return ce, mtp, index / _selecting(params, tokens.shape[1], spec)


def loss(params, tokens, labels, spec: Spec, quantize=None):
    """The training loss (whole model at once)."""
    ce, mtp, index = losses(params, tokens, labels, spec, quantize)
    return ce + spec.mtp_weight * mtp + spec.index_weight * index


@functools.partial(jax.jit, static_argnames=("spec", "quantize"))
def selection(params, tokens, spec: Spec, quantize=None):
    """bool [B, T, T]: layer 0's selection for a batch (the sequence
    must be longer than `index_topk`)."""
    with jax.default_matmul_precision(HIGHEST):
        lp = jax.tree.map(lambda a: a.astype(F32), params["layers"][0])
        x = rms_norm(embed_forward(params["embed"], tokens), lp["ln1"]["g"],
                     spec.rms_eps)
        c_q = mla_project(lp, x, spec, quantize)[3]
        return select(index_scores(lp, x, c_q, spec, quantize),
                      spec.index_topk)


@functools.partial(jax.jit, static_argnames=("spec", "quantize"))
def chosen_experts(params, tokens, spec: Spec, quantize=None):
    """bool [T, E]: the first expert layer's chosen experts for a
    batch (tokens flattened)."""
    with jax.default_matmul_precision(HIGHEST):
        h = embed_forward(params["embed"], tokens)
        for lp in params["layers"]:
            lp = jax.tree.map(lambda a: a.astype(F32), lp)
            if "wg" in lp:
                h = attention_block(lp, h, spec, quantize)[0]
                x = rms_norm(h, lp["ln2"]["g"], spec.rms_eps)
                return route(x.reshape(-1, x.shape[-1]), lp["wg"],
                             lp["wg_bias"], spec)[0]
            h = layer_forward(lp, h, spec, quantize)[0]
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


@functools.partial(jax.jit, static_argnames=("spec", "quantize"))
def _fwd_layer(lp, h, spec, quantize):
    with jax.default_matmul_precision(HIGHEST):
        return layer_forward(lp, h, spec, quantize)


@functools.partial(jax.jit, static_argnames=("spec", "quantize"))
def _head(ln_f, head, h, labels, spec, quantize):
    """The main head's loss and its gradients (float32)."""
    with jax.default_matmul_precision(HIGHEST):
        ones = jnp.ones(labels.shape, F32)
        val, vjp = jax.vjp(
            lambda a, e, x: head_loss(a, e, x, labels, ones, spec, quantize),
            ln_f, head, h)
        return (val,) + vjp(jnp.ones((), F32))


@functools.partial(jax.jit, static_argnames=("spec", "quantize"),
                   donate_argnums=(0,))
def _mtp(mp, embed, ln_f, head, h, labels, index_weight, lr, spec, quantize):
    """The MTP module's two losses, its update, and the gradients it
    sends to the embedding, the final norm, the head and h_L (each
    already weighted)."""
    with jax.default_matmul_precision(HIGHEST):
        (ce, index), vjp = jax.vjp(
            lambda *a: mtp_forward(*a, labels, spec, quantize),
            mp, embed, ln_f, head, h)
        g_mp, g_embed, g_ln, g_head, g_h = vjp(
            (jnp.asarray(spec.mtp_weight, F32), index_weight))
        return _sgd_tree(mp, g_mp, lr), ce, index, g_embed, g_ln, g_head, g_h


@functools.partial(jax.jit, static_argnames=("spec", "quantize"),
                   donate_argnums=(0,))
def _bwd_layer(lp, h, g_out, index_weight, lr, spec, quantize):
    with jax.default_matmul_precision(HIGHEST):
        _, vjp = jax.vjp(
            lambda p, x: layer_forward(p, x, spec, quantize), lp, h)
        g_lp, g_h = vjp((g_out, index_weight))
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
    weight = jnp.asarray(
        spec.index_weight / _selecting(params, tokens.shape[1], spec), F32)
    hs, val = [_fwd_embed(params["embed"], tokens)], 0.0
    for lp in params["layers"]:
        h, index = _fwd_layer(lp, hs[-1], spec, quantize)
        hs.append(h)
        val = val + weight * index
    h_last = hs.pop()
    ce, g_ln, g_head, g_h = _head(params["ln_f"], params["head"], h_last,
                                  labels, spec, quantize)
    val = val + ce
    g_embed = jnp.zeros(params["embed"].shape, F32)
    new = {}
    if params.get("mtp"):
        (mp,) = params["mtp"]
        mp, ce2, index, e2, ln2, head2, h2 = _mtp(
            mp, params["embed"], params["ln_f"], params["head"], h_last,
            labels, weight, lr, spec, quantize)
        new["mtp"] = [mp]
        val = val + spec.mtp_weight * ce2 + weight * index
        g_embed, g_head, g_h = g_embed + e2, g_head + head2, g_h + h2
        g_ln = jax.tree.map(jnp.add, g_ln, ln2)
    del h_last
    layers = list(params["layers"])
    for i in reversed(range(len(layers))):
        layers[i], g_h = _bwd_layer(layers[i], hs.pop(), g_h, weight, lr,
                                    spec, quantize)
    embed, head, ln_f = _bwd_ends(
        params["embed"], params["head"], params["ln_f"], g_embed, g_ln,
        g_head, g_h, tokens, lr)
    return dict(new, embed=embed, head=head, ln_f=ln_f, layers=layers), val
