"""A Mamba-2 state-space mixer on one device, with no loop in the step.

The sixth published model of ``models/transformer.py`` (Nemotron-H's
family; reference ``benchmark/reference/nemotron_decoder.py``) has
layers that are a selective state-space model and nothing else. Per
head h of `heads` (each `head_dim` = P wide, reading group ``h //
(heads / groups)`` of the `groups` pairs B, C of `state` = N numbers)
the layer carries a state ``S`` in ``R^{P x N}`` along the sequence::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T      y_t = S_t C_t + D x_t

with ``dt = softplus(dt_raw + dt_bias) > 0`` and ``A = -exp(A_log) <
0`` one scalar a head. Around it (:func:`mixer`): one product of the
normed input into ``[z | xBC | dt]``, a causal depthwise convolution
over time and a SiLU on ``xBC`` (:func:`causal_conv`), and after it
the gate ``y * silu(z)``, an RMSNorm over each group's channels on its
own (:func:`gated_group_norm`) and the product back to the model's
width.

:func:`chunked_scan` computes the recurrence in chunks of L tokens
(the "state-space duality" form of the Mamba-2 paper, arXiv:2405.21060
section 6) as FOUR batched products and no ``while``:

1. inside a chunk, ``y[l] += sum_{s <= l} exp(a[s+1..l]) (C_l . B_s)
   dt_s x_s`` — the ``[L, L]`` causal matrix of decays times ``C B^T``,
   applied to ``dt x``;
2. each chunk's own state at its end, ``sum_s exp(a[s+1..L-1]) dt_s
   x_s B_s^T``;
3. the states carried from chunk to chunk: ONE product with the
   ``[chunks + 1, chunks]`` matrix of decays between chunk ends — what
   a ``lax.scan`` over the chunks would do one step at a time (and what
   a device trace could not see: a ``while`` event carries no op path);
   its last row is the state after the last token;
4. the carried state read out through ``C`` with the decay from the
   chunk's start.

``a = dt A`` and its cumulative sums, and every exponential, are
float32; a decay's exponent is a DIFFERENCE of cumulative sums, masked
to the causal half BEFORE the exponential (never a quotient of two
exponentials, never ``exp`` of a positive number). The products take
operands in the activations' type with float32 accumulation, but the
chunk-to-chunk product, which is float32 at the highest precision (64
x 64 a head at 8,192 tokens: nothing). Nothing of ``[T, T]`` or of a
state per TOKEN exists: the largest arrays are the decays ``[B, chunks,
H, L, L]`` and the states ``[B, chunks, H, P, N]`` in float32. The
backward pass is autodiff's of this form.

What a recomputed layer may keep (``jax.ad_checkpoint.checkpoint_name``,
chosen by ``models/transformer.py``'s rule): :data:`SSM_IN` — the first
product's result, the dearest thing the layer makes —, :data:`SSM_CONV`
— the convolved ``xBC`` — and :data:`SSM_Y` — the scan's output before
the gate.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

SSM_IN = "ssm_in"
SSM_CONV = "ssm_conv"
SSM_Y = "ssm_y"

F32 = jnp.float32


def causal_conv(xbc, w, b):
    """``silu(b + sum_j w[:, j] * xbc[t - (K - 1) + j])``: a depthwise
    convolution over time with zeros before the sequence. xbc [B, T,
    C], w [C, K], b [C] -> [B, T, C] in xbc's type, computed in
    float32 as K shifted sums."""
    t, k = xbc.shape[1], w.shape[1]
    padded = jnp.pad(xbc.astype(F32), ((0, 0), (k - 1, 0), (0, 0)))
    w = w.astype(F32)
    out = b.astype(F32) + sum(
        padded[:, j:j + t] * w[:, j] for j in range(k))
    return jax.nn.silu(out).astype(xbc.dtype)


def gated_group_norm(y, z, g, groups: int, eps: float):
    """``RMSNorm_groups(y * silu(z)) * g``: the gate first, then the
    norm over each of `groups` equal runs of channels on its own, one
    gain over all channels. y, z [B, T, C] -> [B, T, C] in y's type."""
    gated = y.astype(F32) * jax.nn.silu(z.astype(F32))
    runs = gated.reshape(*gated.shape[:-1], groups, -1)
    runs = runs * lax.rsqrt((runs * runs).mean(-1, keepdims=True) + eps)
    return (runs.reshape(gated.shape) * g.astype(F32)).astype(y.dtype)


def chunked_scan(x, dt, a, bm, cm, chunk: int):
    """The recurrence of the module docstring over whole sequences
    from a zero state, without its ``D x`` term. x [B, T, H, P]; dt
    [B, T, H] float32, positive; a [H] float32, negative; bm, cm [B,
    T, G, N] -> (y [B, T, H, P] in x's type, the state after the last
    token [B, H, P, N] float32). T is a multiple of `chunk`."""
    b, t, h, p = x.shape
    g, n = bm.shape[2], bm.shape[3]
    if t % chunk:
        raise ValueError(f"a sequence of {t} tokens is no whole number of "
                         f"chunks of {chunk}")
    nc, per, dtype = t // chunk, h // g, x.dtype
    # heads by group, the chunk's tokens innermost but for the widths:
    # [B, chunks, G, heads a group, L, ...]
    x = x.reshape(b, nc, chunk, g, per, p).transpose(0, 1, 3, 4, 2, 5)
    dt = dt.astype(F32).reshape(b, nc, chunk, g, per).transpose(
        0, 1, 3, 4, 2)
    bm = bm.reshape(b, nc, chunk, g, n).transpose(0, 1, 3, 2, 4)
    cm = cm.reshape(b, nc, chunk, g, n).transpose(0, 1, 3, 2, 4)
    # cumulative log-decay inside each chunk, this token's included
    cum = jnp.cumsum(dt * a.astype(F32).reshape(g, per, 1), axis=-1)
    total = cum[..., -1]                                     # [B, nc, G, R]

    # 1. inside a chunk
    seg = cum[..., :, None] - cum[..., None, :]              # [B,nc,G,R,L,S]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    scores = jnp.einsum("bcgln,bcgsn->bcgls", cm, bm,
                        preferred_element_type=F32)
    mixed = (scores[:, :, :, None] * decay * dt[..., None, :]).astype(dtype)
    y = jnp.einsum("bcgrls,bcgrsp->bcgrlp", mixed, x,
                   preferred_element_type=F32)

    # 2. each chunk's own state at its end
    to_end = jnp.exp(total[..., None] - cum) * dt            # [B,nc,G,R,L]
    own = jnp.einsum("bcgrlp,bcgln->bcgrpn",
                     (x.astype(F32) * to_end[..., None]).astype(dtype), bm,
                     preferred_element_type=F32)

    # 3. carried from chunk to chunk: row c of `between` weighs chunk
    # j's own state in the state ENTERING chunk c (row nc: the last)
    ends = jnp.concatenate(
        [jnp.zeros_like(total[:, :1]), jnp.cumsum(total, axis=1)], axis=1)
    ends = ends.transpose(0, 2, 3, 1)                        # [B,G,R,nc+1]
    span = ends[..., :, None] - ends[..., None, 1:]          # [B,G,R,nc+1,nc]
    earlier = jnp.tril(jnp.ones((nc + 1, nc), bool), -1)
    between = jnp.exp(jnp.where(earlier, span, -jnp.inf))
    entering = jnp.einsum("bgrcj,bjgrpn->bcgrpn", between, own,
                          precision=lax.Precision.HIGHEST)

    # 4. the carried state read out through C
    y = y + jnp.einsum("bcgln,bcgrpn->bcgrlp", cm,
                       entering[:, :-1].astype(dtype),
                       preferred_element_type=F32) * jnp.exp(cum)[..., None]
    y = y.transpose(0, 1, 4, 2, 3, 5).reshape(b, t, h, p)
    return y.astype(dtype), entering[:, -1].reshape(b, h, p, n)


def mixer(lp, x, *, heads: int, head_dim: int, groups: int, state: int,
          chunk: int, eps: float):
    """The Mamba-2 mixer of the normed x [B, T, d] -> ([B, T, d] in
    x's type, the scan's state after the last token [B, H, P, N]
    float32: a caller that drops it pays nothing for it). Leaves of
    `lp`: ``in_proj``
    [d, 2 * H * P + 2 * G * N + H] (columns ``[z | x B C | dt]``),
    ``conv_w`` [H * P + 2 * G * N, K], ``conv_b``, ``A_log``, ``D``,
    ``dt_bias`` [H], ``ssm_norm`` {"g": [H * P]}, ``out_proj`` [H * P,
    d]."""
    dt_ = x.dtype
    b, t, _ = x.shape
    inner, bc = heads * head_dim, groups * state
    with jax.named_scope("ssm_proj"):
        zxd = checkpoint_name(x @ lp["in_proj"].astype(dt_), SSM_IN)
        z, xbc, dt = (zxd[..., :inner], zxd[..., inner:2 * inner + 2 * bc],
                      zxd[..., 2 * inner + 2 * bc:])
    with jax.named_scope("ssm_conv"):
        xbc = checkpoint_name(
            causal_conv(xbc, lp["conv_w"], lp["conv_b"]), SSM_CONV)
    with jax.named_scope("ssm_scan"):
        xs = xbc[..., :inner].reshape(b, t, heads, head_dim)
        bm = xbc[..., inner:inner + bc].reshape(b, t, groups, state)
        cm = xbc[..., inner + bc:].reshape(b, t, groups, state)
        dt = jax.nn.softplus(dt.astype(F32) + lp["dt_bias"].astype(F32))
        y, last = chunked_scan(xs, dt, -jnp.exp(lp["A_log"].astype(F32)),
                               bm, cm, chunk)
        y = y.astype(F32) + lp["D"].astype(F32)[:, None] * xs.astype(F32)
        y = checkpoint_name(y.astype(dt_).reshape(b, t, inner), SSM_Y)
    with jax.named_scope("ssm_gate_norm"):
        y = gated_group_norm(y, z, lp["ssm_norm"]["g"], groups, eps)
    with jax.named_scope("ssm_proj"):
        return y @ lp["out_proj"].astype(dt_), last
