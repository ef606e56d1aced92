"""Attention on one device.

:func:`attention` is the model's one way in. On the TPU, causal
self-attention over a whole sequence whose shapes the rule
:func:`blockwise_tile` accepts runs blockwise (:func:`blockwise_mha`:
the library's splash-attention kernels, tiles in VMEM, an online
softmax, masked tiles skipped); everything else — the CPU, odd shapes,
blocks of a longer sequence — runs :func:`mha`, the full-softmax
reference that is also the oracle for the blockwise kernel and for the
distributed ring attention (:mod:`ompi_tpu.ops.ring_attention`, which
builds on :func:`online_softmax_block`). Shapes follow
[batch, seq, heads, head_dim] throughout.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ompi_tpu.core import pvar


def mha(q, k, v, causal: bool = True, scale: Optional[float] = None,
        q_offset: int = 0, k_offset: int = 0):
    """Multi-head attention, full-softmax reference.

    q: [B, Tq, H, D], k/v: [B, Tk, H, D] -> [B, Tq, H, D].
    q_offset/k_offset give the global positions of the local blocks
    (used when blocks are slices of a longer sequence).
    """
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(d)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = q_offset + jnp.arange(q.shape[1])
        kpos = k_offset + jnp.arange(k.shape[1])
        mask = qpos[:, None] >= kpos[None, :]
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    p = jnp.exp(scores - lax.stop_gradient(
        jnp.max(scores, axis=-1, keepdims=True)))
    p = jnp.where(jnp.isfinite(scores), p, 0.0)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.maximum(denom, 1e-30)
    # bf16 operands + f32 accumulation: full MXU rate, f32 precision
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


#: Square query / key-value tiles of the blockwise kernel: the largest
#: that divides T runs (v5e, attention alone, forward + backward of one
#: layer with its layout changes, PERF.md section 6, PR 27: at B2 T2048
#: H56 D128 tiles of 1024 take 6.3 ms, 512 6.8 ms, 256 12.9 ms, att.mha
#: 37.2 ms). Inside a key-value tile the scores are made _KV_COMPUTE
#: columns at a time.
_TILES = (1024, 512, 256)
_KV_COMPUTE = 512


def blockwise_tile(backend: str, t_q: int, t_k: int, head_dim: int,
                   causal: bool = True, q_offset=0,
                   k_offset=0) -> Optional[int]:
    """The rule that sends an attention to the blockwise kernel, made
    of what the caller can observe: the tile it runs with, or None
    where it takes :func:`mha` — off the TPU, heads that are not
    multiples of the 128 lanes, a length no tile divides, anything but
    causal self-attention over one whole sequence (blocks at an offset
    of a longer one are the ring's)."""
    whole = all(isinstance(o, int) and o == 0 for o in (q_offset, k_offset))
    if (backend != "tpu" or not causal or not whole or t_q != t_k
            or head_dim % 128):
        return None
    return next((b for b in _TILES if t_q % b == 0), None)


@functools.lru_cache(maxsize=None)
def _splash_kernel(t: int, heads: int, tile: int, interpret: bool):
    """The library's splash-attention kernels for a causal [t, t] mask
    over `heads` heads of one sequence, built once per shape (not once
    per layer): forward `splash_mha_fwd_residuals`, one fused backward
    `splash_mha_dkv_no_residuals`. The mask is computed in the kernel
    from the tile's position; tiles above the diagonal are never
    visited, in any of them."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)

    compute = min(tile, _KV_COMPUTE)
    sizes = sk.BlockSizes(
        block_q=tile, block_kv=tile, block_kv_compute=compute,
        block_q_dkv=tile, block_kv_dkv=tile, block_kv_dkv_compute=compute,
        use_fused_bwd_kernel=True)
    mask = sm.MultiHeadMask([sm.CausalMask((t, t))] * heads)
    # the kernel keeps its block tables as arrays: constants of every
    # program that uses it, not values of the trace that asked first
    with jax.ensure_compile_time_eval():
        return sk.make_splash_mha_single_device(
            mask, block_sizes=sizes, interpret=interpret)


def blockwise_mha(q, k, v, tile: int, scale: Optional[float] = None,
                  interpret: bool = False):
    """Causal self-attention, :func:`mha`'s mathematics (exact softmax
    over the whole causal row, float32 scores, statistics and
    accumulation) computed tile by tile with an online softmax: the
    [B, H, T, T] scores and probabilities never reach HBM, the forward
    saves the per-row log-sum-exp and the backward recomputes each
    tile's scores from it. q, k, v: [B, T, H, D] -> [B, T, H, D].

    The kernel has no scale of its own, so q carries it: a caller that
    can fold 1/sqrt(D) in where q is still float32 passes scale=1.0 and
    nothing is rounded twice."""
    _, t, h, d = q.shape
    scale = scale if scale is not None else 1.0 / float(d) ** 0.5
    if scale != 1.0:
        q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    kernel = _splash_kernel(t, h, tile, interpret)
    o = jax.vmap(kernel)(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                         v.transpose(0, 2, 1, 3))
    return o.transpose(0, 2, 1, 3)


def attention(q, k, v, causal: bool = True, scale: Optional[float] = None,
              q_offset=0, k_offset=0):
    """The model's one way to attention: the blockwise kernel where
    :func:`blockwise_tile` gives a tile, :func:`mha` everywhere else.
    Inside ``jit`` the choice is static; it is counted once per traced
    attention (pvars ``attn_blockwise_layers`` /
    ``attn_reference_layers``)."""
    tile = blockwise_tile(jax.default_backend(), q.shape[1], k.shape[1],
                          q.shape[-1], causal, q_offset, k_offset)
    if tile is None:
        pvar.record("attn_reference_layers")
        return mha(q, k, v, causal=causal, scale=scale, q_offset=q_offset,
                   k_offset=k_offset)
    pvar.record("attn_blockwise_layers")
    return blockwise_mha(q, k, v, tile, scale=scale)


def online_softmax_block(q, k, v, o, l, m, mask=None,
                         scale: Optional[float] = None):
    """One flash-attention accumulation step over a KV block.

    Carries (all float32 regardless of activation dtype):
    o [B,Tq,H,D] numerator, l [B,H,Tq] denominator, m [B,H,Tq]
    running max. Returns updated (o, l, m).
    mask: [Tq, Tk] boolean (True = attend) or None.
    """
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(d)
    # matmul in the input dtype (MXU), softmax statistics in f32 —
    # the flash-attention convention; bf16 stats drift with seq length
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask[None, None], s, -jnp.inf)
    m_blk = jnp.max(s, axis=-1)  # [B,H,Tq]
    m_new = jnp.maximum(m, m_blk)
    # fully-masked block: keep everything finite
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)  # [B,H,Tq,Tk]
    corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
    l_new = l * corr + jnp.sum(p, axis=-1)
    o_new = (o * corr.transpose(0, 2, 1)[..., None]
             + jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                          preferred_element_type=jnp.float32))
    return o_new, l_new, m_new


def finalize_online_softmax(o, l):
    """o / l with fully-masked rows zeroed."""
    denom = l.transpose(0, 2, 1)[..., None]  # [B,Tq,H,1]
    return jnp.where(denom > 0, o / jnp.maximum(denom, 1e-30), 0.0)
