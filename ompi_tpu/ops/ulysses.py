"""Ulysses-style sequence parallelism — all-to-all context parallel.

The second canonical long-context schedule (alongside
:mod:`ompi_tpu.ops.ring_attention`): instead of rotating KV blocks
around a ring, ONE all_to_all re-shards q/k/v from sequence-sharded
[B, T/P, H, D] to head-sharded [B, T, H/P, D], every device runs full
(exact, single-pass) attention over the whole sequence for its head
subset, and a second all_to_all restores sequence sharding.

Trade-off vs ring (why both exist):
  - ulysses: 2 all_to_all launches total (q/k/v reshard as ONE
    batched collective + the output restore), exact softmax (no
    online accumulation), but requires heads % axis_size == 0 and
    peak activation memory holds the full-T attention for H/P heads.
  - ring: P ppermute hops overlapped with compute, O(T/P) memory,
    works for any head count — the choice when T is the scarce
    resource.

Reference mapping (SURVEY §2.10): the reference's building block for
this schedule is MPI_Alltoall (coll_base_alltoall.c) exactly as the
ring schedule maps to its ring/segmented collectives.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
from jax import lax

from ompi_tpu.util import jaxcompat

from ompi_tpu.ops import attention as att


def _heads_to_seq(x, axis: str):
    """Inverse reshard: [B, T, H/P, D] -> [B, T/P, H, D]."""
    return lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                          tiled=True)


def ulysses_attention(q, k, v, axis: str, causal: bool = True,
                      scale: Optional[float] = None):
    """Context-parallel attention inside ``shard_map`` via head
    resharding. q/k/v: local sequence blocks [B, T_local, H, D] in
    rank order along ``axis``; returns the local output block.

    Requires H to be divisible by the axis size (each device owns a
    whole head subset while attending over the full sequence)."""
    n = jaxcompat.axis_size(axis)
    h = q.shape[2]
    if h % n:
        raise ValueError(
            f"ulysses: {h} heads not divisible by axis size {n}; "
            "use ring_attention for this configuration")
    # one batched collective reshards q/k/v together ([3,B,T/P,H,D]:
    # split heads at dim 3, gather sequence at dim 2) — a single
    # all_to_all launch instead of three
    qkv = lax.all_to_all(jnp.stack([q, k, v]), axis, split_axis=3,
                         concat_axis=2, tiled=True)
    # exact full-sequence attention on the head subset (global
    # positions are the natural ones after the gather)
    oh = att.attention(qkv[0], qkv[1], qkv[2], causal=causal, scale=scale)
    return _heads_to_seq(oh, axis).astype(q.dtype)
