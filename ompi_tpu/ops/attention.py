"""Attention on one device.

:func:`attention` is the model's one way in. It takes the causal mask,
a SEGMENT mask (``segments``: an id per position, a pair attends only
inside one id — images packed back to back in one row of patches, each
attending both ways inside itself), or both. On the TPU, self-attention
over a whole sequence runs blockwise (:func:`blockwise_mha`: tiles in
VMEM, an online softmax, no score in HBM) wherever one of two rules
accepts its shapes. Under the causal mask, :func:`blockwise_tile`: the
library's splash-attention kernels; tiles above the diagonal are never
visited — nor, under a sliding WINDOW (``window=W``: query t attends
the keys ``t - W < s <= t``, itself and the W - 1 before it; the
library's ``LocalMask`` in ``CausalMask``'s place, tiles from a list of
its own), those wholly below the window, forward and backward —; the
kernels work lanes of 128, so heads of any other width
(latent attention's 192 against values of 128) are padded with zeros
up to the next 128, which changes no score and no output. Under a
segment mask, :func:`segment_tile`: two kernels of the repo's own
(ops/segment_attention.py). The segment ids are DATA, so the (query
tile, key tile) pairs to visit are a small table made in the trace
(:func:`segment_tiles`) that the kernels walk — visited pairs first,
several heads a grid step, a pair that lies wholly between two
segments neither fetched nor computed, the ids compared only in a pair
that straddles a segment's edge (:func:`segment_interior` says which
do not) — and another packing of the same length runs the same
executable. Those kernels take heads as wide as they are (a tower's
72): the layout of VMEM pads them to its lanes, no copy in HBM does,
and the output is written at its own width. Everything else — the CPU, a length no tile divides,
blocks of a longer sequence, attention that is neither causal nor
segmented, or both — runs :func:`mha`, the full-softmax reference
(pvar ``attn_reference_layers``) that takes the same masks and is the
oracle for every kernel path and for the distributed ring attention
(:mod:`ompi_tpu.ops.ring_attention`, which builds on
:func:`online_softmax_block`). Shapes follow
[batch, seq, heads, head_dim] throughout. Counted once per traced
attention under a window that took the kernels, by the rule that picked
its tile: ``attn_window_tiles`` (the (query tile, key tile) pairs the
kernels walk, :func:`window_tiles`) and ``attn_causal_tiles`` (what the
whole triangle would be at that tile).

Learned sparse attention (DeepSeek-V3.2's DSA, as GLM-5 — the third
published model of ``models/transformer.py``, reference
``benchmark/reference/glm5_decoder.py`` — has it) is four functions of
ONE sequence ([seq, heads, head_dim]; the model maps them over the
batch): :func:`dsa_index_scores` (the indexer's [T, T] scores, its
heads summed block by block), :func:`dsa_select` (each query's top-k
keys, a mask shared by all heads), :func:`dsa_attend` (the softmax
over the selected keys alone, and the head-summed probabilities) and
:func:`dsa_kl` (the indexer's own loss). Exact, and nothing of [heads,
T, T] exists. On the TPU, where the rule :func:`dsa_tile` gives tiles,
:func:`dsa_attend` is three Pallas kernels of the repo's own
(ops/sparse_attention.py: an online softmax over (row block, key block)
pairs whose mask tile is data, the heads' summed probabilities as a
second pass, one fused backward; the key blocks above the diagonal are
never visited, no score leaves VMEM); everywhere else, and for the
indexer's scores everywhere, every causal block of scores is computed
and masked in plain ``jax.numpy`` a block of rows at a time. Neither
skips a block under the diagonal that no query selected: at a top-2048
of T 4096 with seeded weights there is none (PERF.md section 6, PR 31).
The library's splash kernels take such a mask too (a ``jax.Array``
mask, jax 0.9.0) and lost the probe on the chip. A sequence no longer
than the top-k selects nothing and takes :func:`attention`.

What a backward pass reads of an attention carries a NAME
(``jax.ad_checkpoint.checkpoint_name``: an identity wherever no
``jax.checkpoint`` policy asks for it, it lowers to its operand):
:data:`QKV` — q, k and v as the path taken reads them (the kernels'
head-major layouts on the TPU) — and :data:`ATTN_OUT` — the output and,
from a kernel, the per-row log-sum-exp, named inside the kernels' own
forward rules, so that a policy that keeps them spares the backward
pass the forward kernel; :func:`dsa_attend` names the heads' summed
probabilities :data:`DSA_PROBS` too. ``models/transformer.py`` decides
which of them a recomputed layer keeps. The segment kernels hold
both as ``[heads, T, width]``, the log-sum-exp as ``[heads, T]``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ompi_tpu.core import pvar

#: Names of what an attention's backward pass reads (the module
#: docstring's last paragraph).
QKV = "qkv"
ATTN_OUT = "attn_out"
DSA_PROBS = "dsa_probs"


def mha(q, k, v, causal: bool = True, scale: Optional[float] = None,
        q_offset: int = 0, k_offset: int = 0, segments=None,
        window: Optional[int] = None):
    """Multi-head attention, full-softmax reference.

    q: [B, Tq, H, D], k: [B, Tk, H, D], v: [B, Tk, H, Dv] ->
    [B, Tq, H, Dv]. q_offset/k_offset give the global positions of the
    local blocks (used when blocks are slices of a longer sequence).
    `segments`: [B, T] integers for self-attention (Tq == Tk): a query
    sees the keys of its own id alone. `window`: under the causal mask,
    a query at position t sees the keys at t - window < s <= t alone.
    """
    _check_window(causal, segments, window)
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(d)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = q_offset + jnp.arange(q.shape[1])
        kpos = k_offset + jnp.arange(k.shape[1])
        mask = qpos[:, None] >= kpos[None, :]
        if window:
            mask = mask & (qpos[:, None] - kpos[None, :] < window)
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    if segments is not None:
        same = segments[:, :, None] == segments[:, None, :]
        scores = jnp.where(same[:, None], scores, -jnp.inf)
    p = jnp.exp(scores - lax.stop_gradient(
        jnp.max(scores, axis=-1, keepdims=True)))
    p = jnp.where(jnp.isfinite(scores), p, 0.0)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.maximum(denom, 1e-30)
    # bf16 operands + f32 accumulation: full MXU rate, f32 precision
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _check_window(causal: bool, segments, window) -> None:
    if window is not None and (window < 1 or not causal
                               or segments is not None):
        raise ValueError(
            f"window={window!r}: a sliding window is a positive number of "
            "keys under the causal mask; with a segment mask, or without "
            "the causal one, it is not written")


#: Square query / key-value tiles of the blockwise kernel: the largest
#: that divides T runs (v5e, attention alone, forward + backward of one
#: layer with its layout changes, PERF.md section 6, PR 27: at B2 T2048
#: H56 D128 tiles of 1024 take 6.3 ms, 512 6.8 ms, 256 12.9 ms, att.mha
#: 37.2 ms). Inside a key-value tile the scores are made _KV_COMPUTE
#: columns at a time.
_TILES = (1024, 512, 256)
_KV_COMPUTE = 512


#: The kernels' lanes: a head is padded with zeros to a multiple.
LANES = 128


def lanes(width: int) -> int:
    """`width` rounded up to the kernels' lanes."""
    return -(-width // LANES) * LANES


def _whole(q_offset, k_offset) -> bool:
    return all(isinstance(o, int) and o == 0 for o in (q_offset, k_offset))


#: The tiles of a WINDOWED attention, in the order they are tried, and
#: the form of its backward pass. A tile the window's edge crosses is
#: walked whole, so a smaller tile wastes less (at T 16,384 under a
#: window of 1,024 the kernels walk 31 of the triangle's 136 tiles of
#: 1024 and 2.00 x the pairs the window keeps, 93 of 528 at 512 and
#: 1.50 x, 310 of 2,080 at 256 and 1.25 x) and runs slower a pair (PR
#: 27, above). The library's FUSED backward writes a partial dq for
#: every key tile, [T / tile, heads, T, D], and sums them: under the
#: causal mask that is the cheaper form, under a window it is T / tile
#: times the bytes for W / tile key tiles of work a row, so a windowed
#: attention takes the two-kernel backward (`splash_mha_dq`,
#: `splash_mha_dkv`; the scores are made twice and no partial exists).
#: Chosen on the chip (v5e, one core alone at B1 T16384 H32 D128 under
#: a window of 1,024, forward + backward with the layout changes,
#: PERF.md section 6, PR 43): two kernels 18.6 ms at 512 (19.6 at 1024,
#: 28.3 at 256); fused 23.7 ms at 1024, 30.7 at 512, 66.6 at 256,
#: holding 2.1 / 4.3 / 8.6 GB of partials; the whole triangle 58.6 ms
#: fused at 1024 (69.0 with two kernels).
#: The rule was asked again about a window NARROWER than every tile
#: (v5e, one core alone at B1 T8192 H64 D128 under a window of 128,
#: 1,040,448 pairs kept a head, forward + backward with the layout
#: changes, scripts/window_probe.py, PERF.md section 6, PR 50; tiles as
#: query rows x keys, the pairs walked over the pairs kept in
#: brackets): two kernels 13.76 ms at 512 x 512 (7.81), 14.62 at 256 x
#: 256 (3.97), 14.85 at 512 x 256 (5.92), 18.57 at 128 x 128 (2.00),
#: 18.58 at 256 x 128 (2.99), 19.89 at 512 x 128 (4.98); fused 19.85 /
#: 35.08 / 28.16 / 87.13 / 59.72 / 46.74 ms. A tile of 128 keys walks a
#: quarter of the pairs and runs each at less than a quarter of the
#: rate: the list stands for every window, and what the windowed cores
#: cost above their 1.04 M pairs a head is a kernel of the repo's own
#: whose edge is finer than a tile (ROADMAP S15).
_WINDOW_TILES = (512, 1024, 256)


def blockwise_tile(backend: str, t_q: int, t_k: int, head_dim: int,
                   causal: bool = True, q_offset=0, k_offset=0,
                   window: Optional[int] = None) -> Optional[int]:
    """The rule that sends a CAUSAL attention to the library's blockwise
    kernels, made of what the caller can observe: the tile it runs
    with, or None where it takes :func:`mha` — off the TPU, a length no
    tile divides, anything but self-attention over one whole sequence
    (blocks at an offset of a longer one are the ring's), or no causal
    mask (a segment mask alone is :func:`segment_tile`'s; both at once,
    packed causal documents, is ROADMAP Queue 2a). Any `head_dim`
    passes: the kernel pads it to its lanes. Under a `window` the tiles
    are `_WINDOW_TILES`', whatever its width: the chip was asked at a
    window of 1,024 and at one of 128, narrower than every tile."""
    if (backend != "tpu" or not causal or not _whole(q_offset, k_offset)
            or t_q != t_k or head_dim < 1):
        return None
    return next((b for b in (_WINDOW_TILES if window else _TILES)
                 if t_q % b == 0), None)


def window_tiles(t: int, tile, window: Optional[int] = None) -> int:
    """The tiles of a [t, t] attention that hold a pair the mask keeps
    — what the blockwise kernels walk: the triangle's, or under a
    `window` those of them whose nearest pair is fewer than `window`
    keys apart. `tile`: the square tile's side (what
    :func:`blockwise_tile` gives) or (query rows, keys) of a
    rectangular one (what scripts/window_probe.py tries beside it),
    then counted in SQUARES of its shorter side — a tile of 256 x 128
    is two —, so that the count times that side squared is the pairs
    walked whatever the tile's form."""
    rows, keys = tile if isinstance(tile, tuple) else (tile, tile)
    unit = min(rows, keys)
    walked = 0
    for first in range(0, t, rows):  # a query tile: its rows' keys
        low = max(first - window + 1, 0) if window else 0
        walked += (first + rows - 1) // keys - low // keys + 1
    return walked * (rows // unit) * (keys // unit)


def _block_sizes(tile: int, windowed: bool = False):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk)

    compute = min(tile, _KV_COMPUTE)
    if windowed:  # the two-kernel backward: `_WINDOW_TILES`' comment
        return sk.BlockSizes(
            block_q=tile, block_kv=tile, block_kv_compute=compute,
            block_q_dkv=tile, block_kv_dkv=tile, block_kv_dkv_compute=compute,
            block_q_dq=tile, block_kv_dq=tile, use_fused_bwd_kernel=False)
    return sk.BlockSizes(
        block_q=tile, block_kv=tile, block_kv_compute=compute,
        block_q_dkv=tile, block_kv_dkv=tile, block_kv_dkv_compute=compute,
        use_fused_bwd_kernel=True)


@functools.lru_cache(maxsize=None)
def _splash_kernel(t: int, heads: int, tile: int, interpret: bool,
                   window: Optional[int] = None):
    """The library's splash-attention kernels for a causal [t, t] mask
    over `heads` heads of one sequence, built once per shape (not once
    per layer): forward `splash_mha_fwd_residuals`, one fused backward
    `splash_mha_dkv_no_residuals`. The mask is computed in the kernel
    from the tile's position; tiles above the diagonal are never
    visited, in any of them — nor, under a `window`, those wholly
    below it (the library's ``LocalMask``: itself and window - 1 keys
    to the left, none to the right), and the backward is two kernels
    (`_block_sizes`)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)

    one = sm.LocalMask((t, t), (window - 1, 0), 0) if window \
        else sm.CausalMask((t, t))
    mask = sm.MultiHeadMask([one] * heads)
    # the kernel keeps its block tables as arrays: constants of every
    # program that uses it, not values of the trace that asked first
    with jax.ensure_compile_time_eval():
        return sk.make_splash_mha_single_device(
            mask, block_sizes=_block_sizes(tile, bool(window)),
            interpret=interpret,
            residual_checkpoint_name=ATTN_OUT)


def _tile_ids(ids, tile: int, keys: Optional[int]):
    """(smallest, largest) id of each query tile of `ids` [T] as
    columns, of each key tile (`keys`, or `tile`) as rows."""
    def ends(size):
        blocks = ids.reshape(-1, size)
        return blocks.min(1), blocks.max(1)

    (lo_q, hi_q), (lo_k, hi_k) = ends(tile), ends(keys or tile)
    return lo_q[:, None], hi_q[:, None], lo_k[None, :], hi_k[None, :]


def segment_tiles(ids, tile: int, keys: Optional[int] = None):
    """bool [T / tile, T / keys]: the (query tile, key tile) pairs that
    may hold a pair of one segment: their ranges of ids overlap (`keys`:
    the key tile, `tile` where not given). `ids` [T] is data: so is the
    table. Exact for ids that never decrease along the row (images back
    to back); for any other ids a superset, which costs time and
    changes nothing: inside a visited pair that is not
    :func:`segment_interior` the kernels compare the ids pair by
    pair."""
    lo_q, hi_q, lo_k, hi_k = _tile_ids(ids, tile, keys)
    return (lo_q <= hi_k) & (lo_k <= hi_q)


def segment_interior(ids, tile: int, keys: Optional[int] = None):
    """bool, as :func:`segment_tiles`: the pairs whose two tiles hold
    ONE id each, the same one — no pair inside is masked, whatever the
    ids elsewhere."""
    lo_q, hi_q, lo_k, hi_k = _tile_ids(ids, tile, keys)
    return (lo_q == hi_q) & (lo_k == hi_k) & (lo_q == lo_k)


#: The segment kernels' tiles (square: the largest that divides T) and
#: the heads a grid step works: the largest group that divides the
#: heads; the backward's is bounded besides by its dq, kept whole in
#: VMEM as [heads, T, lanes] float32 beside the block it is written
#: to, twice. Chosen on the chip (v5e, one tower block alone at T
#: 12,288, 16 heads of 72, four images, forward + backward, the ops
#: under `attn_core`, PERF.md section 6, PR 38): 10.15 ms at 1024 x
#: 1024 with 8 heads a step and 4 in the backward (10.39 with 4 and 2,
#: 10.52 with 2 and 2; 11.19-12.19 at 512 x 512, 10.94-11.01 at 1024 x
#: 512 either way) where the library's kernels take 13.27.
_SEG_TILES = (1024, 512, 256)
_SEG_HEADS = (8, 4, 2, 1)
_SEG_DQ_BYTES = 56 * 1024 * 1024


def segment_tile(backend: str, t_q: int, t_k: int, heads: int, d_qk: int,
                 d_v: int, itemsize: int = 2, causal: bool = False,
                 q_offset=0, k_offset=0):
    """The rule that sends an attention under a SEGMENT mask to the
    repo's own kernels (ops/segment_attention.py), made of what the
    call can observe: their tiles (a ``segment_attention.Tiles``), or
    None — off the TPU, with the causal mask besides, anything but
    self-attention over one whole sequence, a length no tile divides or
    whose dq does not fit in VMEM for a single head. Any width passes:
    a head lies in whole lanes of VMEM, not of HBM."""
    if (backend != "tpu" or causal or not _whole(q_offset, k_offset)
            or t_q != t_k or min(d_qk, d_v) < 1):
        return None
    tile = next((b for b in _SEG_TILES if t_q % b == 0), None)
    return None if tile is None else _segment_groups(tile, t_q, heads, d_qk,
                                                     itemsize)


def _segment_groups(tile: int, t: int, heads: int, d_qk: int, itemsize: int):
    """Square tiles of `tile` with the heads a step that fit, or None
    where one head's dq does not."""
    from ompi_tpu.ops import segment_attention as sg

    dq = t * lanes(d_qk) * (4 + 2 * itemsize)  # scratch + the block, twice

    def heads_a_step(fits):
        return next((g for g in _SEG_HEADS if heads % g == 0 and fits(g)),
                    None)

    bwd = heads_a_step(lambda g: g * dq <= _SEG_DQ_BYTES)
    return None if bwd is None else sg.Tiles(
        tile, tile, heads_a_step(lambda g: True), bwd)


@functools.lru_cache(maxsize=None)
def _segment_kernels(tiles, interpret: bool):
    """The segment kernels for one tile set as a function (q, k, v
    [H, T, .], ids [T]) -> o [H, T, Dv], the fused backward kernel behind a
    ``custom_vjp``. The tables of pairs are made from the ids in the
    trace, once for each kernel (`segment_tiles` says which pairs are
    visited, `segment_interior` which of them compare no ids). Imported
    late, as `_dsa_kernels`."""
    from ompi_tpu.ops import segment_attention as sg

    on = dict(tiles=tiles, interpret=interpret)

    def table(ids, by_key: bool):
        return sg.pair_table(segment_tiles(ids, tiles.rows, tiles.keys),
                             segment_interior(ids, tiles.rows, tiles.keys),
                             by_key)

    @jax.custom_vjp
    def attend(q, k, v, ids):
        return sg.forward(q, k, v, ids, table(ids, False), **on)[0]

    def fwd(q, k, v, ids):
        o, lse = sg.forward(q, k, v, ids, table(ids, False), **on)
        o, lse = checkpoint_name((o, lse[:, 0]), ATTN_OUT)
        return o, (q, k, v, ids, o, lse)

    def bwd(res, do):
        q, k, v, ids, o, lse = res
        di = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(-1)
        return sg.backward(q, k, v, do, lse[:, None], di[:, None], ids,
                           table(ids, True), **on) + (None,)

    attend.defvjp(fwd, bwd)
    return attend


def _pad_heads(a, width: int):
    """[..., D] -> [..., width] with zeros."""
    short = width - a.shape[-1]
    return a if not short else jnp.pad(
        a, [(0, 0)] * (a.ndim - 1) + [(0, short)])


def blockwise_mha(q, k, v, tile, scale: Optional[float] = None,
                  interpret: bool = False, segments=None,
                  window: Optional[int] = None):
    """Self-attention under the causal mask (inside a `window` of keys
    where one is given), or — where `segments`
    [B, T] is given — both ways under that segment mask and no causal
    one: :func:`mha`'s mathematics (exact softmax over the whole
    unmasked row, float32 scores, statistics and
    accumulation) computed tile by tile with an online softmax: the
    [B, H, T, T] scores and probabilities never reach HBM, the forward
    saves the per-row log-sum-exp and the backward recomputes each
    tile's scores from it. q, k: [B, T, H, D], v: [B, T, H, Dv] ->
    [B, T, H, Dv]. `tile`: what the rule gave — :func:`blockwise_tile`'s
    int (the library's kernels: D and Dv are padded with zeros to their
    lanes here and the padding cut from the result) or
    :func:`segment_tile`'s tiles (the repo's kernels: heads as wide as
    they are, nothing padded here; an int stands for square tiles of it
    with the heads a step that fit).

    The kernels have no scale of their own, so q carries it: a caller
    that can fold 1/sqrt(D) in where q is still float32 passes
    scale=1.0 and nothing is rounded twice."""
    _, t, h, d = q.shape
    dv = v.shape[-1]
    scale = scale if scale is not None else 1.0 / float(d) ** 0.5
    if scale != 1.0:
        q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    if segments is None:
        q, k, v = (_pad_heads(a, lanes(a.shape[-1])) for a in (q, k, v))
        qkv = checkpoint_name(
            tuple(a.transpose(0, 2, 1, 3) for a in (q, k, v)), QKV)
        o = jax.vmap(_splash_kernel(t, h, tile, interpret, window))(*qkv)
        return o.transpose(0, 2, 1, 3)[..., :dv]
    if isinstance(tile, int):
        tile = _segment_groups(tile, t, h, d, q.dtype.itemsize)
    qkv = checkpoint_name(
        tuple(a.transpose(0, 2, 1, 3) for a in (q, k, v)), QKV)
    attend = _segment_kernels(tile, interpret)
    # the tables are a sequence's own: one sequence at a time
    o = jnp.stack([attend(*(a[i] for a in qkv), segments[i])
                   for i in range(q.shape[0])])
    return o.transpose(0, 2, 1, 3)


def attention(q, k, v, causal: bool = True, scale: Optional[float] = None,
              q_offset=0, k_offset=0, segments=None,
              window: Optional[int] = None):
    """The model's one way to attention: the blockwise kernels where
    a rule gives tiles — :func:`segment_tile` under a segment mask,
    :func:`blockwise_tile` without one —, :func:`mha` everywhere else.
    `segments` ([B, T] integers, data) restricts every query to the
    keys of its own id; `window` (under the causal mask) every query to
    itself and the window - 1 keys before it (``attn_window_tiles`` /
    ``attn_causal_tiles`` where the kernels take it: the tiles they
    walk of the triangle's). Inside ``jit`` the choice is static; it is
    counted once per traced attention (pvars ``attn_blockwise_layers``
    / ``attn_reference_layers``; ``attn_segment_layers`` for those that
    took a segment mask, whichever way they went, and
    ``attn_segment_kernel_layers`` for those of them that took the
    repo's kernels)."""
    _check_window(causal, segments, window)
    shape = jax.default_backend(), q.shape[1], k.shape[1]
    if segments is None:
        tile = blockwise_tile(*shape, q.shape[-1], causal, q_offset, k_offset,
                              window)
        if window and tile is not None:
            pvar.record("attn_window_tiles",
                        window_tiles(q.shape[1], tile, window))
            pvar.record("attn_causal_tiles", window_tiles(q.shape[1], tile))
    else:
        pvar.record("attn_segment_layers")
        tile = segment_tile(*shape, q.shape[2], q.shape[-1], v.shape[-1],
                            q.dtype.itemsize, causal, q_offset, k_offset)
        if tile is not None:
            pvar.record("attn_segment_kernel_layers")
    if tile is None:
        pvar.record("attn_reference_layers")
        return checkpoint_name(
            mha(*checkpoint_name((q, k, v), QKV), causal=causal, scale=scale,
                q_offset=q_offset, k_offset=k_offset, segments=segments,
                window=window),
            ATTN_OUT)
    pvar.record("attn_blockwise_layers")
    return blockwise_mha(q, k, v, tile, scale=scale, segments=segments,
                         window=window)


# -- learned sparse attention: indexer scores, selection, attention over it ----

#: Query rows, and heads, worked at a time by the plain-``jax.numpy``
#: functions below — the indexer's scores everywhere, and the attention
#: where `dsa_tile` refuses (`_dsa_attend_blocks`: the CPU, odd shapes)
#: — the largest number of rows that divides T and is shorter than it:
#: what bounds their temporaries, [heads at a time, rows, keys] float32,
#: since nothing of [heads, T, T] may exist. Chosen on the chip (v5e,
#: one layer alone at T 4096, 64 heads of 256, forward + backward,
#: PERF.md section 6, PR 30): attention 35.5 ms at 512 rows x 16 heads
#: (34.6 at 256 x 16 with twice the blocks to compile, 43.1 at 1024 x
#: 16, 45.1-51.8 with 32 or 64 heads at a time); the indexer's scores
#: 6.1 ms at 256 rows (7.8 at 512, 9.9 at 1024). Plain Python blocks,
#: no loop instruction: on the chip a `while` event carries no op path
#: (its HLO does), so a trace reader counts the whole loop as unnamed
#: time — one layer with `lax.scan` over the head groups: 35.3 of its
#: 43.8 ms unnamed, and 5.7% slower than these blocks (44.1 against
#: 41.8 ms) for a third less code.
_DSA_ROWS = (512, 256, 128)
_DSA_INDEX_ROWS = (256, 128)
_DSA_HEADS = 16


def dsa_row_blocks(t: int, sizes=None):
    """[(first row, the row past the last)]: the blocks of query rows
    (`sizes`: the candidates, `_DSA_ROWS` by default). A block sees
    the keys up to its last row and no further."""
    rows = next((b for b in sizes or _DSA_ROWS if t % b == 0 and t > b), t)
    return [(a, a + rows) for a in range(0, t, rows)]


def _causal(t_q: int, t_k: int, q_first: int = 0):
    return (q_first + jnp.arange(t_q))[:, None] >= jnp.arange(t_k)[None, :]


def dsa_index_scores(qi, ki, w):
    """The indexer's score of every causal (query, key) pair of one
    sequence: ``I[t, s] = sum_j w[t, j] relu(qi[t, j] . ki[s])``.
    qi: [T, Hi, Di]; ki: [T, Di] (one key for all indexer heads); w:
    [T, Hi] float32. Returns [T, T] float32, -inf where s > t. The
    per-head scores exist for one block of rows at a time and are
    summed over the heads there; the backward pass makes them again."""
    t = qi.shape[0]

    @jax.checkpoint
    def block(qb, kb, wb):
        s = jnp.einsum("qhd,kd->hqk", qb, kb,
                       preferred_element_type=jnp.float32)
        return (jnp.maximum(s, 0.0) * wb.T[:, :, None]).sum(0)

    out = []
    for a, b in dsa_row_blocks(t, _DSA_INDEX_ROWS):
        got = jnp.where(_causal(b - a, b, a), block(qi[a:b], ki[:b], w[a:b]),
                        -jnp.inf)
        out.append(jnp.pad(got, ((0, 0), (0, t - b)),
                           constant_values=-jnp.inf))
    return jnp.concatenate(out) if len(out) > 1 else out[0]


def _kth_largest(scores, k: int):
    """Per row of float32 `scores`, the k-th largest value, exactly,
    without a sort: the floats' bits as keys in the floats' order, and
    the largest key that k of the row reach, found bit by bit (32
    passes of compare-and-count over the row: 1.4 ms for [4096, 4096]
    on a v5e where `lax.top_k`'s sort takes 8.6, PERF.md section 6, PR
    30)."""
    # one zero: -0.0 equals 0.0 as a float and must as a key
    bits = lax.bitcast_convert_type(
        jnp.where(scores == 0, 0.0, scores).astype(jnp.float32), jnp.uint32)
    top = jnp.uint32(1 << 31)
    keys = jnp.where(bits >= top, ~bits, bits | top)
    kth = jnp.zeros(scores.shape[:-1] + (1,), jnp.uint32)
    for bit in range(31, -1, -1):
        higher = kth | jnp.uint32(1 << bit)
        enough = (keys >= higher).sum(-1, keepdims=True) >= k
        kth = jnp.where(enough, higher, kth)
    return keys, kth


def dsa_select(scores, topk: int):
    """bool [T, T]: per query the `topk` causal keys of largest score —
    every causal key while there are no more than `topk` (a key whose
    score ties with the last chosen one is chosen too). Discrete: no
    gradient passes."""
    t = scores.shape[-1]
    keys, kth = _kth_largest(scores, min(topk, t))
    return (keys >= kth) & _causal(t, t)


#: The kernels' tiles. Rows and keys of a (row block, key block) pair:
#: the largest that divides T. The heads a grid step works (a pair's
#: mask tile is read once for all of them): the forward and the head
#: sum take `_DSA_GROUP_BYTES` / itemsize heads (16 bfloat16, 8 float32:
#: their blocks and accumulators are double that in float32);
#: the backward keeps a group's dq whole in VMEM, [heads, T, D] float32
#: beside the block it is written from, twice: `_DSA_DQ_BYTES` bounds
#: that group, and a sequence so long that one head does not fit takes
#: the blocks. Chosen on the chip (v5e, one layer alone at T 4096, 64
#: heads of 256, forward + backward with the layout changes, PERF.md
#: section 6, PR 31): 20.4 ms at 512 x 512 with 16 heads a step and 4
#: in the backward (20.7 with 8 and 4, 21.6 with 4 and 2; 21.8-22.1 at
#: 1024 rows or keys, 23.1 at 256 x 256) where the blocks below take
#: 45.1.
_DSA_TILES = (512, 256, 128)
_DSA_KERNEL_HEADS = (16, 8, 4, 2, 1)
_DSA_GROUP_BYTES = 32
_DSA_DQ_BYTES = 40 * 1024 * 1024


def dsa_tile(backend: str, t: int, heads: int, d_qk: int, d_v: int,
             itemsize: int = 2):
    """The rule that sends a :func:`dsa_attend` to the Pallas kernels
    (ops/sparse_attention.py), made of what the call can observe: their
    tiles (a ``sparse_attention.Tiles``), or None where it takes the
    masked blocks — off the TPU, widths that are not multiples of the
    128 lanes, a length no tile divides or whose dq does not fit in
    VMEM for a single head."""
    if backend != "tpu" or d_qk % 128 or d_v % 128:
        return None
    tile = next((b for b in _DSA_TILES if t % b == 0), None)
    dq = t * d_qk * (4 + 2 * itemsize)  # scratch + the block, twice

    def heads_a_step(fits):
        return next((g for g in _DSA_KERNEL_HEADS
                     if heads % g == 0 and fits(g)), None)

    group = heads_a_step(lambda g: g * itemsize <= _DSA_GROUP_BYTES)
    bwd = heads_a_step(lambda g: g * dq <= _DSA_DQ_BYTES)
    if tile is None or bwd is None:
        return None
    from ompi_tpu.ops import sparse_attention as sa

    return sa.Tiles(tile, tile, group, bwd)


@functools.lru_cache(maxsize=None)
def _dsa_kernels(tiles, interpret: bool):
    """`dsa_attend`'s kernel path for one tile set: (attend: q, k, v
    [H, T, .], the mask as int8 -> (o, lse), with the fused backward
    kernel behind a ``custom_vjp``; head_sum). Imported late: Pallas is
    1.3 s of Python that only a TPU run needs."""
    from ompi_tpu.ops import sparse_attention as sa

    on = dict(tiles=tiles, interpret=interpret)

    @jax.custom_vjp
    def attend(q, k, v, keep):
        return sa.forward(q, k, v, keep, **on)

    def fwd(q, k, v, keep):
        o, lse = checkpoint_name(attend(q, k, v, keep), ATTN_OUT)
        return (o, lse), (q, k, v, keep.T, o, lse)  # dsa_bwd works S^T

    def bwd(res, cts):
        q, k, v, keep_t, o, lse = res
        do = cts[0]  # lse feeds the constant probabilities alone
        di = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(-1)
        return sa.backward(q, k, v, do, lse, di[:, None, :], keep_t,
                           **on) + (None,)

    attend.defvjp(fwd, bwd)
    return attend, functools.partial(sa.head_sum, **on)


def dsa_attend(q, k, v, keep, scale: float, interpret: bool = False):
    """Softmax attention of one sequence in which query t sees the keys
    `keep[t]` and no others, the same for every head. q, k: [T, H, D];
    v: [T, H, Dv]; keep: [T, T] bool, the causal mask included. Returns
    (o [T, H, Dv] in q's type, p [T, T] float32: the heads'
    probabilities summed over the heads — a constant, for the indexer's
    loss). Exactly the softmax over the kept keys, float32 scores and
    statistics. Where :func:`dsa_tile` gives tiles (the TPU) it runs as
    the blockwise Pallas kernels of ops/sparse_attention.py — an online
    softmax, no score outside VMEM, the key blocks above the diagonal
    never visited, the backward one fused kernel — and everywhere else
    as :func:`_dsa_attend_blocks`. Inside ``jit`` the choice is static;
    it is counted once per traced call (pvars ``attn_dsa_kernel_layers``
    / ``attn_dsa_masked_layers``)."""
    t, h, d = q.shape
    same = q.dtype == k.dtype == v.dtype and q.dtype in (jnp.bfloat16,
                                                         jnp.float32)
    tiles = dsa_tile(jax.default_backend(), t, h, d, v.shape[-1],
                     q.dtype.itemsize) if same else None
    if tiles is None:
        pvar.record("attn_dsa_masked_layers")
        o, p = _dsa_attend_blocks(*checkpoint_name((q, k, v), QKV), keep,
                                  scale)
        return checkpoint_name(o, ATTN_OUT), checkpoint_name(p, DSA_PROBS)
    pvar.record("attn_dsa_kernel_layers")
    attend, head_sum = _dsa_kernels(tiles, interpret)
    # the kernels have no scale of their own: q carries it (GLM-5's is
    # 1/16: exact in any float type)
    q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    qh, kh, vh = checkpoint_name(
        tuple(a.transpose(1, 0, 2) for a in (q, k, v)), QKV)
    o, lse = attend(qh, kh, vh, keep.astype(jnp.int8))
    p = head_sum(*map(lax.stop_gradient, (qh, kh, lse)))
    # the head sum reads no mask and writes no pair above the diagonal
    return o.transpose(1, 0, 2), checkpoint_name(jnp.where(keep, p, 0.0),
                                                 DSA_PROBS)


def _dsa_attend_blocks(q, k, v, keep, scale: float):
    """:func:`dsa_attend` in plain ``jax.numpy``, what the rule falls
    back to and the kernels' oracle: every causal block of scores is
    computed and masked, `_DSA_HEADS` heads and one block of rows at a
    time (float32 `[heads, rows, keys]` blocks in HBM), and made again
    in the backward pass."""
    t, h, _ = q.shape
    step = _DSA_HEADS if h % _DSA_HEADS == 0 else h
    qh, kh, vh = (a.transpose(1, 0, 2) for a in (q, k, v))

    @jax.checkpoint
    def block(qb, kb, vb, keep_b):
        s = jnp.einsum("hqd,hkd->hqk", qb, kb,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(keep_b[None], s, -jnp.inf)
        e = jnp.exp(s - lax.stop_gradient(s.max(-1, keepdims=True)))
        p = e / e.sum(-1, keepdims=True)
        o = jnp.einsum("hqk,hkd->hqd", p.astype(vb.dtype), vb,
                       preferred_element_type=jnp.float32).astype(qb.dtype)
        return o, lax.stop_gradient(p.sum(0))

    cat = lambda xs, ax: jnp.concatenate(xs, ax) \
        if len(xs) > 1 else xs[0]  # noqa: E731
    outs, probs = [], []
    for a, b in dsa_row_blocks(t):
        got = [block(qh[g:g + step, a:b], kh[g:g + step, :b],
                     vh[g:g + step, :b], keep[a:b, :b])
               for g in range(0, h, step)]
        outs.append(cat([o for o, _ in got], 0))
        probs.append(jnp.pad(sum(p for _, p in got), ((0, 0), (0, t - b))))
    return cat(outs, 1).transpose(1, 0, 2), cat(probs, 0)


def dsa_kl(scores, keep, p_heads):
    """The indexer's loss for one sequence: ``mean_t KL(p_t ||
    softmax(scores[t] over keep[t]))``, `p_t` the main attention's
    probabilities summed over its heads (`dsa_attend`'s second result)
    and L1-normalised — a constant; the gradient reaches `scores`
    alone."""
    p = lax.stop_gradient(p_heads / p_heads.sum(-1, keepdims=True))
    logq = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
    seen = keep & (p > 0)
    logp = jnp.log(jnp.where(seen, p, 1.0))
    return jnp.where(seen, p * (logp - jnp.where(seen, logq, 0.0)),
                     0.0).sum(-1).mean()


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
