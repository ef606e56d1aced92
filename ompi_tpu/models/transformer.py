"""Flagship decoder-only transformer — manual-sharding SPMD training.

Parallelism is expressed through the framework's own device plane
(:mod:`ompi_tpu.parallel`), not GSPMD auto-sharding — the model IS the
demonstration that the collective library carries real workloads:

- **dp**: batch sharded; gradients all-reduced with ``psum`` (the
  MPI_Allreduce ring of BASELINE.md config #3, compiled onto ICI).
- **tp**: Megatron column/row parallel linear pairs — qkv/w1 shard the
  output feature dim, wo/w2 shard the input dim, one ``psum`` after each
  row-parallel matmul (MPI analog: Allgather/Reduce_scatter pairs,
  SURVEY.md §2.10).
- **sp**: sequence sharded; attention runs as ring attention
  (:mod:`ompi_tpu.ops.ring_attention`) — KV blocks rotate on the ICI
  ring via ppermute.
- **ep**: optional MoE layers dispatch tokens over ``all_to_all``
  (:mod:`ompi_tpu.ops.moe`), the MPI_Alltoallv expert pattern.

All axes are optional (None = that strategy off), so the same code runs
single-device (``entry()``) and on any mesh factorization. bfloat16
activations by default — MXU-native.

The model is a DESCRIPTION, read from a published config, not a fork
per model: :class:`Config` says which norm, positions, attention and
feed-forward part, how many passes and exits, whether a tower stands in
front; the defaults are OPT's (pre-LN, ReLU MLP, learned positions,
tied head). Nine published configurations run through it, each against
a float32 reference of its own under ``benchmark/reference/``: OPT,
OLMoE, GLM-5, Ouro, Kimi-VL, Nemotron-3-Nano, Mellum2, Solar-Open2,
K-EXAONE.
What the module holds:

- :class:`Config`, :class:`Axes`, `_check_supported`: what a config
  asks for that an axis cannot give yet is an error, never another
  function computed in silence.
- A LAYER is a short list of sub-layers, each ``h + [norm](mixer(
  norm(h)))`` applied by ONE function (`_sublayer`); `layer_forward`
  walks the list models/params.py's ``layout`` gives for the layer's
  kind (that module also says which layer is of which kind and
  describes the parameter tree). The block is attention then a dense
  FFN or the experts, by the layer's INDEX (``moe_every``,
  ``first_dense``) — its attention inside a sliding window or over the
  whole causal triangle, each kind with RoPE parameters of its own, or
  the delta-rule linear mixer in its place, where ``attn_layers`` mixes
  them —; a layer of a ``layer_pattern``
  is ONE mixer, by its letter (`SSM` ``M``, `EXPERTS` ``E``,
  `ATTENTION` ``*``).
- The MIXERS, six, each with what it costs the recomputation rule
  (`_COSTS`): multi-head attention (`_attention`: shared key heads, a
  head width of its own, positions learned / RoPE — YaRN's blended
  frequencies and attention factor, `Rope` — / none, QK-norm, a sliding
  window, an output gate, tp, sp); Kimi Delta Attention (ops/kda.py,
  imported only where ``attn_layers`` has a ``d``); latent attention (`_mla_attention`, with the sparse-attention
  indexer where ``index_topk`` is set); the dense FFN; the experts
  (`_experts`: sorted path, held share, shared expert, ep); the
  Mamba-2 state-space mixer (ops/ssm.py, imported only where a pattern
  has an ``M``).
- ``remat``: each layer application runs as models/remat.py's
  ``Recomputed`` and keeps what that module's rule chooses from the
  costs summed over the layout (`layer_costs`, `step_costs`);
  nothing where no memory limit is stated (the CPU).
- The trunk (`_trunk`: the embedding, a vision tower's rows at the
  image positions, ``loops`` passes over the ONE layer list), the head,
  the losses, the set-up probes and the step (`make_train_step`).

Names on the device (``jax.named_scope``: metadata, the HLO is the
same): the jitted step is module ``jit_ompi_train_step``; its ops carry
``embed``, ``layer_<i>/{ln, attn_proj, attn_core, mlp}``,
``head_loss`` (final norm, head, loss), ``grad_sync`` and
``sgd_update`` in their op path, under the ``jvp(...)`` /
``transpose(jvp(...))`` jax adds for forward and backward — so a trace
reader finds a model part by name, not by XLA's fusion numbering.
Inside ``attn_proj``: ``qk_rope`` (QK-norm and RoPE); inside ``mlp`` of
a MoE layer: ``moe_route`` (router matmul, softmax, top-k, the two
losses), ``moe_dispatch`` (sort, gather), ``moe_experts`` (grouped
matmuls, activation), ``moe_combine`` (un-sort, weighted sum),
``moe_shared`` (the shared expert); the loss's weighted mean of the
layers' router losses is ``moe_route`` alone, after ``head_loss``. A
latent-attention layer has
``attn_proj/{mla_q, mla_kv, mla_o, qk_rope, dsa_index_proj}`` and
``attn_core/{dsa_index, dsa_attend, dsa_kl}`` (the indexer's scores
and top-k; attention over the selection — on the TPU the Pallas
kernels ``dsa_fwd``, ``dsa_head_sum`` and ``dsa_bwd`` of
ops/sparse_attention.py, forward and backward both under this scope;
the indexer's loss); the
multi-token-prediction module is ``layer_<n_layers>`` with
``attn_proj/mtp_merge``, its head ``head_loss/mtp``. All of these sit
INSIDE the scopes named first. Where the layers run more than once,
pass s is ``loop_<s>`` AROUND its ``layer_<i>`` scopes, with the norm
between passes as ``loop_<s>/ln``; exit s is ``head_loss/exit_<s>``
and the gates, the exit distribution and its entropy
``head_loss/exit_gate``. A vision tower is ``embed/vision`` >
``vit_embed``, ``vit_<i>`` > {``ln``, ``attn_proj`` > ``rope2d``,
``attn_core``, ``mlp``}, ``vit_merge`` (models/vision.py): AROUND the
names above, so a reader of ``attn_core`` sums the tower's too and one
of ``vision`` tells them apart. A layer of a pattern is
``layer_<i>/{ln, ssm}`` with ``ssm/{ssm_proj, ssm_conv, ssm_scan,
ssm_gate_norm}`` (``ssm_proj``: both products), ``layer_<i>/{ln,
attn_proj, attn_core}`` or ``layer_<i>/{ln, mlp}`` with the ``moe_*``
names above. A sub-layer's residual add lies under the scopes its
layout row names (the block's under its mixer's; a pattern's experts'
under ``mlp``, its other two mixers' at ``layer_<i>``'s own level).
Counted once per traced layer: ``ssm_layers``,
``ssm_chunks`` (chunks a layer), ``attn_gqa_layers`` and, by
``ops/ssm.mixer`` for the form its scan took, ``ssm_scan_kernel_layers``
/ ``ssm_scan_product_layers`` (and by ``ops/ssm.causal_conv``, for this
mixer's convolution and a delta-rule layer's three, once per traced
call: ``conv_kernel_layers`` / ``conv_shifted_layers``); the probe
:func:`ssm_probe` counts ``ssm_state_norm_micro``. Where a config mixes kinds of attention
(``attn_layers``) — and nowhere else, so every other op path stands —
the scores, softmax and values lie one scope further in:
``attn_core/attn_window`` on a layer under the sliding window,
``attn_core/attn_full`` on a full one (YaRN's table and the rotation
stay under ``attn_proj/qk_rope``); counted once per traced layer there:
``attn_window_layers`` / ``attn_full_layers``, and by
``ops/attention.attention`` for a windowed attention that took the
kernels ``attn_window_tiles`` / ``attn_causal_tiles``. A kind may take
no rotation while the other rotates (`NO_ROPE`: ``attn_unrotated_layers``),
q and k may be normed per head (`PER_HEAD`: ``attn_head_norm_layers``),
and the multi-token-prediction module of such a config is a layer of
the kind ``mtp_attn`` names — its core under ``layer_<n_layers>/
attn_core/attn_full`` or ``attn_window``, counted ``mtp_full_layers`` /
``mtp_window_layers``. A delta-rule
layer of such a config (``d``) is ``layer_<i>/{ln, kda, mlp}`` with
``kda/{kda_proj, kda_conv, kda_core, kda_gate_norm}`` (``kda_proj``:
the q, k, v, decay, beta and gate products, the output product and the
residual add; ``kda_core``: the norms of q and k, the decays, the
intra-chunk system, the carry and the outputs — on the TPU all but
the decays in the Pallas kernels ``kda_delta_fwd`` / ``kda_delta_bwd``
of ops/kda.py); a gated attention's gate is ``attn_proj/attn_gate``.
Counted once per traced layer: ``kda_layers``, ``kda_chunks``,
``attn_gated_layers`` and, by ``ops/kda.mixer`` for the form its core
took, ``kda_carry_scan_layers``, or ``kda_carry_kernel_layers`` and
``kda_core_kernel_layers``; the probe
:func:`kda_probe` counts ``kda_state_norm_micro``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ompi_tpu.core import pvar
from ompi_tpu.models import remat, vision
from ompi_tpu.models.params import (  # noqa: F401 (the model's own names)
    ATTENTION, DELTA, EXPERTS, FULL, PER_HEAD, SSM, WINDOWED, Block,
    _check_attn_layers, _check_indexer, _check_pattern, _is_moe, _layer_kind,
    _mtp_kind, grad_extra_axes, init_params, layout, param_specs)
from ompi_tpu.models.remat import (  # noqa: F401
    ATTN_PROJ_OUT, DSA_SELECT, MLA_LATENTS, MLP_OUT, MLP_UP, REMAT_SHARE)
from ompi_tpu.ops import attention as att
from ompi_tpu.ops import moe as moe_mod
from ompi_tpu.ops.ring_attention import ring_attention
from ompi_tpu.parallel.collectives import region_enter, region_exit


@dataclasses.dataclass(frozen=True)
class Rope:
    """RoPE's parameters for one kind of attention layer (an entry of
    the source's ``rope_parameters``): the base, and YaRN's (Peng et
    al., arXiv:2309.00071) where `factor` is not 1 — the pairs that
    turn more than `beta_fast` times over the `original_max` positions
    the model was first trained on keep their frequency, those that
    turn less than `beta_slow` times are slowed `factor` times, a
    straight line over the pair's index between; cos and sin are both
    multiplied by `attention_factor`, so a layer's scores carry its
    square."""
    theta: float = 10000.0
    factor: float = 1.0
    original_max: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


#: `Config.rope_full` / `Config.rope_window` where that kind of
#: attention layer takes NO rotation while the other kind rotates
NO_ROPE = "none"


@dataclasses.dataclass(frozen=True)
class Config:
    vocab: int = 256
    d_model: int = 128
    n_layers: int = 2
    n_heads: int = 4
    d_ff: int = 512
    max_seq: int = 1024
    moe_every: int = 0       # every k-th layer is MoE (0 = dense only)
    n_experts: int = 8
    #: experts per token (the source's num_experts_per_tok); their
    #: weights are the softmax probabilities as they are unless
    #: norm_topk_prob
    top_k: int = 1
    norm_topk_prob: bool = False
    #: slots per expert = capacity_factor * tokens / experts — the
    #: expert-parallel path only (ops.moe.moe_ffn drops the overflow);
    #: without an ep axis no token is ever dropped
    capacity_factor: float = 1.25
    #: FFN and expert activation ("relu", "silu", "gelu"), and whether
    #: it gates a second projection w3: act(x W1) * (x W3)
    mlp_act: str = "relu"
    mlp_gated: bool = False
    #: "layernorm" (gain and bias) or "rmsnorm" (gain only)
    norm: str = "layernorm"
    norm_eps: float = 1e-5
    #: "learned" (a table of max_seq rows, params["pos"]), "rope"
    #: (rotate-half pairing of dimensions i and i + head_dim / 2) or
    #: "none" (no table, no rotation)
    pos: str = "learned"
    rope_theta: float = 10000.0
    #: RMSNorm of q and k in front of the rotation. True: over the
    #: WHOLE projection (width d_model), before the split into heads
    #: (OLMoE's; gains of the projections' widths). `PER_HEAD` "head":
    #: over each head alone, after the split (gains q_norm / k_norm of
    #: [head_dim], one for every head of q, one for every head of k).
    #: False: none
    qk_norm: Any = False
    #: the head is the embedding's transpose, or params["head"]
    tie_head: bool = True
    #: weights of the router's load-balancing loss E * sum_e f_e P_e
    #: and z-loss mean(logsumexp(logits)^2), averaged over MoE layers
    #: and added to the mean next-token loss
    router_aux_weight: float = 0.0
    router_z_weight: float = 0.0
    dtype: Any = jnp.bfloat16
    #: parameter STORAGE dtype: float32 (default — full-precision
    #: master weights) or bfloat16 (halves weight HBM traffic per
    #: step; bench-style max-throughput training. The SGD update
    #: runs in the storage dtype.)
    param_dtype: Any = np.float32
    #: context-parallel schedule under sp: "ring" (KV rotation,
    #: O(T/P) memory) or "ulysses" (head-resharding all_to_alls,
    #: exact single-pass softmax; needs local heads % sp size == 0)
    sp_schedule: str = "ring"
    #: layers 0 .. first_dense - 1 have a dense FFN and every later
    #: one a mixture of experts (the source's first_k_dense_replace);
    #: None: `moe_every` says which layers are which
    first_dense: Optional[int] = None
    #: an expert's width where it is not the dense layers' d_ff (0)
    moe_d_ff: int = 0
    #: "softmax" (the k largest probabilities of a softmax over all
    #: experts) or "sigmoid" (ops.moe.sigmoid_routing: the k largest
    #: sigmoid scores, chosen with the bias params[..]["wg_bias"] where
    #: `router_bias`, renormalised per `norm_topk_prob`, times
    #: `routed_scale`)
    router_score: str = "softmax"
    router_bias: bool = False
    routed_scale: float = 1.0
    #: experts every token passes through beside the routed ones: ONE
    #: gated FFN of width n_shared_experts * expert width
    n_shared_experts: int = 0
    #: (first, count): the experts of every MoE layer THIS chip holds
    #: (its w1 / w3 / w2 carry `count` experts); the router still
    #: scores all `n_experts` and what the absent ones would add is
    #: left out. None: all of them
    held_experts: Optional[Tuple[int, int]] = None
    #: "mha" (q, k, v of n_heads equal heads) or "mla" (latent
    #: attention: low-rank q and kv paths with their own RMSNorms, per
    #: head a no-position part of qk_nope_dim and a RoPE part of
    #: qk_rope_dim whose key is ONE for all heads, values of v_head_dim)
    attn: str = "mha"
    #: the query latent's width; 0: no query latent, q is ONE product
    #: of x (leaf wq [d_model, n_heads x (nope + rope)])
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    #: RoPE pairs dimensions 2i and 2i + 1 (the MLA and indexer parts;
    #: pos="rope" on whole heads stays rotate-half)
    rope_interleave: bool = False
    #: the learned sparse-attention indexer (DSA) of an "mla" layer:
    #: index_heads heads of index_dim score every causal key, each
    #: query attends to its index_topk best (0: no indexer). A sequence
    #: no longer than index_topk selects nothing: the indexer does not
    #: run and attention is the causal one. Its loss (ops.attention.
    #: dsa_kl, averaged over the layers that select) is added to the
    #: mean next-token loss at index_loss_weight
    index_heads: int = 0
    index_dim: int = 0
    index_topk: int = 0
    index_loss_weight: float = 1.0
    #: multi-token-prediction modules after the last layer
    #: (params["mtp"]; DeepSeek-V3's, depth 1 supported) and the weight
    #: of their loss; where `attn_layers` mixes kinds of attention, the
    #: kind of the module's own (the source's mtp_layer_types: `FULL`
    #: or `WINDOWED`, whatever the trunk's last layer is; with it the
    #: kind's RoPE parameters, or none)
    mtp_layers: int = 0
    mtp_weight: float = 0.0
    mtp_attn: Optional[str] = None
    #: recompute each layer application in the backward pass: from its
    #: input and the named values `remat_keep` chooses for this trace's
    #: shapes and the device's memory limit (`remat_order`,
    #: REMAT_SHARE); from its input alone where the device states no
    #: limit (the CPU) or has no room
    remat: bool = False
    #: passes over the layer list, every pass with the same weights;
    #: the final norm (params["ln_f"]) is applied after EVERY pass, so
    #: the next one starts from the normed state
    loops: int = 1
    #: a second norm on each sub-layer's OUTPUT, before it joins the
    #: residual stream (leaves ln1_post, ln2_post)
    post_norm: bool = False
    #: an exit after every pass: the shared head's loss there, and a
    #: gate sigmoid(h . w + b) (params["exit_gate"]) that says how much
    #: of what has not left yet leaves; the loss is the expected loss
    #: over the exits less exit_entropy_weight x the entropy of that
    #: exit distribution, per token
    exit_gate: bool = False
    exit_entropy_weight: float = 0.0
    #: a vision tower in front of the decoder (a models/vision.py
    #: VisionConfig; None: none): a batch is then a dict — the ids
    #: under "tokens" and the packed images' leaves beside them — and
    #: the tower's merged rows replace the embedding's at the image
    #: positions (params["vision"]). Its norms are LayerNorms with gain
    #: and bias and every product of it has a bias, whatever the
    #: decoder's `norm` says; the decoder's products have none
    vision: Any = None
    #: one letter a layer (the source's hybrid_override_pattern): a
    #: layer is ONE pre-norm and ONE mixer, h + mixer(norm(h)) — `SSM`
    #: "M" a Mamba-2 state-space mixer, `EXPERTS` "E" the mixture of
    #: experts (with its shared expert), `ATTENTION` "*" attention.
    #: None: every layer is attention THEN a feed-forward part
    layer_pattern: Optional[str] = None
    #: a head's width where it is not d_model / n_heads (0), and the
    #: key / value heads where n_heads query heads share fewer (0: as
    #: many): query head i attends with key head i // (n_heads /
    #: n_kv_heads)
    head_width: int = 0
    n_kv_heads: int = 0
    #: the shared expert's width where it is not n_shared_experts x
    #: the experts' (0)
    shared_d_ff: int = 0
    #: a Mamba-2 mixer's sizes (ops/ssm.py): heads of ssm_head_dim,
    #: groups of B and C of ssm_state numbers each, the convolution's
    #: taps, the tokens of a chunk of the scan
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_chunk: int = 128
    #: kinds of attention mixed by layer (the source's layer_types):
    #: one letter a layer — `WINDOWED` "w": query t attends the keys
    #: t - attn_window < s <= t, itself and the attn_window - 1 before
    #: it; `FULL` "f": every s <= t. None: every layer is full
    attn_layers: Optional[str] = None
    attn_window: int = 0
    #: RoPE's parameters where they are more than `rope_theta` says, by
    #: the layer's kind of attention (a `Rope`: a base of its own,
    #: YaRN): the full layers' — every layer's where `attn_layers` is
    #: None — and the windowed ones'. None: `rope_theta`, unscaled.
    #: `NO_ROPE`: the layers of that kind take no rotation at all while
    #: the other kind's do (`pos` stays "rope"; where no layer rotates
    #: `pos` is "none")
    rope_full: Any = None
    rope_window: Any = None
    #: the delta-rule linear mixer of the layers `attn_layers` marks
    #: `DELTA` "d" (Kimi Delta Attention, ops/kda.py): heads of
    #: kda_head_dim channels for keys and values alike, the taps of the
    #: three short convolutions, the tokens of a chunk, and the rank of
    #: the decay's and the output gate's two-step projections (0: a
    #: head's width)
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 4
    kda_chunk: int = 64
    kda_rank: int = 0
    #: multi-head attention's output is gated before its last product:
    #: o * sigmoid(x W_a), one number a head and channel from the same
    #: normed x (leaf wa [d_model, n_heads x head_dim])
    attn_gate: bool = False

    @property
    def head_dim(self) -> int:
        return self.head_width or self.d_model // self.n_heads

    @property
    def shared_width(self) -> int:
        return self.shared_d_ff or self.n_shared_experts * self.expert_d_ff

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_width(self) -> int:
        """The channels the convolution runs over: x, B and C."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff


@dataclasses.dataclass(frozen=True)
class Axes:
    """Mesh axis names per strategy; None disables the strategy."""
    dp: Optional[str] = None
    tp: Optional[str] = None
    sp: Optional[str] = None
    ep: Optional[str] = None
    pp: Optional[str] = None  # pipeline stages (models/pipeline.py)

    def batch_axes(self):
        """Axes over which the *tokens* are sharded (dp, sp, and ep —
        expert parallelism reuses a data axis, the standard layout).
        Grads of params replicated over these axes are psummed over
        them; the tp axis is handled by the region_enter/exit AD
        boundary instead (Megatron f/g), never by grad psum."""
        return tuple(a for a in (self.dp, self.sp, self.ep) if a)


def _ln(x, g, b):
    with jax.named_scope("ln"):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) * lax.rsqrt(var + 1e-5) * g + b


def _rms(x, g, eps: float):
    with jax.named_scope("ln"):
        return x * lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _norm(x, p, cfg: Config):
    """The config's norm of a float32 x: LayerNorm (gain and bias) or
    RMSNorm (gain only)."""
    if cfg.norm == "layernorm":
        return _ln(x, p["g"], p["b"])
    if cfg.norm == "rmsnorm":
        return _rms(x, p["g"], cfg.norm_eps)
    raise ValueError(f"norm={cfg.norm!r}: expected 'layernorm' or "
                     "'rmsnorm'")


def yarn_range(half: int, rp: Rope) -> Tuple[int, int]:
    """(low, high): the pairs 0 .. low keep their frequency under
    YaRN, the pairs from high on are slowed `rp.factor` times — the
    pair's index at which a head of 2 x `half` dimensions turns
    `beta_fast` / `beta_slow` times over `original_max` positions,
    rounded outwards and held inside the head."""
    def pair(turns: float) -> float:
        return 2 * half * math.log(rp.original_max / (turns * 2 * math.pi)) \
            / (2 * math.log(rp.theta))

    return (max(math.floor(pair(rp.beta_fast)), 0),
            min(math.ceil(pair(rp.beta_slow)), 2 * half - 1))


def rope_frequencies(half: int, rp: Rope):
    """float32 [half]: the angle a position adds to pair i,
    ``theta^(-i / half)`` — under YaRN blended with that divided by
    `rp.factor` along `yarn_range`'s line."""
    pairs = jnp.arange(half, dtype=jnp.float32)
    inv_freq = rp.theta ** (-pairs / half)
    if rp.factor == 1.0:
        return inv_freq
    low, high = yarn_range(half, rp)
    ramp = jnp.clip((pairs - low) / (max(high, low + 0.001) - low), 0.0, 1.0)
    return inv_freq * (1.0 - ramp) + inv_freq / rp.factor * ramp


def rope(x, positions, theta):
    """Rotary positions on x [B, T, H, Dh] at integer `positions` [T]:
    the rotate-half pairing (dimension i with i + Dh/2), computed in
    float32, returned in x's type. `theta`: the base, or a `Rope`
    (its frequencies, cos and sin times its attention factor)."""
    half = x.shape[-1] // 2
    scaled = isinstance(theta, Rope)
    inv_freq = rope_frequencies(half, theta) if scaled \
        else theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    if scaled and theta.attention_factor != 1.0:
        cos, sin = (a * theta.attention_factor for a in (cos, sin))
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def rope_interleaved(x, positions, theta: float):
    """Rotary positions on x [B, T, H, Dr] at integer `positions` [T]
    with the INTERLEAVED pairing (dimension 2i with 2i + 1, frequency
    theta^(-2i / Dr)), computed in float32, returned in x's type and
    x's layout."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], half, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape).astype(x.dtype)


def _check_supported(cfg: Config, ax: Axes, is_moe, pos_offset, t=None):
    """What the config may ask for that an axis cannot give yet is an
    error, never another function computed in silence. `t`: the tokens
    of a sequence on this shard, where the caller has them."""
    is_moe = getattr(is_moe, "moe", is_moe)  # a `Block`'s
    if cfg.pos not in ("learned", "rope", "none"):
        raise ValueError(f"pos={cfg.pos!r}: expected 'learned', 'rope' or "
                         "'none'")
    if cfg.layer_pattern is not None:
        _check_pattern(cfg)
        for axis, missing in (
                (ax.tp, "tensor parallelism (ax.tp): a Mamba-2 mixer's "
                 "heads and groups, the shared key heads and the widths "
                 "of a pattern's layers are not split by columns yet"),
                (ax.sp, "sequence parallelism (ax.sp): the scan's carried "
                 "state and the convolution's last taps would cross "
                 "chips, and attention without positions has no ring "
                 "path for shared key heads yet"),
                (ax.ep, "expert parallelism (ax.ep): a pattern's expert "
                 "layers take the sorted path with a chip's held share; "
                 "the exchange in the middle of the sort is ROADMAP R1b"),
                (ax.pp, "pipeline parallelism (ax.pp): the stages of a "
                 "pattern are unlike (models/pipeline.py scans equal "
                 "ones; ROADMAP R3)")):
            if axis:
                raise NotImplementedError(
                    "a layer pattern (Config.layer_pattern) under "
                    + missing)
        if cfg.post_norm or cfg.attn != "mha":
            raise NotImplementedError(
                "a layer pattern (Config.layer_pattern) with a norm on a "
                "mixer's output or latent attention: a pattern's layer is "
                "one pre-norm and one mixer, its attention multi-head")
        if SSM in cfg.layer_pattern:
            if cfg.ssm_heads % cfg.ssm_groups:
                raise ValueError(
                    f"ssm_heads={cfg.ssm_heads} is no multiple of "
                    f"ssm_groups={cfg.ssm_groups}")
            if t is not None and t % cfg.ssm_chunk:
                raise NotImplementedError(
                    f"a sequence of {t} tokens is no whole number of the "
                    f"scan's chunks (ssm_chunk={cfg.ssm_chunk}): a last "
                    "chunk padded with tokens that change no state is "
                    "not written")
    if cfg.attn_layers is not None:
        _check_attn_layers(cfg)
        if DELTA in cfg.attn_layers:
            for axis, missing in (
                    (ax.tp, "tensor parallelism (ax.tp): the mixer's heads, "
                     "its convolutions' channels and the decay's bottleneck "
                     "are not split by columns yet"),
                    (ax.ep, "expert parallelism (ax.ep): the tokens an ep "
                     "axis shards are a sequence's, whose carried state "
                     "would cross chips")):
                if axis:
                    raise NotImplementedError(
                        "a delta-rule mixer (Config.attn_layers 'd') under "
                        + missing)
            if t is not None and t % cfg.kda_chunk:
                raise NotImplementedError(
                    f"a sequence of {t} tokens is no whole number of the "
                    f"delta rule's chunks (kda_chunk={cfg.kda_chunk}): a "
                    "last chunk padded with tokens that change no state "
                    "is not written")
        for on, missing in (
                (ax.sp, "sequence parallelism (ax.sp): the ring and the "
                 "Ulysses schedules take causal=True and nothing else; a "
                 "window that skips the blocks no query of a shard "
                 "reaches — and a delta-rule mixer's carried state and "
                 "last taps across chips — are ROADMAP Queue 2a"),
                (ax.pp, "pipeline parallelism (ax.pp): layers of two "
                 "kinds do not stack into equal stages "
                 "(models/pipeline.py scans equal ones; ROADMAP R3)"),
                (cfg.attn == "mla", "latent attention (attn='mla'): its "
                 "core and its sparse-attention indexer take no window"),
                (cfg.layer_pattern is not None, "a layer pattern "
                 "(Config.layer_pattern): a pattern's attention layers "
                 "are told apart by no letter yet")):
            if on:
                raise NotImplementedError(
                    "kinds of attention mixed by layer (Config.attn_layers) "
                    "under " + missing)
    elif cfg.attn_window or cfg.rope_window is not None \
            or cfg.mtp_attn is not None:
        raise ValueError(
            f"attn_window={cfg.attn_window}, rope_window="
            f"{cfg.rope_window!r}, mtp_attn={cfg.mtp_attn!r}: which layers "
            "are under the window is for Config.attn_layers to say, and "
            "it is None")
    if NO_ROPE in (cfg.rope_full, cfg.rope_window) and (
            cfg.pos != "rope" or cfg.attn_layers is None
            or cfg.rope_full == cfg.rope_window):
        raise ValueError(
            f"rope_full={cfg.rope_full!r}, rope_window={cfg.rope_window!r}: "
            "NO_ROPE takes the rotation from ONE kind of attention layer "
            "of a config that mixes two (Config.attn_layers) and rotates "
            "the other (pos='rope'); a config no layer of which rotates "
            "says pos='none'")
    if cfg.qk_norm not in (False, True, PER_HEAD):
        raise ValueError(f"qk_norm={cfg.qk_norm!r}: expected False, True "
                         f"(over the whole projection) or {PER_HEAD!r}")
    if cfg.n_heads % (cfg.n_kv_heads or cfg.n_heads):
        raise ValueError(f"n_heads={cfg.n_heads} is no multiple of "
                         f"n_kv_heads={cfg.n_kv_heads}")
    if cfg.vision is not None and (ax.tp or ax.sp or ax.pp):
        raise NotImplementedError(
            "a vision tower (Config.vision) under tensor, sequence or "
            "pipeline parallelism (ax.tp, ax.sp, ax.pp): the tower is "
            "replicated and runs whole before the first layer; which "
            "stage owns it, its column split and a packed row of "
            "patches over a sharded sequence are ROADMAP Queue 2a")
    if cfg.attn == "mla" and (ax.tp or ax.sp):
        raise NotImplementedError(
            "latent attention (attn='mla') and its sparse-attention "
            "mask under tensor or sequence parallelism (ax.tp, ax.sp): "
            "the low-rank projections' column split, the shared RoPE "
            "key and a [T, T] selection over a sharded sequence are "
            "not written yet")
    if cfg.attn not in ("mha", "mla"):
        raise ValueError(f"attn={cfg.attn!r}: expected 'mha' or 'mla'")
    _check_indexer(cfg)
    if is_moe and cfg.held_experts and ax.ep:
        raise NotImplementedError(
            "held experts (held_experts) describe ONE chip's share of "
            "a layer; over an expert-parallel axis (ax.ep) the share "
            "comes from the exchange in the middle of the sort, which "
            "is ROADMAP R1b")
    if (cfg.loops > 1 or cfg.exit_gate) and ax.pp:
        raise NotImplementedError(
            "a layer stack run more than once (loops > 1) and exits "
            "after each pass (exit_gate) under pipeline parallelism "
            "(ax.pp): the last stage would feed the first and every "
            "pass would need the head; a repeated stack under pp is "
            "ROADMAP Queue 2a (models/pipeline.py)")
    if cfg.mtp_layers and ax.pp:
        raise NotImplementedError(
            "multi-token prediction under pipeline parallelism "
            "(ax.pp): the last stage would need the embedding and a "
            "second head; unequal pipeline stages are ROADMAP R3")
    if is_moe and ax.ep and (cfg.top_k != 1 or cfg.mlp_gated
                             or cfg.mlp_act != "relu"):
        raise NotImplementedError(
            "expert parallelism (ax.ep) runs capacity-based top-1 ReLU "
            "experts only; top_k > 1 and gated experts over an ep axis "
            "are ROADMAP R1b (the sorted dispatch with an exchange in "
            "the middle)")
    if cfg.pos == "rope" and ax.sp and pos_offset is None:
        raise NotImplementedError(
            "RoPE under sequence parallelism (ax.sp) needs the shard's "
            "position offset, which this caller does not pass "
            "(models/pipeline.py; ROADMAP R1b)")
    if cfg.qk_norm and ax.tp:
        raise NotImplementedError(
            "QK-norm over the whole projection, which tensor parallelism "
            "(ax.tp) shards by columns: its sum of squares over the tp "
            "axis is not written yet" if cfg.qk_norm != PER_HEAD else
            "QK-norm per head (qk_norm='head') under tensor parallelism "
            "(ax.tp): a head's norm needs no sum across the axis, but the "
            "two gains are replicated INSIDE the tp region and their "
            "gradient arrives partial, one shard's heads a chip; the "
            "psum over tp (as the router's, grad_extra_axes) is not "
            "written yet (ROADMAP Queue 2a)")


def _moe_sorted(flat, lp, cfg: Config, aux):
    """A MoE layer's FFN on one device: route in float32, then the
    drop-free sorted path. `aux` collects this layer's (load-balancing
    loss, z-loss, routing) where the caller wants them."""
    dt = flat.dtype
    with jax.named_scope("moe_route"):
        logits = jnp.dot(flat.astype(jnp.float32),
                         lp["wg"].astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
        if cfg.router_score == "sigmoid":
            route = moe_mod.sigmoid_routing(
                logits, lp["wg_bias"] if cfg.router_bias else None,
                cfg.top_k, cfg.norm_topk_prob, cfg.routed_scale)
        elif cfg.router_score == "softmax":
            route = moe_mod.topk_routing(logits, cfg.top_k,
                                         cfg.norm_topk_prob)
        else:
            raise ValueError(f"router_score={cfg.router_score!r}: "
                             "expected 'softmax' or 'sigmoid'")
        if aux is not None:
            aux.append((moe_mod.load_balance_loss(route),
                        moe_mod.router_z_loss(route), route))
    w3 = lp["w3"].astype(dt) if cfg.mlp_gated else None
    bound = None  # every expert is here: all the rows
    if cfg.held_experts is not None:
        with jax.named_scope("moe_dispatch"):
            route = moe_mod.held_share(route, *cfg.held_experts)
        bound = moe_mod.held_rows_bound(
            flat.shape[0], cfg.top_k, cfg.held_experts[1], cfg.n_experts)
    return moe_mod.sorted_moe_ffn(
        flat, route, lp["w1"].astype(dt), w3, lp["w2"].astype(dt),
        cfg.mlp_act, bound)


def _ffn(x, w1, w3, w2, cfg: Config):
    """act(x W1) [* (x W3)] W2 in x's type."""
    u = moe_mod.activation(cfg.mlp_act)(
        checkpoint_name(x @ w1.astype(x.dtype), MLP_UP))
    if w3 is not None:
        u = u * checkpoint_name(x @ w3.astype(x.dtype), MLP_UP)
    return u @ w2.astype(x.dtype)


def _mla_project(lp, x, cfg: Config, positions):
    """Latent attention's projections of the normed x [B, T, d]: (q, k
    [B, T, H, nope + rope], v [B, T, H, v_head_dim], c_q [B, T,
    q_lora_rank]: the normed query latent, which the indexer reads
    too). RoPE (interleaved or rotate-half per the config) turns the
    rope part of q and the ONE rope key all heads share."""
    dt = cfg.dtype
    b, t, _ = x.shape
    h, nope, rkv = cfg.n_heads, cfg.qk_nope_dim, cfg.kv_lora_rank
    turn = rope_interleaved if cfg.rope_interleave else rope
    with jax.named_scope("mla_q"):
        if cfg.q_lora_rank:
            q_a = checkpoint_name(x @ lp["wq_a"].astype(dt), MLA_LATENTS)
            c_q = _rms(q_a.astype(jnp.float32),
                       lp["q_a_norm"]["g"], cfg.norm_eps).astype(dt)
            q = c_q @ lp["wq_b"].astype(dt)
        else:
            c_q, q = None, x @ lp["wq"].astype(dt)
        q = q.reshape(b, t, h, nope + cfg.qk_rope_dim)
    with jax.named_scope("mla_kv"):
        kv_a = checkpoint_name(x @ lp["wkv_a"].astype(dt), MLA_LATENTS)
        c_kv = _rms(kv_a[..., :rkv].astype(jnp.float32),
                    lp["kv_a_norm"]["g"], cfg.norm_eps).astype(dt)
        kv = (c_kv @ lp["wkv_b"].astype(dt)).reshape(
            b, t, h, nope + cfg.v_head_dim)
    with jax.named_scope("qk_rope"):
        q_r = turn(q[..., nope:], positions, cfg.rope_theta)
        k_r = turn(kv_a[:, :, None, rkv:], positions, cfg.rope_theta)
        q = jnp.concatenate([q[..., :nope], q_r], axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_r, q_r.shape)], axis=-1)
    return q, k, kv[..., nope:], c_q


def _index_project(lp, x, c_q, cfg: Config, positions):
    """The indexer's (queries [B, T, Hi, Di], keys [B, T, Di] — one
    for all its heads —, head weights [B, T, Hi] float32) from the
    normed x and the query latent, both with their gradient STOPPED:
    the indexer is trained by its own loss and nothing else learns
    from it. RoPE turns the first qk_rope_dim dimensions of each."""
    dt = cfg.dtype
    b, t, _ = x.shape
    hi, di, r = cfg.index_heads, cfg.index_dim, cfg.qk_rope_dim
    x, c_q = lax.stop_gradient(x), lax.stop_gradient(c_q)
    turn = rope_interleaved if cfg.rope_interleave else rope
    qi = (c_q @ lp["wi_q"].astype(dt)).reshape(b, t, hi, di)
    ki = _ln((x @ lp["wi_k"].astype(dt)).astype(jnp.float32),
             lp["wi_k_norm"]["g"], lp["wi_k_norm"]["b"]).astype(dt)
    qi = jnp.concatenate(
        [turn(qi[..., :r], positions, cfg.rope_theta), qi[..., r:]], -1)
    ki = jnp.concatenate(
        [turn(ki[:, :, None, :r], positions, cfg.rope_theta)[:, :, 0],
         ki[..., r:]], -1)
    w = jnp.dot(x.astype(jnp.float32), lp["wi_w"].astype(jnp.float32),
                precision=lax.Precision.HIGHEST) * (hi * di) ** -0.5
    return qi, ki, w


def _dsa_core(q, k, v, index, cfg: Config, index_aux):
    """Attention over each query's index_topk keys, sequence by
    sequence. `index_aux` receives (the indexer's loss, the selection
    [B, T, T] bool)."""
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5

    def one(q, k, v, qi, ki, w):
        with jax.named_scope("dsa_index"):
            scores = checkpoint_name(att.dsa_index_scores(qi, ki, w),
                                     DSA_SELECT)
            keep = checkpoint_name(
                att.dsa_select(lax.stop_gradient(scores), cfg.index_topk),
                DSA_SELECT)
        with jax.named_scope("dsa_attend"):
            o, p = att.dsa_attend(q, k, v, keep, scale)
        with jax.named_scope("dsa_kl"):
            return o, att.dsa_kl(scores, keep, p), keep

    o, kl, keep = jax.vmap(one)(q, k, v, *index)
    if index_aux is not None:
        index_aux.append((kl.mean(), keep))
    return o


def _mla_attention(lp, x, cfg: Config, pos_offset, index_aux):
    """Latent attention's mixer on one device (x: the normed h): the
    output projection's result [B, T, d]. Where the sequence is longer
    than index_topk each query attends to the keys its indexer
    selects; else to all causal ones, through the model's one entry
    (``ops.attention.attention``: the blockwise kernel on the TPU also
    where q and v differ in width, 192 against 128 in Kimi-VL's
    decoder — the kernel pads both to its lanes; ``att.mha`` off the
    TPU or at a length no tile divides, pvar
    ``attn_reference_layers``). Counted once per traced layer:
    ``attn_mla_layers``, and ``attn_mla_plain_q_layers`` for those
    without a query latent."""
    b, t = x.shape[0], x.shape[1]
    positions = jnp.arange(t) if pos_offset is None \
        else pos_offset + jnp.arange(t)
    selects = bool(cfg.index_topk) and t > cfg.index_topk
    pvar.record("attn_mla_layers")
    if not cfg.q_lora_rank:
        pvar.record("attn_mla_plain_q_layers")
    with jax.named_scope("attn_proj"):
        q, k, v, c_q = _mla_project(lp, x, cfg, positions)
        if selects:
            with jax.named_scope("dsa_index_proj"):
                index = _index_project(lp, x, c_q, cfg, positions)
    with jax.named_scope("attn_core"):
        if selects:
            pvar.record("attn_dsa_layers")
            o = _dsa_core(q, k, v, index, cfg, index_aux)
        else:
            o = att.attention(q, k, v, causal=True)
    with jax.named_scope("attn_proj"), jax.named_scope("mla_o"):
        return o.reshape(b, t, -1) @ lp["wo"].astype(cfg.dtype)


def _attention(lp, x, cfg: Config, ax: Axes, pos_offset,
               windowed: bool = False):
    """Multi-head attention's mixer (x: the normed h): causal attention
    of `n_heads` query heads over `n_kv_heads` shared key / value heads
    (0: as many), then the output projection [B, T, d]; inside the tp
    region where there is one. Query head i attends with key head
    ``i // (n_heads / n_kv_heads)``: the key heads are repeated in
    front of the model's one entry (``ops.attention.attention``: the
    blockwise kernel on the TPU, ``att.mha`` elsewhere), so autodiff
    sums a key head's gradient over its queries. RoPE turns q and k
    where ``pos == "rope"`` (a learned table is the embedding's
    business). Under sp the ring or the Ulysses schedule runs in the
    kernel's place. Counted once per traced layer with shared key
    heads: ``attn_gqa_layers``. `windowed`: this layer's attention is
    under the config's sliding window (``attn_window`` keys, passed
    down to the entry) and turns by ``rope_window``; a full layer by
    ``rope_full`` — a kind whose entry is `NO_ROPE` not at all (counted
    ``attn_unrotated_layers``). QK-norm (``qk_norm``) is an RMSNorm of
    q and of k in float32 under ``attn_proj/qk_rope``, in front of the
    rotation: over the whole projection before the split into heads,
    or per head after it (`PER_HEAD`, counted
    ``attn_head_norm_layers``). Where the config mixes the two kinds
    the core lies under a scope of the kind's name and the layer is
    counted:
    ``attn_window_layers`` / ``attn_full_layers``. Where the config
    gates attention's output (``attn_gate``) the core's result is
    multiplied by ``sigmoid(x W_a)`` in float32 in front of the output
    projection, under ``attn_proj/attn_gate``; counted once per traced
    layer: ``attn_gated_layers``."""
    dt = cfg.dtype
    b, t, _ = x.shape
    dh = cfg.head_dim
    window = cfg.attn_window if windowed else None
    theta = (cfg.rope_window if windowed else cfg.rope_full) \
        or cfg.rope_theta
    per_head = cfg.qk_norm == PER_HEAD
    # The blockwise kernel takes q already scaled. Where it will run
    # (the rule att.attention applies below), 1/sqrt(Dh) goes in where q
    # is still float32 — the projection's accumulator or the QK-norm —
    # so q is rounded to dt once, as it is for att.mha.
    q_scale = dh ** -0.5 if not ax.sp and att.blockwise_tile(
        jax.default_backend(), t, t, dh, window=window) else None

    def split(a):  # [B, T, Hl, Dh]: the local heads under tp
        return a.reshape(b, t, a.shape[-1] // dh, dh)

    # The ORDER of these equations is part of a step's lowered text:
    # with shared key heads and no QK-norm (which reads the WHOLE
    # projection) each projection is split into heads as it is made —
    # nemotron-train-t8192's step, as PR 39 wrote it — else after all
    # three, every other cell's (a norm per head then follows the
    # split). One order for both changes a cell's program text:
    # measured work (ROADMAP D23), not PR 42's fold.
    at_once = not cfg.qk_norm and cfg.n_kv_heads not in (0, cfg.n_heads)
    first, later = (split, lambda a: a) if at_once else (lambda a: a, split)
    with jax.named_scope("attn_proj"):
        if ax.tp:
            x = region_enter(x, ax.tp)
        if q_scale and not cfg.qk_norm:
            q = (jnp.dot(x, lp["wq"].astype(dt),
                         preferred_element_type=jnp.float32)
                 * q_scale).astype(dt)
        else:
            q = x @ lp["wq"].astype(dt)  # [B,T,Hl*Dh] (tp-sharded cols)
        q = first(q)
        k = first(x @ lp["wk"].astype(dt))
        v = first(x @ lp["wv"].astype(dt))
        def qk_normed(q, k):  # 1/sqrt(Dh) where q is still float32
            with jax.named_scope("qk_rope"):
                q = _rms(q.astype(jnp.float32), lp["q_norm"]["g"],
                         cfg.norm_eps)
                return ((q * q_scale if q_scale else q).astype(dt),
                        _rms(k.astype(jnp.float32), lp["k_norm"]["g"],
                             cfg.norm_eps).astype(dt))

        if cfg.qk_norm and not per_head:
            q, k = qk_normed(q, k)
        q, k, v = later(q), later(k), later(v)
        if per_head:  # [.., Dh] against gains of [Dh]: each head alone
            pvar.record("attn_head_norm_layers")
            q, k = qk_normed(q, k)
        if theta == NO_ROPE:
            pvar.record("attn_unrotated_layers")
        elif cfg.pos == "rope":
            with jax.named_scope("qk_rope"):
                positions = jnp.arange(t) if pos_offset is None \
                    else pos_offset + jnp.arange(t)
                q = rope(q, positions, theta)
                k = rope(k, positions, theta)
        if k.shape[2] != q.shape[2]:
            pvar.record("attn_gqa_layers")
            k, v = (jnp.repeat(a, q.shape[2] // k.shape[2], axis=2)
                    for a in (k, v))
    with contextlib.ExitStack() as scopes:  # scores, softmax, AV
        scopes.enter_context(jax.named_scope("attn_core"))
        if cfg.attn_layers is not None:
            pvar.record("attn_window_layers" if windowed
                        else "attn_full_layers")
            scopes.enter_context(jax.named_scope(
                "attn_window" if windowed else "attn_full"))
        if ax.sp:
            if cfg.sp_schedule == "ulysses":
                from ompi_tpu.ops.ulysses import ulysses_attention

                o = ulysses_attention(q, k, v, ax.sp, causal=True)
            elif cfg.sp_schedule == "ring":
                o = ring_attention(q, k, v, ax.sp, causal=True)
            else:
                raise ValueError(
                    f"sp_schedule={cfg.sp_schedule!r}: expected 'ring' "
                    "or 'ulysses'")
        elif window:
            o = att.attention(q, k, v, causal=True,
                              scale=1.0 if q_scale else None, window=window)
        else:  # the call as every configuration without a window makes it
            o = att.attention(q, k, v, causal=True,
                              scale=1.0 if q_scale else None)
    with jax.named_scope("attn_proj"):
        o = o.reshape(b, t, -1)
        if cfg.attn_gate:
            with jax.named_scope("attn_gate"):
                pvar.record("attn_gated_layers")
                gate = jax.nn.sigmoid(jnp.dot(
                    x, lp["wa"].astype(dt),
                    preferred_element_type=jnp.float32))
                o = (o.astype(jnp.float32) * gate).astype(dt)
        o = o @ lp["wo"].astype(dt)   # row parallel: partial sums
        if ax.tp:
            o = region_exit(o, ax.tp)
        return o


def _dense_ffn(lp, x, cfg: Config, ax: Axes):
    """The dense FFN's mixer, inside the tp region where there is one."""
    with jax.named_scope("mlp"):
        if ax.tp:
            x = region_enter(x, ax.tp)
        y = _ffn(x, lp["w1"], lp["w3"] if cfg.mlp_gated else None, lp["w2"],
                 cfg)
        if ax.tp:
            y = region_exit(y, ax.tp)
        return y


def _experts(lp, x, cfg: Config, ax: Axes, aux):
    """The mixture of experts' mixer: the routed experts (the sorted
    path on one device, ``ops.moe.moe_ffn``'s capacity path over an ep
    axis) and, where the config has one (``Config.shared_width``), the
    shared expert every token passes through, inside the tp region
    where there is one."""
    dt = cfg.dtype
    b, t, d = x.shape
    with jax.named_scope("mlp"):
        if ax.tp:
            x = region_enter(x, ax.tp)
        flat = x.reshape(b * t, d)
        if ax.ep:
            y = moe_mod.moe_ffn(
                flat, lp["wg"].astype(dt), lp["w1"].astype(dt),
                lp["w2"].astype(dt), ax.ep,
                capacity_factor=cfg.capacity_factor)
        else:
            y = _moe_sorted(flat, lp, cfg, aux)
        if cfg.shared_width:
            with jax.named_scope("moe_shared"):
                y = y + _ffn(flat, lp["ws1"], lp.get("ws3"), lp["ws2"], cfg)
        if ax.tp:
            y = region_exit(y, ax.tp)
        return y.reshape(b, t, d)


def _ssm_mixer(lp, x, cfg: Config):
    """(the Mamba-2 mixer's output, its scan's final state) of the
    normed x at the config's sizes (ops/ssm.py, imported where a
    pattern has a state-space layer and nowhere else)."""
    from ompi_tpu.ops import ssm

    with jax.named_scope("ssm"):
        return ssm.mixer(
            lp, x, heads=cfg.ssm_heads, head_dim=cfg.ssm_head_dim,
            groups=cfg.ssm_groups, state=cfg.ssm_state, chunk=cfg.ssm_chunk,
            eps=cfg.norm_eps)


def _kda_mixer(lp, x, cfg: Config):
    """(the delta-rule mixer's output, its state after the last token)
    of the normed x at the config's sizes (ops/kda.py, imported where
    `attn_layers` has a delta-rule layer and nowhere else)."""
    from ompi_tpu.ops import kda

    with jax.named_scope("kda"):
        return kda.mixer(lp, x, heads=cfg.kda_heads,
                         head_dim=cfg.kda_head_dim, chunk=cfg.kda_chunk,
                         eps=cfg.norm_eps)


def _sublayer(lp, h, cfg: Config, ax: Axes, sub, pos_offset=None, aux=None,
              index_aux=None):
    """One sub-layer (`sub`: a row of models/params.py's ``layout``):
    ``h + mixer(norm(h))`` — through a norm on the mixer's output where
    the row names one, the output named for the recomputation rule
    where the row names it, the add under the row's scopes. Counted
    once per traced state-space layer: ``ssm_layers`` and
    ``ssm_chunks``, per traced delta-rule layer ``kda_layers`` and
    ``kda_chunks``; the other mixers count of themselves."""
    x = _norm(h.astype(jnp.float32), lp[sub.pre], cfg).astype(cfg.dtype)
    if sub.mixer == "attention":
        y = _attention(lp, x, cfg, ax, pos_offset)
    elif sub.mixer == "window_attention":
        y = _attention(lp, x, cfg, ax, pos_offset, windowed=True)
    elif sub.mixer == "mla":
        y = _mla_attention(lp, x, cfg, pos_offset, index_aux)
    elif sub.mixer == "ffn":
        y = _dense_ffn(lp, x, cfg, ax)
    elif sub.mixer == "experts":
        y = _experts(lp, x, cfg, ax, aux)
    elif sub.mixer == "kda":
        pvar.record("kda_layers")
        pvar.record("kda_chunks", x.shape[1] // cfg.kda_chunk)
        y = _kda_mixer(lp, x, cfg)[0]
    else:
        pvar.record("ssm_layers")
        pvar.record("ssm_chunks", x.shape[1] // cfg.ssm_chunk)
        y = _ssm_mixer(lp, x, cfg)[0]
    with contextlib.ExitStack() as scopes:
        for scope in sub.scopes:
            scopes.enter_context(jax.named_scope(scope))
        if sub.name:  # what the backward pass of a norm on y reads
            y = checkpoint_name(y, sub.name)
        if not sub.post:
            return h + y
        return h + _norm(y.astype(jnp.float32), lp[sub.post],
                         cfg).astype(y.dtype)


def layer_forward(lp, h, cfg: Config, ax: Axes, is_moe: bool,
                  pos_offset=None, aux=None, index_aux=None):
    """One layer on local shards: its sub-layers in turn
    (models/params.py's ``layout``) — the block's pre-norm attention
    (+tp Megatron f/g pair, +sp ring attention; or latent attention
    with its sparse-attention indexer) then FFN or MoE, or the ONE
    mixer of a pattern's letter. Shared by the layer loop below and the
    pipeline-parallel stage scan (models/pipeline.py). `pos_offset` is
    the global position of the shard's first token (RoPE under sp needs
    it; None = not given); `aux`, a list, receives a MoE layer's
    (load-balancing loss, z-loss, routing: an ops.moe.TopKRoute);
    `index_aux`, a list, a selecting layer's (indexer loss, selection
    [B, T, T]). `is_moe` is the layer's kind (`_layer_kind`): whether
    the block's feed-forward part is a mixture of experts, or the
    layer's letter where the config has a pattern."""
    _check_supported(cfg, ax, is_moe, pos_offset, h.shape[1])
    for sub in layout(cfg, is_moe):
        h = _sublayer(lp, h, cfg, ax, sub, pos_offset, aux, index_aux)
    return h


# What a mixer costs models/remat.py's rule, from the config's widths
# alone: (the bytes ONE application over `n` tokens in sequences of `t`
# holds under each name the mixer makes and its backward pass reads, at
# `it` bytes an item; the operations of the PRODUCTS that pass need not
# make again where a name is kept — each name as if kept alone; the
# norms, RoPE, layout changes and the indexer's search it spares beside
# are not counted; attention's over the causal half —; the operations of
# the mixer's LAST product, which its output's name spares).

def _attention_costs(cfg: Config, n: int, t: int, it: int,
                     window: Optional[int] = None):
    """q, k and v as the kernel reads them: the key heads repeated.
    The core's two products over the pairs the mask keeps: a query's
    share of the causal half, t / 2 keys — under a `window` of w =
    min(t, window) keys, w (1 - w / 2t) of them."""
    d, heads, dh = cfg.d_model, cfg.n_heads, cfg.head_dim
    kv = cfg.n_kv_heads or heads
    w = min(t, window or t)
    return ({att.ATTN_OUT: n * heads * (dh * it + 4),
             att.QKV: 3 * n * heads * dh * it},
            {att.ATTN_OUT: 2 * n * (w * (2 * t - w) // t) * heads * dh,
             att.QKV: 2 * n * d * (heads + 2 * kv) * dh},
            2 * n * heads * dh * d)


def _window_attention_costs(cfg: Config, n: int, t: int, it: int):
    return _attention_costs(cfg, n, t, it, cfg.attn_window)


def _mla_costs(cfg: Config, n: int, t: int, it: int):
    d, heads = cfg.d_model, cfg.n_heads
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    latents = cfg.q_lora_rank + cfg.kv_lora_rank + cfg.qk_rope_dim
    sizes = {att.ATTN_OUT: n * heads * (cfg.v_head_dim * it + 4),
             att.QKV: n * heads * (2 * qk + cfg.v_head_dim) * it,
             MLA_LATENTS: n * latents * it}
    if cfg.index_topk and t > cfg.index_topk:
        sizes[att.DSA_PROBS] = n * t * 4
        sizes[DSA_SELECT] = n * t * (4 + 1)
    return (sizes,
            {att.ATTN_OUT: n * t * heads * (qk + cfg.v_head_dim),
             att.QKV: 2 * n * heads * (
                 cfg.q_lora_rank * qk
                 + cfg.kv_lora_rank * (cfg.qk_nope_dim + cfg.v_head_dim)),
             MLA_LATENTS: 2 * n * d * latents,
             att.DSA_PROBS: n * t * heads * qk,
             DSA_SELECT: n * t * cfg.index_heads * cfg.index_dim},
            2 * n * heads * cfg.v_head_dim * d)


def _ffn_costs(width: int):
    """A feed-forward part of `width`: its up-projections (the dense
    FFN's; of the experts, the shared expert's — the routed rows are
    ops/moe.py's own business)."""
    def costs(cfg: Config, n: int, t: int, it: int):
        up = n * width(cfg) * (2 if cfg.mlp_gated else 1)
        return ({MLP_UP: up * it}, {MLP_UP: 2 * cfg.d_model * up},
                2 * n * width(cfg) * cfg.d_model)
    return costs


def _ssm_costs(cfg: Config, n: int, t: int, it: int):
    """ops/ssm.py's three names. Kept, the scan's output spares the
    two products that make it, not those its own backward pass reads:
    on the kernels of ops/ssm_scan.py that is the whole forward kernel,
    whose backward keeps its operands alone and makes the states
    entering the chunks again in a sweep of its own — the numbers stand
    for both forms."""
    from ompi_tpu.ops import ssm

    inner, conv = cfg.ssm_inner, cfg.ssm_conv_width
    first = inner + conv + cfg.ssm_heads
    return ({ssm.SSM_IN: n * first * it, ssm.SSM_CONV: n * conv * it,
             ssm.SSM_Y: n * inner * it},
            {ssm.SSM_IN: 2 * n * cfg.d_model * first,
             ssm.SSM_CONV: 2 * n * conv * cfg.ssm_conv,
             ssm.SSM_Y: n * inner * (cfg.ssm_chunk + 2 * cfg.ssm_state)},
            0)


def _kda_costs(cfg: Config, n: int, t: int, it: int):
    """ops/kda.py's two names: the three wide products' results, and
    the gated output in front of the last product. Each run of heads
    is recomputed in its own backward pass whatever is kept; what the
    gated output, kept, spares of the recurrence's products depends on
    the core's form (``kda.carry_tile``): as ``jax.numpy`` the run's
    recomputation is all the backward pass needs, and the layer's is
    spared one run of the core; on the kernels the run keeps the core's
    output and entering states from the LAYER's recomputation, which
    therefore runs the core whatever is kept — and the run's own does
    not: nothing is spared."""
    from ompi_tpu.ops import kda

    wide = cfg.kda_heads * cfg.kda_head_dim
    on_kernels = kda.carry_tile(
        jax.default_backend(), t, min(kda.HEADS_A_RUN, cfg.kda_heads),
        cfg.kda_head_dim, cfg.kda_chunk, cfg.dtype) is not None
    return ({kda.KDA_PROJ: 3 * n * wide * it, kda.KDA_OUT: n * wide * it},
            {kda.KDA_PROJ: 2 * n * cfg.d_model * 3 * wide,
             kda.KDA_OUT: 0 if on_kernels else n * cfg.kda_heads
             * kda.core_flops_per_token(cfg.kda_head_dim, cfg.kda_chunk)},
            2 * n * wide * cfg.d_model)


#: a mixer's costs, by the name its layout row carries
_COSTS = {"attention": _attention_costs,
          "window_attention": _window_attention_costs, "mla": _mla_costs,
          "kda": _kda_costs,
          "ffn": _ffn_costs(lambda cfg: cfg.d_ff),
          "experts": _ffn_costs(lambda cfg: cfg.shared_width),
          "ssm": _ssm_costs}


def layer_costs(cfg: Config, b: int, t: int, kind) -> remat.Application:
    """What ONE application of a layer of `kind` over [b, t] tokens
    costs the rule: its mixers' costs summed over its layout, and a
    sub-layer's output where something reads it again — a norm on it,
    or the sub-layer after it (a bare residual add's backward reads
    nothing, and the layer's own output is the next layer's input).
    Names that hold nothing are left out."""
    it = jnp.dtype(cfg.dtype).itemsize
    n, rows = b * t, layout(cfg, kind)
    sizes, spared = {}, {}
    for sub in rows:
        made, ops, last_product = _COSTS[sub.mixer](cfg, n, t, it)
        if sub.name and (sub.post or sub is not rows[-1]):
            made[sub.name], ops[sub.name] = n * cfg.d_model * it, last_product
        sizes.update({name: size for name, size in made.items() if size})
        spared.update({name: ops[name] for name in made if made[name]})
    return remat.Application(sizes, spared, n * cfg.d_model * it)


#: the tower's layer kind beside the decoder's (False: a dense layer,
#: True: a MoE layer, or a pattern's letter), models/vision.py's blocks
VIT = "vit"


def _application_kinds(cfg: Config):
    """Per layer application of a step, in order: is it a MoE layer's
    (the trunk's layers, pass after pass, then the multi-token-prediction
    modules), or `VIT`: a block of the vision tower, which run first."""
    return [VIT] * (cfg.vision.n_layers if cfg.vision is not None else 0) \
        + [_layer_kind(cfg, i) for i in range(cfg.n_layers)] * cfg.loops \
        + [_mtp_kind(cfg)] * cfg.mtp_layers


def step_costs(cfg: Config, b: int, t: int, param_bytes: int = 0,
               patches: int = 0, largest: Optional[int] = None):
    """(the step's applications, the bytes it holds whatever they keep)
    as models/remat.py's rule takes them, from what a trace can
    observe: the tokens' shape [b, t] (and the packed row's `patches`
    where the config has a tower: a block's costs are
    models/vision.py's), the config's widths and depth, the bytes of
    the parameters. The fixed bytes, term by term from the program
    (`make_train_step`): the parameters; their gradients (all of them
    where the layers run more than once and a leaf's gradient is a sum
    over the passes, else ONE application's: the update takes each as
    it appears — `largest`, the bytes of the parameters of the
    application that has most, where the caller has the tree, else the
    mean over the applications, which is less where the layers are
    unlike); ONE exit's float32 logits, their exponentials and their
    cotangent (`_nll`'s operand)."""
    it = jnp.dtype(cfg.dtype).itemsize
    kinds = _application_kinds(cfg)
    per = {kind: vision.application(cfg.vision, patches, it) if kind == VIT
           else layer_costs(cfg, b, t, kind) for kind in set(kinds)}
    grads = param_bytes if cfg.loops > 1 \
        else largest or param_bytes // max(len(kinds), 1)
    return ([per[kind] for kind in kinds],
            param_bytes + grads + 3 * b * t * cfg.vocab * 4)


def _ids(batch):
    """A batch's token ids [B, T]: the batch itself, or its "tokens"
    leaf where it is a dict (a config with a tower: models/vision.py
    says what lies beside them)."""
    return batch["tokens"] if isinstance(batch, dict) else batch


def _remat_names(params, batch, cfg: Config) -> Tuple[str, ...]:
    """models/remat.py's ``remat_keep`` on what this trace has."""
    if not cfg.remat:
        return ()
    patches = batch["patches"].shape[0] if isinstance(batch, dict) else 0

    def nbytes(tree):
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))

    # the layers of a pattern are unlike (an expert layer's parameters
    # are 4.6x a state-space layer's in Nemotron-3-Nano): the largest.
    # The blocks' keep-sets were fitted on the chip with the mean
    # (PERF.md 6, PR 35 and PR 37) and stay on it
    largest = max(map(nbytes, params["layers"])) \
        if cfg.layer_pattern is not None else None
    return remat.remat_keep(
        *step_costs(cfg, *_ids(batch).shape, nbytes(params), patches,
                    largest), remat._memory_limit())


class _Recomputed:
    """layer_forward for the applications of ONE trace of a step,
    recomputed in the backward pass where the config says so: the
    names the applications keep (the rule's answer for the trace) and
    each layer kind as one models/remat.py ``Recomputed``, shared by
    that kind's applications in this trace and in no other. The
    backward pass is given the layer's input and what the application
    made under those names, and makes the rest again; with no name
    kept — the fallback: a device that states no limit or has no room —
    that is the whole layer, from its input. ONE path either way.
    Counted once per traced application: models/remat.py's three
    ``remat_*`` pvars, and whatever layer_forward counts of itself."""

    def __init__(self, cfg: Config, ax: Axes, keep: Tuple[str, ...]):
        self.cfg, self.ax, self.keep = cfg, ax, keep
        self._kinds = {}  # (is_moe, fixed_offset) -> the kind's function

    def __call__(self, lp, h, is_moe: bool, pos_offset, aux, index_aux):
        """The layer's output; what it collects for `aux` and
        `index_aux` leaves the recomputed region as results. A
        pos_offset that is None or a Python int is a static argument
        (part of the program, as a value the function closed over would
        be), an `axis_index` a traced one."""
        cfg, ax = self.cfg, self.ax
        if not cfg.remat:
            return layer_forward(lp, h, cfg, ax, is_moe,
                                 pos_offset=pos_offset, aux=aux,
                                 index_aux=index_aux)
        fixed = pos_offset is None or isinstance(pos_offset, int)
        if (is_moe, fixed) not in self._kinds:
            def layer(lp, h, pos_offset):
                mine, index_mine = [], []
                out = layer_forward(lp, h, cfg, ax, is_moe,
                                    pos_offset=pos_offset, aux=mine,
                                    index_aux=index_mine)
                return out, mine, index_mine

            self._kinds[is_moe, fixed] = remat.Recomputed(
                layer, self.keep,
                layer_costs(cfg, h.shape[0], h.shape[1], is_moe).sizes,
                static_argnums=(2,) if fixed else ())
        out, mine, index_mine = self._kinds[is_moe, fixed](lp, h, pos_offset)
        if aux is not None:
            aux.extend(mine)
        if index_aux is not None:
            index_aux.extend(index_mine)
        return out


def _trunk(params, batch, cfg: Config, ax: Axes, aux=None, index_aux=None,
           exits=None):
    """Embedding and layers on local shards: tokens [B_local, T_local]
    (or, for a config with a vision tower, the dict that holds them
    and the packed images: the tower's merged rows replace the
    embedding's at the image positions) -> the residual stream after
    the last layer, and the global position of the shard's first
    token. Where the config runs the
    layers more than once, every pass but the last ends in the final
    norm and the next starts from that normed state, which `exits`, a
    list, receives."""
    dt = cfg.dtype
    tokens = _ids(batch)
    b, t = tokens.shape
    if (cfg.vision is not None) != isinstance(batch, dict):
        raise ValueError(
            "a config with a vision tower (Config.vision) takes a batch "
            "that is a dict (models/vision.py: tokens and the packed "
            "images), every other config the token ids alone")
    # global sequence offset of this sp shard
    if ax.sp:
        t_off = lax.axis_index(ax.sp) * t
    else:
        t_off = 0
    keep = _remat_names(params, batch, cfg)
    with jax.named_scope("embed"):
        h = params["embed"].astype(dt)[tokens]
        if cfg.pos == "learned":
            pos = lax.dynamic_slice_in_dim(
                params["pos"], t_off, t, axis=0) \
                if ax.sp else params["pos"][:t]
            h = h + pos.astype(dt)[None]
        if cfg.vision is not None:
            _check_supported(cfg, ax, False, t_off)
            rows = vision.tower(params["vision"], batch, cfg.vision, dt,
                                cfg.remat, keep)
            h = vision.place(h, rows, batch["image_positions"])

    recomputed = _Recomputed(cfg, ax, keep)
    for s in range(cfg.loops):
        with jax.named_scope(f"loop_{s}") if cfg.loops > 1 \
                else contextlib.nullcontext():
            pvar.record("loop_passes")
            for i, lp in enumerate(params["layers"]):
                pvar.record("loop_layer_applications")
                with jax.named_scope(f"layer_{i}"):
                    h = recomputed(lp, h, _layer_kind(cfg, i), t_off, aux,
                                   index_aux)
            if s < cfg.loops - 1:
                h = _final_norm(params, h, cfg)
                if exits is not None:
                    exits.append(h)
    return h, t_off


def _final_norm(params, h, cfg: Config):
    """The final norm of the residual stream, in the activations'
    type."""
    return _norm(h.astype(jnp.float32), params["ln_f"], cfg).astype(
        cfg.dtype)


def _head_matrix(params, cfg: Config):
    """The head's [vocab, d] matrix (tied: the embedding)."""
    return params["embed"] if cfg.tie_head else params["head"]


def _head_logits(head, x, cfg: Config):
    """The head on a final-normed x: float32 logits [B, T, vocab]."""
    # bf16 operands at full MXU rate, f32 accumulation (the vocab
    # matmul is the single largest matmul in the model; an f32xf32
    # product here runs at half the systolic-array throughput)
    return jnp.einsum("btd,vd->btv", x, head.astype(cfg.dtype),
                      preferred_element_type=jnp.float32)


def _head(params, h, cfg: Config):
    """Final norm and head: float32 logits [B, T, vocab]."""
    return _head_logits(_head_matrix(params, cfg),
                        _final_norm(params, h, cfg), cfg)


def forward_local(params, tokens, cfg: Config, ax: Axes, aux=None,
                  index_aux=None):
    """Forward pass on local shards (inside shard_map when any axis is
    set). tokens: [B_local, T_local] int32 (or the dict `_trunk`
    takes) -> logits [B_local, T_local,
    vocab] float32. `aux`, a list, receives each MoE layer's
    (load-balancing loss, z-loss, routing); `index_aux` each selecting
    layer's (indexer loss, selection)."""
    h, _ = _trunk(params, tokens, cfg, ax, aux, index_aux)
    with jax.named_scope("head_loss"):
        return _head(params, h, cfg)


def _mtp_forward(mp, h, params, labels, cfg: Config, ax: Axes, pos_offset,
                 aux, index_aux):
    """One multi-token-prediction module (DeepSeek-V3's): position i
    merges the trunk's h[i] with the embedding of token i + 1 (the
    label of i) — ``W_eh [norm(h) ; norm(emb)]`` — and runs one more
    layer (`_mtp_kind`: dense or experts as a layer after the last
    would be; where the config mixes kinds of attention, of the kind
    ``mtp_attn`` names, counted ``mtp_full_layers`` /
    ``mtp_window_layers``); the caller puts the SHARED final norm and
    head on the result to predict token i + 2."""
    dt = cfg.dtype
    kind = _mtp_kind(cfg)
    if isinstance(kind, Block):
        pvar.record("mtp_window_layers" if kind.windowed
                    else "mtp_full_layers")
    with jax.named_scope("attn_proj"), jax.named_scope("mtp_merge"):
        e = params["embed"].astype(dt)[jnp.maximum(labels, 0)]
        both = jnp.concatenate(
            [_norm(h.astype(jnp.float32), mp["hnorm"], cfg),
             _norm(e.astype(jnp.float32), mp["enorm"], cfg)], axis=-1)
        h = both.astype(dt) @ mp["eh_proj"].astype(dt)
    return _Recomputed(cfg, ax, _remat_names(params, labels, cfg))(
        mp, h, kind, pos_offset, aux, index_aux)


def _label_hit(logits, labels):
    """bool [B, T, vocab]: where each position's label sits (a label
    below 0 is compared as 0 and left to the caller's mask)."""
    return lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1) \
        == jnp.maximum(labels, 0)[..., None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _nll(logits, labels, dtype):
    """Per-position cross-entropy [B, T] of float32 logits [B, T,
    vocab]: the one reading of the label's logit in the model, and the
    one making of the logits' cotangent, written once in `dtype` (the
    activations' type: what the head's two backward products take)."""
    return _nll_fwd(logits, labels, dtype)[0]


def _nll_fwd(logits, labels, dtype):
    # the label's logit by a mask, not a gather: a gather's transpose
    # scatters into the FLATTENED logits, and the chip pays two
    # relayout copies of [B, T, vocab] float32 and a float32 cotangent
    # around it, under no scope's name (6.6 ms an exit of [4096,
    # 49152]: PERF.md 6, PR 32; 6.8 ms of olmoe-train-t4096's 122:
    # PR 45). A sum of one float32 value and zeros is exact.
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.where(_label_hit(logits, labels), logits, 0.0).sum(-1)
    return logz - gold, (logits, logz, labels)


def _nll_bwd(dtype, res, g):
    logits, logz, labels = res
    d = (jnp.exp(logits - logz[..., None])
         - _label_hit(logits, labels).astype(jnp.float32)) * g[..., None]
    # softmax - onehot is written ONCE, rounded as the products round
    # it anyway, and the barrier keeps it so: left to itself XLA makes
    # it again inside both products' input fusions, for every output
    # tile (+3.2 ms on the two products of olmoe-train-t4096 where this
    # pass costs 1.8: PERF.md 6, PR 45)
    return lax.optimization_barrier(d.astype(dtype)).astype(
        jnp.float32), None


_nll.defvjp(_nll_fwd, _nll_bwd)


def _token_nll(logits, labels, mask, dtype):
    """Summed cross-entropy of float32 logits over the masked
    positions (`dtype`: `_nll`'s)."""
    return (_nll(logits, labels, dtype) * mask).sum()


def _exit_terms(params, exits, h, labels, mask, cfg: Config):
    """Per exit, at each position [B, T] in float32: (the shared
    head's cross-entropy there, the log of the probability of leaving
    there). `exits`: the final-normed state after each pass but the
    last, `h` the last pass's state before its norm. Exit s is taken with probability lambda_s x prod_{j<s} (1 - lambda_j),
    lambda_s = sigmoid(state_s . w + b); the last takes what is left.
    An exit's logits are made again in the backward pass, so that one
    exit's [B, T, vocab] is alive at a time."""
    def nll(x, head):
        return _nll(_head_logits(head, x, cfg), labels, cfg.dtype) * mask

    states = exits + [_final_norm(params, h, cfg)]
    nlls = []
    for s, x in enumerate(states):
        with jax.named_scope(f"exit_{s}"):
            nlls.append(jax.checkpoint(nll)(x, _head_matrix(params, cfg)))
    with jax.named_scope("exit_gate"):
        gate = params["exit_gate"]
        logps, left = [], 0.0
        for x in states[:-1]:
            z = jnp.einsum("btd,d->bt", x.astype(jnp.float32),
                           gate["w"].astype(jnp.float32),
                           precision=lax.Precision.HIGHEST) \
                + gate["b"].astype(jnp.float32)
            logps.append(left + jax.nn.log_sigmoid(z))
            left = left + jax.nn.log_sigmoid(-z)
        logps.append(left + jnp.zeros_like(mask))
    return nlls, logps


def _exit_loss(nlls, logps, mask, cfg: Config):
    """The summed loss over the exits: per position the expected
    cross-entropy under the exit distribution, less
    exit_entropy_weight x that distribution's entropy."""
    with jax.named_scope("exit_gate"):
        each = sum(jnp.exp(lp) * (n + cfg.exit_entropy_weight * lp)
                   for n, lp in zip(nlls, logps))
        return (each * mask).sum()


def loss_local(params, tokens, labels, cfg: Config, ax: Axes):
    """Summed next-token CE over local tokens + local count (caller
    normalizes after cross-shard psum). Where the config weighs the
    router's losses, their mean over the MoE layers times the local
    count is in the sum, so the caller's nll / count is the mean CE
    plus the weighted router losses; the indexers' loss (mean over the
    selecting layers) and the multi-token-prediction loss (mean over
    ITS positions) enter the same way at their weights."""
    aux = [] if (cfg.router_aux_weight or cfg.router_z_weight) else None
    index_aux = []
    exits = [] if cfg.exit_gate else None
    h, t_off = _trunk(params, tokens, cfg, ax, aux, index_aux, exits)
    with jax.named_scope("head_loss"):
        mask = (labels >= 0).astype(jnp.float32)
        count = mask.sum()
        if cfg.exit_gate:
            nll = _exit_loss(*_exit_terms(params, exits, h, labels, mask,
                                          cfg), mask, cfg)
        else:
            nll = _token_nll(_head(params, h, cfg), labels, mask, cfg.dtype)
    extra = 0.0
    if cfg.mtp_layers:
        if cfg.mtp_layers != 1:
            raise NotImplementedError(
                "mtp_layers > 1: only DeepSeek-V3's depth-1 module is "
                "written")
        with jax.named_scope(f"layer_{cfg.n_layers}"):
            h2 = _mtp_forward(params["mtp"][0], h, params, labels, cfg, ax,
                              t_off, aux, index_aux)
        with jax.named_scope("head_loss"), jax.named_scope("mtp"):
            # position i predicts token i + 2, the label of i + 1; the
            # last position has none
            t = labels.shape[1]
            labels2 = jnp.roll(labels, -1, axis=1)
            mask2 = ((labels >= 0) & (labels2 >= 0)
                     & (jnp.arange(t) < t - 1)[None]).astype(jnp.float32)
            extra = extra + cfg.mtp_weight * _token_nll(
                _head(params, h2, cfg), labels2, mask2, cfg.dtype) \
                / jnp.maximum(mask2.sum(), 1.0)
    if index_aux:
        with jax.named_scope("head_loss"):
            extra = extra + cfg.index_loss_weight * sum(
                a[0] for a in index_aux) / len(index_aux)
    if aux:
        with jax.named_scope("moe_route"):
            extra = extra + cfg.router_aux_weight * sum(
                a[0] for a in aux) / len(aux) + cfg.router_z_weight * sum(
                a[1] for a in aux) / len(aux)
    with jax.named_scope("head_loss"):
        return nll + count * extra, count


def _probe(name: str, *static: str):
    """jit a set-up probe under the job's own prefix: the compile
    ledger (prof/compile.py) counts programs named ``ompi_*`` as the
    job's, and a probe's compile is part of its set-up. `static`: its
    static arguments beside the config."""
    def wrap(fn):
        fn.__name__ = fn.__qualname__ = name
        return jax.jit(fn, static_argnames=("cfg",) + static)
    return wrap


@_probe("ompi_route_counts")
def _route_probe(params, tokens, cfg: Config):
    aux = []
    forward_local(params, tokens, cfg, Axes(), aux)
    return (jnp.stack([route.counts for _, _, route in aux]),
            jnp.stack([route.experts for _, _, route in aux]))


def route_counts(params, tokens, cfg: Config):
    """int32 [MoE layers, n_experts]: how many of the batch's
    `tokens * top_k` assignments each expert got in each MoE layer of
    a one-device forward pass. A probe the host calls outside any
    timed window (it waits for the result): what it counted goes to
    the always-on counters `moe_assignments` and
    `moe_dropped_assignments` — the second is what a capacity or an
    exchange lost, and reads 0 on the sorted path by construction —
    and, for a chip that holds a share of the experts,
    `moe_held_assignments` and `moe_over_bound_layers`: the layers of
    this batch whose held assignments exceed the rows the layer is
    bounded at (`ops/moe.held_rows_bound`), which take its full path."""
    counts = _route_probe(params, tokens, cfg)[0]
    tokens = _ids(tokens)
    routed = int(counts.sum())
    pvar.record("moe_assignments", routed)
    pvar.record("moe_dropped_assignments",
                counts.shape[0] * tokens.size * cfg.top_k - routed)
    if cfg.held_experts:  # what this chip's share of each layer got
        first, n = cfg.held_experts
        held = np.asarray(counts[:, first:first + n].sum(1))
        pvar.record("moe_held_assignments", int(held.sum()))
        # the layers of this batch that would take the full path
        pvar.record("moe_over_bound_layers", int((
            held > moe_mod.held_rows_bound(tokens.size, cfg.top_k, n,
                                           cfg.n_experts)).sum()))
    return counts


def route_experts(params, tokens, cfg: Config):
    """int32 [MoE layers, tokens, top_k]: the experts each token chose
    (the same probe as :func:`route_counts`; top-k of many is discrete,
    so this is what tells a re-routed token from a wrong one)."""
    return _route_probe(params, tokens, cfg)[1]


@_probe("ompi_dsa_selection")
def _selection_probe(params, tokens, cfg: Config):
    index_aux = []
    forward_local(params, tokens, cfg, Axes(), None, index_aux)
    return jnp.stack([keep for _, keep in index_aux])


def dsa_selection(params, tokens, cfg: Config):
    """bool [selecting layers, B, T, T]: the keys each query attends to
    in each layer whose indexer selects, in a one-device forward pass
    (empty where the sequence is no longer than index_topk). A probe
    the host calls outside any timed window: what it counted goes to
    the always-on counters `dsa_selected_pairs` and `dsa_causal_pairs`
    (their ratio is the share of the causal pairs attention keeps)."""
    b, t = tokens.shape
    if not cfg.index_topk or t <= cfg.index_topk:
        return jnp.zeros((0, b, t, t), bool)
    keep = _selection_probe(params, tokens, cfg)
    pvar.record("dsa_selected_pairs", int(keep.sum()))
    pvar.record("dsa_causal_pairs", keep.shape[0] * b * t * (t + 1) // 2)
    return keep


@_probe("ompi_vision_rows")
def _vision_probe(params, batch, cfg: Config):
    return vision.tower(params["vision"], batch, cfg.vision, cfg.dtype,
                        cfg.remat)


def vision_rows(params, batch, cfg: Config):
    """[image positions, d_model]: the rows the vision tower and its
    projector make of a batch's packed images, as the step's forward
    pass places them in the sequence. A probe the host calls outside
    any timed window."""
    return _vision_probe(params, batch, cfg=cfg)


@_probe("ompi_exit_stats")
def _exit_probe(params, tokens, labels, cfg: Config):
    exits = []
    h, _ = _trunk(params, tokens, cfg, Axes(), exits=exits)
    with jax.named_scope("head_loss"):
        mask = (labels >= 0).astype(jnp.float32)
        nlls, logps = _exit_terms(params, exits, h, labels, mask, cfg)
        return (jnp.stack([n.sum() for n in nlls]),
                jnp.stack([(jnp.exp(lp) * mask).sum() for lp in logps]),
                mask.sum())


def exit_stats(params, tokens, labels, cfg: Config):
    """(mean cross-entropy at each exit [loops], mean probability of
    leaving at each exit [loops]) over the batch's labelled positions,
    in a one-device forward pass of a config with exits. A probe the
    host calls outside any timed window: what it counted goes to the
    always-on counters `exit_probe_tokens` and `exit_mass_micro_p<s>`
    (the summed probability of exit s, in millionths of a token)."""
    nll, mass, count = (np.asarray(a, np.float64) for a in _exit_probe(
        params, tokens, labels, cfg))
    pvar.record("exit_probe_tokens", int(count))
    for s, m in enumerate(mass):
        pvar.record(f"exit_mass_micro_p{s}", int(round(m * 1e6)))
    return nll / count, mass / count


#: the leaves of a state-space layer whose gradients exist only
#: through the scan, the convolution and the gated norm
SSM_SMALL = ("A_log", "dt_bias", "D", "conv_w", "conv_b", "ssm_norm")


def _first_of(kind: str, params, tokens, cfg: Config):
    """(the leaves of the first layer of `kind`, its normed input) of a
    one-device forward pass of a config with a layer pattern."""
    dt = cfg.dtype
    h = params["embed"].astype(dt)[tokens]
    if cfg.pos == "learned":
        h = h + params["pos"][:tokens.shape[1]].astype(dt)[None]
    for i, lp in enumerate(params["layers"]):
        if _layer_kind(cfg, i) == kind:
            return lp, _norm(h.astype(jnp.float32), lp["ln"], cfg).astype(dt)
        h = layer_forward(lp, h, cfg, Axes(), _layer_kind(cfg, i))
    raise ValueError(f"layer_pattern={cfg.layer_pattern!r} has no layer "
                     f"{kind!r}")


@_probe("ompi_ssm_probe")
def _ssm_probe(params, tokens, cfg: Config):
    return _ssm_mixer(*_first_of(SSM, params, tokens, cfg), cfg)


@_probe("ompi_gqa_probe")
def _gqa_probe(params, tokens, cfg: Config):
    return _attention(*_first_of(ATTENTION, params, tokens, cfg), cfg,
                      Axes(), None)


def gqa_probe(params, tokens, cfg: Config):
    """The first attention layer's mixer output [B, T, d_model] (before
    the residual add) in a one-device forward pass of a config with a
    layer pattern: which key head a query head attends with shows in
    it and in little else. A probe the host calls outside any timed
    window."""
    return _gqa_probe(params, tokens, cfg=cfg)


def _embedded_for(params, tokens, cfg: Config, layer: int, delta: bool):
    """(block `layer`'s leaves, its first sub-layer's layout row, that
    sub-layer's normed input) on the EMBEDDED batch — the stream
    entering layer 0 — for a probe of a delta-rule mixer (`delta`) or
    of attention: asked for the other kind of layer it raises. Layer
    ``n_layers`` is the multi-token-prediction module's, where the
    config has one."""
    dt = cfg.dtype
    h = params["embed"].astype(dt)[tokens]
    if cfg.pos == "learned":
        h = h + params["pos"][:tokens.shape[1]].astype(dt)[None]
    lp, kind = (params["mtp"][0], _mtp_kind(cfg)) \
        if cfg.mtp_layers and layer == cfg.n_layers \
        else (params["layers"][layer], _layer_kind(cfg, layer))
    _check_supported(cfg, Axes(), kind, None, tokens.shape[1])
    sub = layout(cfg, kind)[0]
    if (sub.mixer == "kda") != delta:
        raise ValueError(
            f"layer {layer} " + ("has no delta-rule mixer" if delta else
                                 "is a delta-rule layer: kda_probe reads it")
            + f" (attn_layers={cfg.attn_layers!r})")
    return lp, sub, _norm(h.astype(jnp.float32), lp[sub.pre], cfg).astype(dt)


@_probe("ompi_attn_probe", "layer")
def _attn_probe(params, tokens, cfg: Config, layer: int):
    lp, sub, x = _embedded_for(params, tokens, cfg, layer, delta=False)
    return _attention(lp, x, cfg, Axes(), None,
                      windowed=sub.mixer == "window_attention")


def attn_probe(params, tokens, cfg: Config, layer: int):
    """Block `layer`'s attention mixer — its norm, its weights, its
    kind's mask and RoPE parameters — on the EMBEDDED batch: [B, T,
    d_model], before any residual add. Every layer is read on the same
    rows, the stream entering layer 0, so that a reading is the mixer's
    own and carries nothing of the layers in front (a bfloat16 run
    re-routes a few tokens in every expert layer it has passed). A
    window ignored or one key off, one kind's RoPE parameters on the
    other kind of layer, or query heads on the wrong key heads show in
    it and in little else. A probe the host calls outside any timed
    window."""
    if cfg.layer_pattern is not None or cfg.attn != "mha":
        raise ValueError("attn_probe reads a block's multi-head attention "
                         "(no layer_pattern, attn='mha')")
    return _attn_probe(params, tokens, cfg=cfg, layer=layer)


@_probe("ompi_kda_probe", "layer")
def _kda_probe(params, tokens, cfg: Config, layer: int):
    lp, _, x = _embedded_for(params, tokens, cfg, layer, delta=True)
    return _kda_mixer(lp, x, cfg)


def kda_probe(params, tokens, cfg: Config, layer: int):
    """(block `layer`'s delta-rule mixer output [B, T, d_model], its
    state after the last token [B, H, K, K] float32) on the EMBEDDED
    batch, as :func:`attn_probe` reads a block's attention: a decay
    ignored, the correction term left out, a chunk boundary handled
    wrongly or the output gate missing show in it and in little else. A
    probe the host calls outside any timed window: the norm of that
    state goes to the always-on counter `kda_state_norm_micro`, in
    millionths."""
    out, last = _kda_probe(params, tokens, cfg=cfg, layer=layer)
    pvar.record("kda_state_norm_micro", int(round(1e6 * float(
        jnp.sqrt(jnp.sum(last * last))))))
    return out, last


def ssm_probe(params, tokens, cfg: Config):
    """(the first state-space layer's mixer output [B, T, d_model],
    its scan's state after the last token [B, H, P, N] float32) in a
    one-device forward pass. A probe the host calls outside any timed
    window: the norm of that state (a scan that forgets everything or
    nothing shows in it) goes to the always-on counter
    `ssm_state_norm_micro`, in millionths."""
    out, last = _ssm_probe(params, tokens, cfg=cfg)
    pvar.record("ssm_state_norm_micro", int(round(1e6 * float(
        jnp.sqrt(jnp.sum(last * last))))))
    return out, last


@_probe("ompi_ssm_leaf_grads")
def _ssm_grads_probe(params, tokens, labels, cfg: Config):
    def small(lp):
        return jax.tree.map(lambda a: a.astype(jnp.float32),
                            {n: lp[n] for n in SSM_SMALL})

    mine = [i for i in range(cfg.n_layers) if _layer_kind(cfg, i) == SSM]

    def mean_loss(leaves):
        layers = list(params["layers"])
        for i, sm in zip(mine, leaves):
            layers[i] = dict(layers[i], **sm)
        nll, count = loss_local(dict(params, layers=layers), tokens, labels,
                                cfg, Axes())
        return nll / count

    grads = jax.grad(mean_loss)([small(params["layers"][i]) for i in mine])
    return jnp.stack([jnp.stack([
        jnp.sqrt(sum(jnp.sum(a * a) for a in jax.tree.leaves(g[n])))
        for n in SSM_SMALL]) for g in grads])


def ssm_leaf_grads(params, tokens, labels, cfg: Config):
    """float32 [state-space layers, len(SSM_SMALL)]: the norm of the
    training loss's gradient in each `SSM_SMALL` leaf of each
    state-space layer, taken in float32 (the step hands the optimizer
    the parameters' type, and a step of lr x such a gradient is less
    than bfloat16 resolves in a leaf near 1). A probe the host calls
    outside any timed window."""
    return _ssm_grads_probe(params, tokens, labels, cfg=cfg)


def grad_sync(grads, specs, ax: Axes, extra=None):
    """Cross-device gradient reduction (the DDP-bucket MPI_Allreduce of
    SURVEY.md §2.10, compiled to one psum per param).

    Rule: psum each grad over the batch axes (dp/sp/ep) minus any axis
    the param is sharded on. The tp axis never appears here — partial
    tp cotangents are already all-reduced at the region_enter AD
    boundary (Megatron f) — except for params listed in `extra`
    (see grad_extra_axes)."""
    batch = ax.batch_axes()

    def reduce_one(g, spec, ex):
        sharded = set()
        for entry in (tuple(spec) if spec is not None else ()):
            if entry is None:
                continue
            if isinstance(entry, tuple):
                sharded.update(entry)
            else:
                sharded.add(entry)
        axes = tuple(a for a in batch if a not in sharded)
        if ex:
            axes = axes + (ex,)
        return lax.psum(g, axes) if axes else g

    g_leaves, treedef = jax.tree.flatten(grads)
    s_leaves = treedef.flatten_up_to(specs)
    e_leaves = treedef.flatten_up_to(extra) if extra is not None \
        else [""] * len(g_leaves)
    with jax.named_scope("grad_sync"):
        out = [reduce_one(g, s, e)
               for g, s, e in zip(g_leaves, s_leaves, e_leaves)]
    return jax.tree.unflatten(treedef, out)


def sgd_update(params, grads, scale):
    """The SGD step shared by the flat and pipeline train steps. The
    trailing astype keeps each param's STORAGE dtype: scale is f32,
    and bf16 params would otherwise promote to f32 — changing the
    jitted step's input signature and forcing an XLA recompile inside
    any steady-state loop (the artifact documented in BASELINE.md)."""
    with jax.named_scope("sgd_update"):
        return jax.tree.map(
            lambda p, g: (p - scale * g.astype(p.dtype)).astype(p.dtype),
            params, grads)


def make_train_step(cfg: Config, ax: Axes, specs, lr: float = 1e-2):
    """(params, tokens, labels) -> (new_params, loss). Call inside
    shard_map over the mesh (or directly when all axes are None).
    Jitted as it is, the step is module ``jit_ompi_train_step`` in a
    device trace."""
    extra = grad_extra_axes(cfg, ax)

    def ompi_train_step(params, tokens, labels):
        (nll, cnt), grads = jax.value_and_grad(
            lambda p: loss_local(p, tokens, labels, cfg, ax),
            has_aux=True)(params)
        batch = ax.batch_axes()
        if batch:
            nll = lax.psum(nll, batch)
            cnt = lax.psum(cnt, batch)
        loss = nll / cnt
        grads = grad_sync(grads, specs, ax, extra)
        scale = lr / cnt
        new_params = sgd_update(params, grads, scale)
        return new_params, loss

    return ompi_train_step
