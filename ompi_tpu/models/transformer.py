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

Names on the device (``jax.named_scope``: metadata, the HLO is the
same): the jitted step is module ``jit_ompi_train_step``; its ops carry
``embed``, ``layer_<i>/{ln, attn_proj, attn_core, mlp}``,
``head_loss`` (final LN, tied head, loss), ``grad_sync`` and
``sgd_update`` in their op path, under the ``jvp(...)`` /
``transpose(jvp(...))`` jax adds for forward and backward — so a trace
reader finds a model part by name, not by XLA's fusion numbering.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ompi_tpu.ops import attention as att
from ompi_tpu.ops import moe as moe_mod
from ompi_tpu.ops.ring_attention import ring_attention
from ompi_tpu.parallel.collectives import region_enter, region_exit


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
    capacity_factor: float = 1.25
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

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


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


def _is_moe(cfg: Config, layer: int) -> bool:
    return cfg.moe_every > 0 and (layer + 1) % cfg.moe_every == 0


def init_params(rng: np.random.Generator, cfg: Config) -> Dict:
    """Full (unsharded) parameters, host-side numpy. Sharding happens at
    the jit boundary via param_specs (the driver of HtoD layout)."""
    pdt = np.dtype(cfg.param_dtype)

    def normal(*shape, scale):
        return np.asarray(rng.standard_normal(shape) * scale,
                          dtype=pdt)

    d, f, v = cfg.d_model, cfg.d_ff, cfg.vocab
    s_emb = 1.0 / math.sqrt(d)
    params: Dict = {
        "embed": normal(v, d, scale=s_emb),
        "pos": normal(cfg.max_seq, d, scale=0.02),
        "ln_f": {"g": np.ones(d, pdt),
                 "b": np.zeros(d, pdt)},
        "layers": [],
    }
    for i in range(cfg.n_layers):
        lp = {
            "ln1": {"g": np.ones(d, pdt),
                    "b": np.zeros(d, pdt)},
            "ln2": {"g": np.ones(d, pdt),
                    "b": np.zeros(d, pdt)},
            "wq": normal(d, d, scale=s_emb),
            "wk": normal(d, d, scale=s_emb),
            "wv": normal(d, d, scale=s_emb),
            "wo": normal(d, d, scale=s_emb / math.sqrt(2 * cfg.n_layers)),
        }
        if _is_moe(cfg, i):
            lp["wg"] = normal(d, cfg.n_experts, scale=s_emb)
            lp["w1"] = normal(cfg.n_experts, d, f, scale=s_emb)
            lp["w2"] = normal(cfg.n_experts, f, d,
                              scale=1.0 / math.sqrt(f))
        else:
            lp["w1"] = normal(d, f, scale=s_emb)
            lp["w2"] = normal(f, d, scale=1.0 / math.sqrt(f))
        params["layers"].append(lp)
    return params


def param_specs(cfg: Config, ax: Axes):
    """PartitionSpec pytree matching init_params' structure.

    tp shards: wq/wk/wv on output dim (column parallel), wo on input dim
    (row parallel), dense w1/w2 likewise. ep shards MoE experts on dim 0.
    Everything else replicated.
    """
    from jax.sharding import PartitionSpec as P

    rep = P()
    specs: Dict = {
        "embed": rep, "pos": rep,
        "ln_f": {"g": rep, "b": rep},
        "layers": [],
    }
    for i in range(cfg.n_layers):
        ls = {
            "ln1": {"g": rep, "b": rep},
            "ln2": {"g": rep, "b": rep},
            "wq": P(None, ax.tp), "wk": P(None, ax.tp),
            "wv": P(None, ax.tp), "wo": P(ax.tp, None),
        }
        if _is_moe(cfg, i):
            ls["wg"] = rep
            ls["w1"] = P(ax.ep, None, ax.tp)
            ls["w2"] = P(ax.ep, ax.tp, None)
        else:
            ls["w1"] = P(None, ax.tp)
            ls["w2"] = P(ax.tp, None)
        specs["layers"].append(ls)
    return specs


def grad_extra_axes(cfg: Config, ax: Axes):
    """Extra grad-psum axes per param, same structure as init_params.

    The MoE router wg is replicated yet lives *inside* the tp region
    (its cotangent arrives partial, via the combine-weights path through
    the tp-sharded expert outputs), so unlike other replicated params it
    needs an explicit psum over tp."""
    # leaves are axis-name strings ("" = none): strings are pytree
    # leaves, so the tree composes with tree.flatten_up_to cleanly
    none = ""
    extra: Dict = {"embed": none, "pos": none,
                   "ln_f": {"g": none, "b": none}, "layers": []}
    for i in range(cfg.n_layers):
        le = {"ln1": {"g": none, "b": none},
              "ln2": {"g": none, "b": none},
              "wq": none, "wk": none, "wv": none, "wo": none,
              "w1": none, "w2": none}
        if _is_moe(cfg, i):
            le["wg"] = ax.tp or none
        extra["layers"].append(le)
    return extra


def _ln(x, g, b):
    with jax.named_scope("ln"):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) * lax.rsqrt(var + 1e-5) * g + b


def layer_forward(lp, h, cfg: Config, ax: Axes, is_moe: bool):
    """One transformer block on local shards: pre-LN attention (+tp
    Megatron f/g pair, +sp ring attention) then FFN or MoE. Shared by
    the layer loop below and the pipeline-parallel stage scan
    (models/pipeline.py)."""
    dt = cfg.dtype
    b, t = h.shape[0], h.shape[1]
    x = _ln(h.astype(jnp.float32), lp["ln1"]["g"],
            lp["ln1"]["b"]).astype(dt)
    with jax.named_scope("attn_proj"):
        if ax.tp:
            x = region_enter(x, ax.tp)
        q = x @ lp["wq"].astype(dt)   # [B,T,Hl*Dh] (tp-sharded cols)
        k = x @ lp["wk"].astype(dt)
        v = x @ lp["wv"].astype(dt)
        hl = q.shape[-1] // cfg.head_dim  # local heads under tp
        q = q.reshape(b, t, hl, cfg.head_dim)
        k = k.reshape(b, t, hl, cfg.head_dim)
        v = v.reshape(b, t, hl, cfg.head_dim)
    with jax.named_scope("attn_core"):  # scores, softmax, AV
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
        else:
            # reference mha, not the pallas flash kernel: measured on
            # the v5e at T=1024 the kernel is ~4% SLOWER (XLA's fused
            # softmax wins while the T x T score tensor is small);
            # att.mha_auto remains available for long-context
            # single-device use where the score materialization
            # dominates
            o = att.mha(q, k, v, causal=True)
    with jax.named_scope("attn_proj"):
        o = o.reshape(b, t, hl * cfg.head_dim)
        o = o @ lp["wo"].astype(dt)   # row parallel: partial sums
        if ax.tp:
            o = region_exit(o, ax.tp)
        h = h + o

    x = _ln(h.astype(jnp.float32), lp["ln2"]["g"],
            lp["ln2"]["b"]).astype(dt)
    with jax.named_scope("mlp"):
        if ax.tp:
            x = region_enter(x, ax.tp)
        if is_moe:
            flat = x.reshape(b * t, cfg.d_model)
            if ax.ep:
                y = moe_mod.moe_ffn(
                    flat, lp["wg"].astype(dt), lp["w1"].astype(dt),
                    lp["w2"].astype(dt), ax.ep,
                    capacity_factor=cfg.capacity_factor)
            else:
                y = _moe_dense(flat, lp, cfg)
            if ax.tp:
                y = region_exit(y, ax.tp)
            y = y.reshape(b, t, cfg.d_model)
        else:
            u = jnp.maximum(x @ lp["w1"].astype(dt), 0)
            y = u @ lp["w2"].astype(dt)
            if ax.tp:
                y = region_exit(y, ax.tp)
        return h + y


def forward_local(params, tokens, cfg: Config, ax: Axes):
    """Forward pass on local shards (inside shard_map when any axis is
    set). tokens: [B_local, T_local] int32 -> logits [B_local, T_local,
    vocab] float32."""
    dt = cfg.dtype
    b, t = tokens.shape
    # global sequence offset of this sp shard
    if ax.sp:
        t_off = lax.axis_index(ax.sp) * t
    else:
        t_off = 0
    with jax.named_scope("embed"):
        h = params["embed"].astype(dt)[tokens]
        pos = lax.dynamic_slice_in_dim(params["pos"], t_off, t, axis=0) \
            if ax.sp else params["pos"][:t]
        h = h + pos.astype(dt)[None]

    for i, lp in enumerate(params["layers"]):
        with jax.named_scope(f"layer_{i}"):
            h = layer_forward(lp, h, cfg, ax, _is_moe(cfg, i))

    with jax.named_scope("head_loss"):
        h = _ln(h.astype(jnp.float32), params["ln_f"]["g"],
                params["ln_f"]["b"])
        # weight-tied head: bf16 operands at full MXU rate, f32
        # accumulation (the vocab matmul is the single largest matmul
        # in the model; an f32xf32 product here runs at half the
        # systolic-array throughput)
        return jnp.einsum("btd,vd->btv", h.astype(dt),
                          params["embed"].astype(dt),
                          preferred_element_type=jnp.float32)


def _moe_dense(flat, lp, cfg: Config):
    """Single-device MoE (no ep axis): dense einsum over all experts."""
    cap = max(int(cfg.capacity_factor * flat.shape[0] / cfg.n_experts), 1)
    route = moe_mod.top1_routing(flat @ lp["wg"].astype(flat.dtype), cap)
    slots = jnp.einsum("tec,td->ecd", route.dispatch,
                       flat.astype(jnp.float32))
    hidden = jnp.maximum(jnp.einsum("ecd,edf->ecf", slots, lp["w1"]), 0)
    out = jnp.einsum("ecf,efd->ecd", hidden, lp["w2"])
    return jnp.einsum("tec,ecd->td", route.combine, out).astype(flat.dtype)


def loss_local(params, tokens, labels, cfg: Config, ax: Axes):
    """Summed next-token CE over local tokens + local count (caller
    normalizes after cross-shard psum)."""
    logits = forward_local(params, tokens, cfg, ax)
    with jax.named_scope("head_loss"):
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, labels[..., None], axis=-1)[..., 0]
        mask = (labels >= 0).astype(jnp.float32)
        nll = ((logz - gold) * mask).sum()
        return nll, mask.sum()


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
