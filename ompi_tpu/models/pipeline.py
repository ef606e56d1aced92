"""Pipeline parallelism — microbatched stage pipeline over ppermute.

Reference analog: the partitioned p2p machinery (ompi/mca/part/part.h:
124-185, part/persist) that SURVEY.md §2.10 maps to pipeline-parallel
stage handoffs; the host-plane face is ompi_tpu.mpi's
Psend_init/Precv_init. Here the device plane implements the schedule
itself, TPU-first: layers are stacked on a leading dim sharded over the
``pp`` mesh axis (each stage holds n_layers/pp of them), activations
hand off stage-to-stage with ``lax.ppermute``, and the whole schedule
is a ``lax.scan`` over n_micro + pp - 1 ticks (GPipe fill/drain).

Why scan+ppermute rather than a hand-written 1F1B executor: under XLA
the backward pass of the scanned pipeline interleaves with forward
recomputation per microbatch automatically (the compiler schedules
collective-permute DMA alongside stage compute), which recovers the
1F1B overlap without data-dependent control flow; ``jax.checkpoint``
on the stage body bounds activation memory to one microbatch per
in-flight tick, the same bound 1F1B targets.

Constraints: homogeneous layers (all dense or all MoE — stacking
requires one pytree structure), n_layers % pp == 0, global batch
divisible by n_micro.

The host-plane face of the same idea lives at the bottom of this
module: :func:`stage_handoff_send` / :func:`stage_handoff_recv` wrap
the part/ subsystem's Psend_init/Precv_init with one partition per
microbatch, for pipelines whose stages run as separate MPI ranks
(heterogeneous stages the stacked scan cannot express) — the producer
``Pready``-s microbatch i the moment its stage compute finishes, the
consumer ``Parrived``-polls and starts on it while later microbatches
are still in flight.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ompi_tpu.util import jaxcompat

from ompi_tpu.models import transformer as tfm


def stack_layers(params: Dict) -> Dict:
    """layers list -> one stacked pytree with leading layer dim
    (required for sharding layers over the pp axis)."""
    layers = params["layers"]
    stacked = jax.tree.map(lambda *xs: np.stack(xs), *layers)
    out = {k: v for k, v in params.items() if k != "layers"}
    out["layers"] = stacked
    return out


def stacked_param_specs(cfg: tfm.Config, ax: tfm.Axes):
    """param_specs with the layer dim of every stacked layer param
    sharded over pp."""
    from jax.sharding import PartitionSpec as P

    base = tfm.param_specs(cfg, ax)
    one = base["layers"][0]
    pp = ax.pp

    def prepend(spec):
        entries = tuple(spec) if spec is not None else ()
        return P(pp, *entries)

    stacked = jax.tree.map(prepend, one,
                           is_leaf=lambda x: isinstance(x, type(P())))
    out = {k: v for k, v in base.items() if k != "layers"}
    out["layers"] = stacked
    return out


def _stage_apply(stage_layers, h, cfg, ax, is_moe):
    """Run this stage's local layers (scan over the local layer dim)."""

    def body(carry, lp):
        return tfm.layer_forward(lp, carry, cfg, ax, is_moe), None

    # checkpoint: recompute stage activations in backward — bounds
    # pipeline memory to ~one microbatch per tick (the 1F1B bound)
    h, _ = lax.scan(jax.checkpoint(body), h, stage_layers)
    return h


def pipeline_forward(params, tokens, cfg: tfm.Config, ax: tfm.Axes,
                     n_micro: int):
    """Microbatched pipelined forward on local shards (inside
    shard_map). tokens: [B_local, T_local] -> f32 logits [B_local,
    T_local, vocab] valid on the LAST stage (other stages return
    zeros — mask downstream with `is_last_stage`).
    """
    assert ax.pp, "pipeline_forward requires a pp axis"
    pp = jaxcompat.axis_size(ax.pp)
    stage = lax.axis_index(ax.pp)
    dt = cfg.dtype
    b, t = tokens.shape
    assert b % n_micro == 0, f"batch {b} not divisible by {n_micro}"
    mb = b // n_micro
    is_moe = cfg.moe_every == 1  # homogeneous check in make_train_step

    # embedding (params replicated over pp; only stage 0's result is
    # consumed — the ppermute ring discards the rest)
    t_off = lax.axis_index(ax.sp) * t if ax.sp else 0
    h = params["embed"].astype(dt)[tokens]
    pos = lax.dynamic_slice_in_dim(params["pos"], t_off, t, axis=0) \
        if ax.sp else params["pos"][:t]
    h = h + pos.astype(dt)[None]
    micro = h.reshape(n_micro, mb, t, cfg.d_model)

    n_ticks = n_micro + pp - 1
    fwd = [(i, (i + 1) % pp) for i in range(pp)]  # stage i -> i+1

    def tick(carry, i):
        state, out = carry
        # stage 0 injects microbatch i (draining ticks feed zeros that
        # nothing consumes); others take the handed-off activation
        inject = jnp.where(i < n_micro, i, n_micro - 1)
        x0 = lax.dynamic_index_in_dim(micro, inject, keepdims=False)
        x = jnp.where(stage == 0, x0, state)
        y = _stage_apply(params["layers"], x, cfg, ax, is_moe)
        # last stage banks finished microbatch i-(pp-1)
        done_idx = jnp.clip(i - (pp - 1), 0, n_micro - 1)
        bank = (stage == pp - 1) & (i >= pp - 1)
        out = jnp.where(
            bank,
            lax.dynamic_update_index_in_dim(out, y, done_idx, axis=0),
            out)
        state = lax.ppermute(y, ax.pp, perm=fwd)
        return (state, out), None

    state0 = jnp.zeros((mb, t, cfg.d_model), dt)
    out0 = jnp.zeros((n_micro, mb, t, cfg.d_model), dt)
    (_, outs), _ = lax.scan(tick, (state0, out0),
                            jnp.arange(n_ticks))
    hfin = outs.reshape(b, t, cfg.d_model)

    hfin = tfm._ln(hfin.astype(jnp.float32), params["ln_f"]["g"],
                   params["ln_f"]["b"])
    logits = jnp.einsum("btd,vd->btv", hfin.astype(dt),
                        params["embed"].astype(dt),
                        preferred_element_type=jnp.float32)
    return logits


def make_pp_train_step(cfg: tfm.Config, ax: tfm.Axes, specs,
                       n_micro: int, lr: float = 1e-2):
    """(stacked_params, tokens, labels) -> (new_params, loss); call
    inside shard_map over a mesh with the pp axis. Loss/grads are valid
    on every device (loss terms are psummed over pp from the last
    stage; replicated-param grads are psummed over pp since stages
    contribute different terms)."""
    if cfg.moe_every not in (0, 1):
        raise ValueError(
            "pipeline stages must be homogeneous: moe_every must be 0 "
            "(all dense) or 1 (all MoE) so layers stack")
    if ax.pp is None:
        raise ValueError("make_pp_train_step requires ax.pp")
    if (cfg.pos != "learned" or cfg.norm != "layernorm"
            or not cfg.tie_head or cfg.router_aux_weight
            or cfg.router_z_weight):
        raise NotImplementedError(
            "the pipeline's first and last stage compute learned "
            "positions, a final LayerNorm and the tied head, and its "
            "stages collect no router loss: RoPE, RMSNorm, an untied "
            "head and the router losses under ax.pp are ROADMAP R3")
    # stacked version of grad_extra_axes (homogeneous layers: every
    # layer's extra-psum tree is identical, so the first one stands in
    # for the stacked dim) — drops the tp psum on the MoE router wg
    # gradient otherwise
    base_extra = tfm.grad_extra_axes(cfg, ax)
    extra = {k: v for k, v in base_extra.items() if k != "layers"}
    extra["layers"] = base_extra["layers"][0]

    def step(params, tokens, labels):
        def loss_fn(p):
            logits = pipeline_forward(p, tokens, cfg, ax, n_micro)
            pp = jaxcompat.axis_size(ax.pp)
            last = (lax.axis_index(ax.pp) == pp - 1).astype(jnp.float32)
            mask = (labels >= 0).astype(jnp.float32) * last
            return tfm._token_nll(logits, labels, mask,
                                  cfg.dtype), mask.sum()

        (nll, cnt), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        axes = tuple(a for a in (ax.dp, ax.sp, ax.ep, ax.pp) if a)
        nll = lax.psum(nll, axes)
        cnt = lax.psum(cnt, axes)
        loss = nll / cnt
        grads = tfm.grad_sync(grads, specs, ax, extra)
        # replicated params (embed/pos/ln_f) get contributions from
        # different stages (stage 0: embedding; last: head) — sum them.
        # pp-sharded layer params are complete per stage already.
        def pp_sync(g, spec):
            entries = tuple(spec) if spec is not None else ()
            flat = set()
            for e in entries:
                if isinstance(e, tuple):
                    flat.update(e)
                elif e is not None:
                    flat.add(e)
            return g if ax.pp in flat else lax.psum(g, ax.pp)

        g_leaves, treedef = jax.tree.flatten(grads)
        s_leaves = treedef.flatten_up_to(specs)
        grads = jax.tree.unflatten(
            treedef, [pp_sync(g, s)
                      for g, s in zip(g_leaves, s_leaves)])
        scale = lr / cnt
        new_params = tfm.sgd_update(params, grads, scale)
        return new_params, loss

    return step


# ---------------------------------------------------------------------------
# host-plane stage handoff via partitioned p2p (ompi_tpu.part)


def stage_handoff_send(comm, acts, n_micro: int, dest: int,
                       tag: int = 11):
    """Partitioned send of a stacked microbatch activation buffer
    [n_micro, ...] to the next pipeline stage: one partition per
    microbatch. Returns the STARTED PartitionedSendRequest — call
    ``req.Pready(i)`` as each microbatch's stage compute completes
    (its transfer then overlaps microbatch i+1's compute) and
    ``req.wait()`` at the end of the pipeline tick. The request is
    persistent: re-``start()`` it next tick, same pairing."""
    acts = np.ascontiguousarray(acts)
    if acts.shape[0] != n_micro:
        raise ValueError(
            f"stage_handoff_send: leading dim {acts.shape[0]} must "
            f"be n_micro={n_micro} (one partition per microbatch)")
    req = comm.Psend_init(acts, n_micro, dest, tag)
    req.start()
    return req


def stage_handoff_recv(comm, buf, n_micro: int, source: int,
                       tag: int = 11):
    """Receiving side of :func:`stage_handoff_send`: posts all
    microbatch partition receives into ``buf`` ([n_micro, ...],
    C-contiguous — partitions alias it) and returns the STARTED
    PartitionedRecvRequest. Poll ``req.Parrived(i)`` and start this
    stage's compute on microbatch i without waiting for the rest."""
    if buf.shape[0] != n_micro:
        raise ValueError(
            f"stage_handoff_recv: leading dim {buf.shape[0]} must "
            f"be n_micro={n_micro} (one partition per microbatch)")
    req = comm.Precv_init(buf, n_micro, source, tag)
    req.start()
    return req
