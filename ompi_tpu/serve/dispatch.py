"""Capacity-factor dispatch policies over the EP alltoall path.

The training router (:func:`ompi_tpu.ops.moe.top1_routing`) is
Switch-Transformer top-1 with static capacity: every token past an
expert's ``C`` slots is silently zeroed. Under serving skew that is a
*policy decision*, and this module makes it explicit — three policies,
each ONE compiled program per (policy, mesh, capacity) riding the
per-comm ``_Ctx`` caches of :mod:`ompi_tpu.coll.xla`:

``drop``
    Exactly the training path (bit-identical outputs — the program
    embeds the same ``top1_routing`` + ``ep_apply`` op sequence), but
    the overflow is METERED: the program returns a stats vector and
    the host leg feeds ``serve_dropped_tokens`` + the expert-load
    heatmap.

``reroute``
    Overflow tokens are re-dispatched to the least-loaded experts in
    the SAME slice (GShard's second-expert idea, restricted to free
    capacity): experts sort by primary load ascending, each overflow
    token takes the next free slot in that order, its combine weight
    is its gate for the expert it actually landed on. Token-conserving
    by construction — the j-th overflow token maps to the j-th free
    slot, and a token never holds two slots.

``dcn_overflow``
    Topology-aware: the primary program runs drop over the hier
    plane's ICI level only (slices are expert REPLICAS, so
    ``E_total = E_local * n_ici``); overflow tokens are then shipped
    to the neighbor slice over the DCN level via two
    ``alltoallv_dev`` legs (token rows forward, activations back),
    served from the replica's free capacity, and added back at their
    positions. The ``serve_dcn_budget_bytes`` cvar bounds the shipped
    bytes per dispatch — overflow past the budget drops, which is the
    link-cost-aware drop decision the flat policies cannot make.

An unknown policy name raises ``MPIError(ERR_ARG)`` at the FIRST
dispatch and is never cached (the coll/hier bad-split contract: a
config typo keeps surfacing instead of silently serving drop).
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from ompi_tpu import errors
from ompi_tpu.coll import hier as _hier, xla as _xla
from ompi_tpu.core import cvar, pvar
from ompi_tpu.monitoring import matrix as _mon
from ompi_tpu.ops import moe
from ompi_tpu.parallel import hierarchical as H
from ompi_tpu.util import jaxcompat

#: dispatch policy names, in documentation order
POLICIES = ("drop", "reroute", "dcn_overflow")

# registered WITHOUT choices= on purpose (the coll_hier_dcn_dtype
# precedent): serve policy/config errors surface at dispatch time via
# MPIError(ERR_ARG), not at mca-parse time
_budget_var = cvar.register(
    "serve_dcn_budget_bytes", 0, int,
    help="Per-dispatch byte budget for the dcn_overflow policy's "
         "remote leg (forward token rows + returned activations, "
         "f32 wire). Overflow tokens past the budget are dropped — "
         "the link-cost-aware drop decision. 0 [default] ships every "
         "overflow token.", level=5)


def _softmax(logits):
    """The exact gate formula of ``top1_routing`` (shared so the
    dcn_overflow program's remote combine weight is bit-consistent
    with the local one)."""
    import jax.numpy as jnp
    from jax import lax

    g = logits.astype(jnp.float32)
    g = jnp.exp(g - lax.stop_gradient(g.max(-1, keepdims=True)))
    return g / g.sum(-1, keepdims=True)


def reroute_routing(logits, capacity: int):
    """Top-1 routing with overflow re-dispatched to free capacity.

    Returns ``(MoEDispatch, rerouted)``. All shapes static: overflow
    tokens are ranked by arrival (j = their index among overflow),
    experts by primary load ascending (stable argsort), and the j-th
    overflow token takes the j-th free slot in that expert order —
    ``searchsorted`` over the cumulative free-slot counts finds the
    landing expert without any loop. Tokens past the total free
    capacity stay dropped (capacity rounding can make E*C < T)."""
    import jax.numpy as jnp

    t, e = logits.shape
    gates = _softmax(logits)
    expert = jnp.argmax(gates, axis=-1)                   # [T]
    onehot = jnp.eye(e, dtype=jnp.float32)[expert]        # [T,E]
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0       # [T,E]
    keep = (pos >= 0) & (pos < capacity)
    pos_c = jnp.clip(pos, 0, capacity - 1).astype(jnp.int32)
    dispatch = (jnp.eye(capacity, dtype=jnp.float32)[pos_c]
                * keep[..., None])                        # [T,E,C]
    gate1 = (gates * onehot).sum(-1)                      # [T]
    combine = dispatch * gate1[:, None, None]
    counts = onehot.sum(0).astype(jnp.int32)              # [E]

    # --- the reroute leg: j-th overflow token -> j-th free slot -----
    used = jnp.minimum(counts, capacity)                  # [E]
    free = capacity - used                                # [E]
    order = jnp.argsort(used)                             # least-loaded first
    cfree = jnp.cumsum(free[order])                       # [E]
    total_free = cfree[-1]
    over = 1 - (dispatch.sum((1, 2)) > 0.5).astype(jnp.int32)  # [T]
    j = jnp.cumsum(over) * over - 1                       # [T], -1 = kept
    valid = (over > 0) & (j >= 0) & (j < total_free)
    k = jnp.clip(jnp.searchsorted(cfree, j, side="right"), 0, e - 1)
    new_e = order[k]                                      # [T]
    offset = jnp.where(k > 0, cfree[jnp.maximum(k - 1, 0)], 0)
    slot = jnp.clip(used[new_e] + (j - offset),
                    0, capacity - 1).astype(jnp.int32)
    oh_new = (jnp.eye(e, dtype=jnp.float32)[new_e]
              * valid.astype(jnp.float32)[:, None])       # [T,E]
    disp_new = (jnp.eye(capacity, dtype=jnp.float32)[slot][:, None, :]
                * oh_new[..., None])                      # [T,E,C]
    gate_new = (gates * oh_new).sum(-1)                   # [T]
    dispatch = dispatch + disp_new
    combine = combine + disp_new * gate_new[:, None, None]
    rerouted = valid.sum().astype(jnp.int32)
    dropped = (over.sum() - rerouted).astype(jnp.int32)
    return moe.MoEDispatch(combine=combine, dispatch=dispatch,
                           counts=counts, dropped=dropped), rerouted


def routed_ffn(x, wg, w1, w2, axis: str, capacity_factor: float,
               policy: str):
    """The traced policy layer: ``moe_ffn`` with explicit overflow
    handling and a stats tail. Usable inside any shard_map (the bench
    drives it on an in-process mesh); :class:`Dispatcher` compiles it
    over a communicator's mesh. Returns ``(out [T,D], stats)`` where
    stats is ``int32 [4 + E]``: kept, rerouted, dropped,
    multi-assigned tokens (conservation probe, always 0), then the
    per-expert routed histogram (pre-capacity demand — what the
    hot-expert verdict reads)."""
    import jax.numpy as jnp

    if policy not in ("drop", "reroute"):
        raise errors.MPIError(
            errors.ERR_ARG,
            f"routed_ffn: policy {policy!r} not traceable here "
            "(expected 'drop' or 'reroute'; 'dcn_overflow' needs the "
            "Dispatcher's host legs)")
    n = jaxcompat.axis_size(axis)
    t = x.shape[0]
    e_total = w1.shape[0] * n
    cap = max(int(capacity_factor * t / e_total), 1)
    logits = x @ wg
    if policy == "drop":
        route = moe.top1_routing(logits, cap)
        rerouted = jnp.int32(0)
    else:
        route, rerouted = reroute_routing(logits, cap)
    out = moe.ep_apply(route, x, w1, w2, axis)
    multi = (route.dispatch.sum((1, 2)) > 1.5).sum().astype(jnp.int32)
    kept = (t - route.dropped - rerouted).astype(jnp.int32)
    stats = jnp.concatenate([
        jnp.stack([kept, rerouted, route.dropped, multi]), route.counts])
    return out, stats


class Dispatcher:
    """One serving MoE layer bound to a communicator.

    ``wg`` is the router ``[D, E_total]`` (replicated), ``w1``/``w2``
    this rank's experts ``[E_local, D, F]`` / ``[E_local, F, D]``.
    Under the flat policies ``E_total = E_local * comm.size``; under
    ``dcn_overflow`` the hier grid's slices are expert replicas, so
    ``E_total = E_local * n_ici`` and every slice passes the same
    logical weights. ``dispatch(x)`` returns ``(out, info)`` with
    info the host-readable stats dict; every dispatch feeds the
    ``serve_*`` pvars and the monitoring ``[serve]`` section."""

    def __init__(self, comm, wg, w1, w2, *,
                 capacity_factor: float = 1.25,
                 policy: str = "drop") -> None:
        self.comm = comm
        self.wg, self.w1, self.w2 = wg, w1, w2
        self.capacity_factor = float(capacity_factor)
        self.policy = policy
        self._staged: dict = {}

    # -- staged (device-resident, immutable) weight globals ----------
    def _weights(self, ctx, mode: str, sharding=None):
        st = self._staged.get(mode)
        if st is None:
            import jax.numpy as jnp

            st = self._staged[mode] = tuple(
                ctx.to_global(jnp.asarray(w, jnp.float32), sharding)
                for w in (self.wg, self.w1, self.w2))
        return st

    def dispatch(self, x):
        # policy validation BEFORE any cache/plan touch: a bad name
        # raises here on every call, never cached
        if self.policy not in POLICIES:
            raise errors.MPIError(
                errors.ERR_ARG,
                f"serve: unknown dispatch policy {self.policy!r} "
                f"(expected one of {POLICIES})")
        import jax.numpy as jnp

        x_j = jnp.asarray(x, jnp.float32)
        ctx = _xla._ctx(self.comm)
        if self.policy == "dcn_overflow":
            return self._dispatch_dcn(ctx, x_j)
        return self._dispatch_flat(ctx, x_j)

    __call__ = dispatch

    def _check_router(self, groups: int, scope: str) -> None:
        # a mismatched router width would otherwise surface as an
        # opaque reshape error inside the traced alltoall
        e_total = int(self.wg.shape[1])
        e_local = int(self.w1.shape[0])
        if e_total != e_local * groups:
            raise errors.MPIError(
                errors.ERR_ARG,
                f"serve: router wg has {e_total} experts but "
                f"{self.policy!r} dispatch expects e_local * {scope} "
                f"= {e_local} * {groups} = {e_local * groups}")

    # -- drop / reroute: one compiled program over the flat mesh ------
    def _dispatch_flat(self, ctx, x_j):
        self._check_router(self.comm.size, "comm.size")
        t = int(x_j.shape[0])
        cf, policy = self.capacity_factor, self.policy
        key = _xla._key(x_j, "serve_ffn", policy, cf,
                        int(self.w1.shape[0]))

        def build():
            def body(xb, wgb, w1b, w2b):
                return routed_ffn(xb[0], wgb[0], w1b[0], w2b[0],
                                  axis=_xla.AXIS, capacity_factor=cf,
                                  policy=policy)
            return ctx.smap(body, out_varying=True)

        fn = ctx.compiled(key, build)
        gwg, gw1, gw2 = self._weights(ctx, "flat")
        out_g, stats_g = ctx.launch(fn, ctx.to_global(x_j),
                                    gwg, gw1, gw2)
        stats = np.array(ctx.my_shard(stats_g))
        return ctx.my_shard(out_g), self._meter(stats, t, 0, 0)

    # -- dcn_overflow: ICI-drop program + DCN host legs ---------------
    def _dispatch_dcn(self, ctx, x_j):
        plan = _hier._plan(self.comm)  # ERR_ARG on bad split, uncached
        if plan is None:
            raise errors.MPIError(
                errors.ERR_ARG,
                "serve: policy 'dcn_overflow' needs a hier grid for "
                "this comm — set coll_hier_split (e.g. '2x2') or run "
                "across slices")
        import jax.numpy as jnp

        t, d = (int(s) for s in x_j.shape)
        e_local = int(self.w1.shape[0])
        n_ici, n_dcn = plan.n_ici, plan.n_dcn
        self._check_router(n_ici, "n_ici (slices are replicas)")
        cap = max(int(self.capacity_factor * t / (e_local * n_ici)), 1)
        key = _xla._key(x_j, "serve_ffn_dcn", self.capacity_factor,
                        n_dcn, n_ici, e_local)

        def build():
            def body(xb, wgb, w1b, w2b):
                x_, wg_ = xb[0], wgb[0]
                logits = x_ @ wg_
                route = moe.top1_routing(logits, cap)
                out = moe.ep_apply(route, x_, w1b[0], w2b[0],
                                   H.ICI_AXIS)
                assigned = route.dispatch.sum((1, 2))
                kept_tok = (assigned > 0.5).astype(jnp.int32)   # [T]
                picked = jnp.argmax(logits, -1).astype(jnp.int32)
                gate1 = _softmax(logits).max(-1)                # [T]
                multi = (assigned > 1.5).sum().astype(jnp.int32)
                stats = jnp.concatenate([
                    jnp.stack([kept_tok.sum().astype(jnp.int32),
                               jnp.int32(0), route.dropped, multi]),
                    route.counts])
                return out, stats, kept_tok, picked, gate1
            return _hier._smap(ctx, plan, body, out_varying=True)

        fn = ctx.compiled(key, build)
        gwg, gw1, gw2 = self._weights(ctx, "dcn", plan.sharding)
        out_g, stats_g, kept_g, picked_g, gate_g = ctx.launch(
            fn, ctx.to_global(x_j, plan.sharding), gwg, gw1, gw2)
        out = np.array(ctx.my_shard(out_g))
        stats = np.array(ctx.my_shard(stats_g))
        kept_tok = np.asarray(ctx.my_shard(kept_g))
        picked = np.asarray(ctx.my_shard(picked_g))
        gate1 = np.asarray(ctx.my_shard(gate_g))

        # --- DCN leg (host): ship overflow rows to the neighbor
        # slice's replica of the picked expert. Every rank runs the
        # SAME collective sequence (allgather_obj + 2 alltoallv) even
        # with zero overflow — these are collectives.
        me, size = self.comm.rank, self.comm.size
        d_me = me // n_ici
        over_idx = np.nonzero(kept_tok == 0)[0]
        row_elems = d + 2                      # x row, e_rel, gate
        cost = (row_elems + d) * 4             # fwd + return, f32
        budget = int(_budget_var.get())
        n_ship = len(over_idx)
        if budget > 0:
            n_ship = min(n_ship, budget // cost)
        shipped = over_idx[:n_ship]
        e_rel = picked[shipped] % e_local
        owner_ici = picked[shipped] // e_local
        dst = ((d_me + 1) % n_dcn) * n_ici + owner_ici
        order = np.argsort(dst, kind="stable")
        shipped, dst, e_rel = shipped[order], dst[order], e_rel[order]
        x_np = np.asarray(x_j)
        payload = np.zeros((len(shipped), row_elems), np.float32)
        payload[:, :d] = x_np[shipped]
        payload[:, d] = e_rel
        payload[:, d + 1] = gate1[shipped]
        scounts = tuple(
            int(c) for c in np.bincount(dst, minlength=size))
        mat = self.comm.coll.allgather_obj(self.comm, scounts)
        rcounts = tuple(int(mat[s][me]) for s in range(size))
        fwd = np.asarray(_xla.alltoallv_dev(
            self.comm, jnp.asarray(payload), scounts, rcounts,
            max_count=t, _expert_tokens=False))
        # serve the visitors from this rank's replica (eager — the
        # remote leg is the slow path by design; budget bounds it)
        xs, er = fwd[:, :d], fwd[:, d].astype(np.int64)
        w1l = np.asarray(self.w1, np.float32)
        w2l = np.asarray(self.w2, np.float32)
        h = np.maximum(np.einsum("kd,kdf->kf", xs, w1l[er]), 0.0)
        y = (np.einsum("kf,kfd->kd", h, w2l[er])
             * fwd[:, d + 1][:, None]).astype(np.float32)
        back = np.asarray(_xla.alltoallv_dev(
            self.comm, jnp.asarray(y), rcounts, scounts,
            max_count=t, _expert_tokens=False))
        # return rows arrive grouped by serving rank ascending ==
        # exactly my dst-sorted payload order
        if len(shipped):
            out[shipped] += back
        dcn_bytes = int(payload.nbytes) + len(shipped) * d * 4
        stats[2] -= len(shipped)  # DCN-served tokens are not dropped
        info = self._meter(stats, t, len(shipped), dcn_bytes)
        tm = _mon.TRAFFIC
        if tm is not None:
            tm.hier("serve_overflow", 0.0, float(dcn_bytes))
        return jnp.asarray(out), info

    # -- stats -> pvars / monitoring ----------------------------------
    def _meter(self, stats, tokens: int, dcn_tokens: int,
               dcn_bytes: int) -> dict:
        kept, rerouted, dropped, multi = (int(v) for v in stats[:4])
        counts = [int(c) for c in stats[4:]]
        pvar.record("serve_tokens", tokens)
        if dropped:
            pvar.record("serve_dropped_tokens", dropped)
        if rerouted:
            pvar.record("serve_rerouted_tokens", rerouted)
        if dcn_tokens:
            pvar.record("serve_dcn_overflow_tokens", dcn_tokens)
        if dcn_bytes:
            pvar.record("serve_dcn_overflow_bytes", dcn_bytes)
        from ompi_tpu import monitoring as _monitoring

        _monitoring.expert_load(counts)
        tm = _mon.TRAFFIC
        if tm is not None:
            tm.serve_event(self.policy, tokens=tokens, kept=kept,
                           rerouted=rerouted, dropped=dropped,
                           dcn_tokens=dcn_tokens, dcn_bytes=dcn_bytes)
        return {"policy": self.policy, "tokens": tokens, "kept": kept,
                "rerouted": rerouted, "dropped": dropped,
                "multi_assigned": multi, "dcn_tokens": dcn_tokens,
                "dcn_bytes": dcn_bytes, "counts": counts}
