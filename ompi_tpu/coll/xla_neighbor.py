"""coll/xla neighborhood collectives — device-executed halo exchange.

Reference: the coll framework's neighborhood slots
(ompi/mca/coll/coll.h:600-618, implemented linearly in coll/basic over
p2p). Here a topology comm's adjacency compiles to a static schedule
of ``lax.ppermute`` rounds, so a cart/graph comm's neighbor exchange
on jax arrays runs entirely on the device plane (ICI on TPU) — the
last host-staging seam in the device path (r3 VERDICT missing #5).

Schedule construction (host side, once per (comm, shape)): the
directed edge set {(src, dst)} from the topology is greedily
edge-colored so every color class is a partial matching — unique
sources AND unique targets — which is exactly XLA CollectivePermute's
contract. One ppermute per color; a bounded-degree stencil needs
~degree rounds regardless of comm size (König: Δ colors suffice for
bipartite multigraphs; the greedy bound is < 2Δ).

Semantics on immutable arrays: results are NEW arrays with
(slot, *shape) leading-row layout matching the host recvbuf layout;
PROC_NULL slots (open cart boundaries) hold zeros (the host path
leaves those recv slots untouched — a template cannot be "untouched"
when the result is a fresh array). Ragged degrees (general graphs)
are padded to the max degree inside the compiled program and sliced
back per rank on exit.
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np

from ompi_tpu import errors
from ompi_tpu.coll import xla as X
from ompi_tpu.pml.request import PROC_NULL


class _GlobalAdj:
    """Global adjacency view for topologies that only know their own
    rank's lists (DistGraphTopo): one cached allgather round supplies
    every rank's (in, out) lists — the metadata analog of the modex
    (cached like _scatter_meta; dist-graph adjacency is immutable
    after creation, so the cache can never go stale)."""

    def __init__(self, ins, outs):
        self._ins, self._outs = ins, outs

    def in_neighbors(self, r):
        return self._ins[r]

    def out_neighbors(self, r):
        return self._outs[r]


def _global_topo(comm):
    topo = comm.topo
    if topo.kind != "dist_graph":
        return topo  # cart/graph topologies answer for any rank
    adj = getattr(comm, "_coll_xla_nbr_adj", None)
    if adj is None:
        gathered = comm.allgather(
            (list(topo.in_neighbors(comm.rank)),
             list(topo.out_neighbors(comm.rank))))
        adj = comm._coll_xla_nbr_adj = _GlobalAdj(
            [g[0] for g in gathered], [g[1] for g in gathered])
    return adj


def _edges_allgather(topo, n: int):
    """Directed edges (src, dst, dst_slot) — dst receives src's whole
    sendbuf into row dst_slot (its position in dst's in-neighbor
    list, PROC_NULL slots kept as holes)."""
    edges = []
    max_in = 0
    for d in range(n):
        nbrs = topo.in_neighbors(d)
        max_in = max(max_in, len(nbrs))
        for slot, s in enumerate(nbrs):
            if s != PROC_NULL:
                edges.append((s, d, slot))
    return edges, max_in


def _edges_alltoall(topo, n: int):
    """Directed edges (src, dst, src_slot, dst_slot): src sends row
    src_slot (its position of dst in src's out list) into dst's row
    dst_slot.

    Pairing: cartesian slots pair conjugate (in-slot j <-> the peer's
    out-slot j^1 — the (d,-1) in-edge IS the peer's (d,+1) out-edge;
    required for the periodic size-2 degenerate dim, same rule as
    basic's conjugate tags); graph/dist-graph multi-edges pair
    occurrence-by-occurrence (the standard's posted-order matching)."""
    is_cart = getattr(topo, "kind", None) == "cart"
    # per (s, d): FIFO of src slots where s lists d outbound
    out_slots = {}
    max_out = 0
    for s in range(n):
        outs = topo.out_neighbors(s)
        max_out = max(max_out, len(outs))
        for j, d in enumerate(outs):
            if d != PROC_NULL:
                out_slots.setdefault((s, d), []).append(j)
    edges = []
    max_in = 0
    for d in range(n):
        ins = topo.in_neighbors(d)
        max_in = max(max_in, len(ins))
        for slot, s in enumerate(ins):
            if s == PROC_NULL:
                continue
            if is_cart:
                edges.append((s, d, slot ^ 1, slot))
                continue
            q = out_slots.get((s, d))
            if not q:
                raise errors.MPIError(
                    errors.ERR_TOPOLOGY,
                    f"inconsistent topology: rank {d} lists {s} as an "
                    f"in-neighbor more times than {s} lists {d} "
                    "outbound")
            edges.append((s, d, q.pop(0), slot))
    return edges, max_in, max_out


def _color(edges) -> List[list]:
    """Greedy partition of directed edges into partial matchings
    (unique src + unique dst per round) — each round is one valid
    CollectivePermute."""
    remaining = list(edges)
    rounds = []
    while remaining:
        used_s, used_d, rnd, rest = set(), set(), [], []
        for e in remaining:
            if e[0] in used_s or e[1] in used_d:
                rest.append(e)
            else:
                used_s.add(e[0])
                used_d.add(e[1])
                rnd.append(e)
        rounds.append(rnd)
        remaining = rest
    return rounds


def _place(out, recvd, slot_np, tgt_np, ctx):
    """Place this round's received block into each target's slot row
    (non-targets keep `out`)."""
    import jax.numpy as jnp
    from jax import lax

    me = lax.axis_index(X.AXIS)
    slot = jnp.asarray(slot_np)[me]
    is_tgt = jnp.asarray(tgt_np)[me]
    upd = lax.dynamic_update_slice_in_dim(out, recvd[None], slot,
                                          axis=0)
    return jnp.where(is_tgt, upd, out)


def _per_peer(topo, rank: int, nbytes: float):
    """Send-side traffic by graph edge, not an algo model: ``nbytes``
    to every (non-PROC_NULL) out-neighbor, once per edge."""
    per = {}
    for p in topo.out_neighbors(rank):
        if p != PROC_NULL:
            per[p] = per.get(p, 0.0) + nbytes
    return per


@X._slot("neighbor_allgather")
def neighbor_allgather_dev(comm, sendbuf):
    """Device MPI_Neighbor_allgather: returns (n_in, *sendbuf.shape)
    — row k is in-neighbor k's sendbuf (zeros for PROC_NULL slots)."""
    from jax import lax

    topo = _global_topo(comm)
    ctx = X._ctx(comm)
    n = ctx.n
    my_rows = len(topo.in_neighbors(comm.rank))

    def build():
        import jax.numpy as jnp

        edges, max_in = _edges_allgather(topo, n)
        rounds = _color(edges)
        # per round: ppermute pairs + (slot, is-target) lookup tables
        plan = []
        for rnd in rounds:
            slot_np = np.zeros(n, np.int32)
            tgt_np = np.zeros(n, bool)
            for s, d, slot in rnd:
                slot_np[d] = slot
                tgt_np[d] = True
            plan.append(([(s, d) for s, d, _ in rnd], slot_np, tgt_np))

        def body(a):
            x = a[0]
            out = jnp.zeros((max_in,) + x.shape, x.dtype)
            for perm, slot_np, tgt_np in plan:
                recvd = lax.ppermute(x, X.AXIS, perm=perm)
                out = _place(out, recvd, slot_np, tgt_np, ctx)
            return out

        return ctx.smap(body, out_varying=True)

    fn = ctx.compiled(X._key(sendbuf, "neighbor_allgather"), build)
    # the full sendbuf goes to every out-neighbor
    out = X._run("neighbor_allgather", comm, sendbuf,
                 ctx.bind(fn, sendbuf),
                 per_peer=lambda: _per_peer(topo, comm.rank,
                                            sendbuf.nbytes))
    return out[:my_rows]


@X._slot("neighbor_alltoall")
def neighbor_alltoall_dev(comm, sendbuf):
    """Device MPI_Neighbor_alltoall: ``sendbuf`` rows are per-out-
    neighbor blocks (row j to out-neighbor j); returns (n_in, *blk)
    with row k from in-neighbor k. PROC_NULL rows send nowhere /
    stay zero."""
    import jax.numpy as jnp
    from jax import lax

    topo = _global_topo(comm)
    ctx = X._ctx(comm)
    n = ctx.n
    my_out = len(topo.out_neighbors(comm.rank))
    my_in = len(topo.in_neighbors(comm.rank))
    if sendbuf.shape[0] != my_out:
        raise errors.MPIError(
            errors.ERR_COUNT,
            f"neighbor_alltoall: sendbuf dim0 {sendbuf.shape[0]} != "
            f"out-degree {my_out}")
    payload = sendbuf  # as the caller gave it, before the padding
    edges, max_in, max_out = _edges_alltoall(topo, n)
    # SPMD needs uniform operand shapes: pad ragged out-degrees
    if sendbuf.shape[0] < max_out:
        pad = jnp.zeros((max_out - sendbuf.shape[0],)
                        + sendbuf.shape[1:], sendbuf.dtype)
        sendbuf = jnp.concatenate([sendbuf, pad]) if sendbuf.shape[0] \
            else jnp.zeros((max_out,) + sendbuf.shape[1:],
                           sendbuf.dtype)

    def build():
        rounds = _color(edges)
        plan = []
        for rnd in rounds:
            srow_np = np.zeros(n, np.int32)
            slot_np = np.zeros(n, np.int32)
            tgt_np = np.zeros(n, bool)
            for s, d, srow, slot in rnd:
                srow_np[s] = srow
                slot_np[d] = slot
                tgt_np[d] = True
            plan.append(([(s, d) for s, d, _, _ in rnd],
                         srow_np, slot_np, tgt_np))

        def body(a):
            x = a[0]  # (max_out, *blk)
            blk_shape = x.shape[1:]
            out = jnp.zeros((max_in,) + blk_shape, x.dtype)
            me = lax.axis_index(X.AXIS)
            for perm, srow_np, slot_np, tgt_np in plan:
                srow = jnp.asarray(srow_np)[me]
                blk = lax.dynamic_index_in_dim(x, srow, axis=0,
                                               keepdims=False)
                recvd = lax.ppermute(blk, X.AXIS, perm=perm)
                out = _place(out, recvd, slot_np, tgt_np, ctx)
            return out

        return ctx.smap(body, out_varying=True)

    fn = ctx.compiled(X._key(sendbuf, "neighbor_alltoall"), build)
    # one sendbuf row per out-neighbor (PROC_NULL rows go nowhere)
    out = X._run("neighbor_alltoall", comm, payload,
                 ctx.bind(fn, sendbuf),
                 per_peer=lambda: _per_peer(
                     topo, comm.rank,
                     payload.nbytes / my_out if my_out else 0.0))
    return out[:my_in]


def slots(comm):
    """Neighborhood device slots — installed only on topology comms
    (the reference installs neighborhood functions at topo-comm
    creation, coll.h:600-618)."""
    if getattr(comm, "topo", None) is None:
        return {}
    return {
        "neighbor_allgather_dev": neighbor_allgather_dev,
        "neighbor_alltoall_dev": neighbor_alltoall_dev,
    }
