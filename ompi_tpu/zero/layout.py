"""zero/layout — pad-and-shard bucket layout for sharded data parallel.

The ZeRO family (Rajbhandari et al., SC'20) and FSDP (Zhao et al.,
VLDB'23) replace the replicated allreduce-everything step with a
reduce_scatter(grads) -> local shard update -> all_gather(params)
cycle, so every rank materializes O(1/n) optimizer state. The layout
problem is the same one the fused allreduce already solved with
:class:`~ompi_tpu.coll.xla._FusePlan` — dtype-segregated flat buckets
that close at the ``coll_xla_bucket_bytes`` threshold — plus ONE new
constraint: a bucket's flat element count must divide evenly by the
comm size so the whole bucket lowers to a single tiled
``reduce_scatter``/``all_gather``. :class:`ZeroPlan` extends the fuse
plan with exactly that: per-bucket zero padding up to the next
multiple of n (``zero_pad_bytes`` pvar counts the waste).

:class:`ShardedState` is the per-rank view a `Reduce_scatter_multi`
returns and an `Allgather_multi` consumes: one 1-D shard array per
bucket (length ``padded/n``) plus the metadata to reassemble the
original pytree. Packing order is jax.tree.flatten leaf order — the
same order the fused allreduce concatenates, which is what keeps the
``deterministic='linear'`` fold bit-identical to the per-buffer path.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from ompi_tpu import errors, op as op_mod
from ompi_tpu.coll.xla import _FusePlan, _bucket_var, _fuse_metas
from ompi_tpu.core import pvar


class ZeroPlan(_FusePlan):
    """_FusePlan + per-bucket pad-to-comm-size layout.

    Inherits the dtype-segregated ``buckets`` (tuples of leaf indices;
    close-at-threshold rule, launch bound ceil(total/bucket_bytes) +
    n_dtypes) and adds, per bucket: flat element count, padded count
    (next multiple of ``n``), per-rank shard length, and dtype.
    Construction is deterministic in (metas, bucket_bytes, n) — two
    independent builders (the collective path and a local
    :meth:`ShardedState.from_full` pack) always agree on the layout.
    """

    __slots__ = ("n", "elems", "padded", "shard_elems", "dtypes",
                 "pad_bytes")

    def __init__(self, metas, bucket_bytes: int, n: int) -> None:
        super().__init__(metas, bucket_bytes)
        self.n = int(n)
        elems, padded, shard, dtypes = [], [], [], []
        pad_bytes = 0
        for idxs in self.buckets:
            dt = metas[idxs[0]][1]
            e = sum(_elems_of(metas[i][0]) for i in idxs)
            p = -(-e // self.n) * self.n  # ceil to multiple of n
            elems.append(e)
            padded.append(p)
            shard.append(p // self.n)
            dtypes.append(dt)
            pad_bytes += (p - e) * np.dtype(dt).itemsize
        self.elems = tuple(elems)
        self.padded = tuple(padded)
        self.shard_elems = tuple(shard)
        self.dtypes = tuple(dtypes)
        self.pad_bytes = pad_bytes


def _elems_of(shape) -> int:
    n = 1
    for s in shape:
        n *= int(s)
    return n


def plan_for(leaves, n: int, bucket_bytes: Optional[int] = None
             ) -> ZeroPlan:
    """The bucket/pad layout the zero collectives will use for these
    leaves on a size-``n`` comm (default bucket size: the
    ``coll_xla_bucket_bytes`` cvar). Local, deterministic — safe to
    call on any rank without agreement."""
    bb = int(_bucket_var.get()) if bucket_bytes is None \
        else int(bucket_bytes)
    return ZeroPlan(_fuse_metas(leaves), bb, n)


def layer_groups(template) -> Tuple[Tuple[str, Tuple[int, ...]], ...]:
    """Ordered (name, leaf_indices) layer grouping of a pytree — the
    unit of ZeRO stage-3 parameter streaming (gather a layer, use it,
    free it). Leaves group by the TOP component of their jax key path;
    when that component holds a sequence the second component joins
    the key, so ``layers[0]``, ``layers[1]``, … are separate layers
    (the transformer-block shape) while ``{"embed": …}`` stays one.
    Groups are ordered by first appearance in flatten order — the
    forward-pass order a prefetch scheduler runs ahead of.
    Deterministic in the treedef: every rank derives the same grouping
    locally, no agreement needed."""
    import jax

    paths, _ = jax.tree_util.tree_flatten_with_path(template)
    groups: dict = {}
    for i, (path, _leaf) in enumerate(paths):
        depth = 2 if (len(path) > 1 and isinstance(
            path[1], jax.tree_util.SequenceKey)) else 1
        key = jax.tree_util.keystr(path[:depth]) if path else ""
        groups.setdefault(key, []).append(i)
    return tuple((k, tuple(v)) for k, v in groups.items())


def _xp(arrs):
    """jnp for jax arrays, numpy otherwise (one code path packs both
    the device and host layouts)."""
    try:
        import jax

        if any(isinstance(a, jax.Array) for a in arrs):
            import jax.numpy as jnp

            return jnp
    except ImportError:  # pragma: no cover - jax is a hard dep today
        pass
    return np


class ShardedState:
    """This rank's 1/n of a pytree packed by a :class:`ZeroPlan`.

    ``shards[b]`` is a 1-D array of ``plan.shard_elems[b]`` elements of
    ``plan.dtypes[b]`` — rank r's contiguous chunk of bucket b's padded
    flat concat. Produced by ``Comm.Reduce_scatter_multi`` (the
    reduced gradient shards) or :meth:`from_full` (a local slice of
    replicated values, e.g. the initial parameters); consumed by
    ``Comm.Allgather_multi`` which reassembles the full pytree."""

    __slots__ = ("plan", "metas", "treedef", "shards", "rank", "n",
                 "versions")

    def __init__(self, plan: ZeroPlan, metas, treedef, shards,
                 rank: int, n: int, versions=None) -> None:
        self.plan = plan
        self.metas = metas
        self.treedef = treedef
        self.shards = list(shards)
        self.rank = int(rank)
        self.n = int(n)
        #: per-bucket mutation counters (changed-bucket dirty
        #: tracking): every :meth:`map` bumps them, so the async
        #: checkpoint plane's incremental mode can tell which buckets
        #: MAY have changed since the last snapshot without touching
        #: the data (digest-diff stays the source of truth — versions
        #: are the cheap over-approximation)
        self.versions = list(versions) if versions is not None \
            else [0] * len(self.shards)

    # -- sizing (the O(1/n) story the smoke lane asserts) -----------------
    @property
    def shard_bytes(self) -> int:
        """Bytes this rank actually holds."""
        return sum(int(plan_sh) * np.dtype(dt).itemsize
                   for plan_sh, dt in zip(self.plan.shard_elems,
                                          self.plan.dtypes))

    @property
    def total_bytes(self) -> int:
        """Bytes of the full (replicated) pytree this shards."""
        return self.plan.nbytes

    @property
    def nbytes(self) -> int:
        """Alias of :attr:`total_bytes` — generic byte-counting hooks
        (the telemetry flight PMPI interposer reads ``args[0].nbytes``)
        see the full cycle payload."""
        return self.plan.nbytes

    # -- local elementwise math (the optimizer update) --------------------
    def map(self, fn, *others: "ShardedState", where=None
            ) -> "ShardedState":
        """New state with ``fn(self.shards[b], *others.shards[b])`` per
        bucket — the local-shard update step (runs on whatever array
        type the shards are; no collective). ``where`` (optional
        per-bucket bool mask) limits the update to selected buckets:
        unselected buckets keep their shard AND their version counter,
        which is what lets a downstream allgather prove "this bucket
        did not change" (the frozen-leaf skip path)."""
        for o in others:
            if o.plan.buckets != self.plan.buckets \
                    or o.plan.n != self.plan.n:
                raise errors.MPIError(
                    errors.ERR_ARG,
                    "ShardedState.map: operand packed by a different "
                    "plan (shard-wise math requires identical bucket "
                    "layouts)")
        if where is not None and len(where) != len(self.shards):
            raise errors.MPIError(
                errors.ERR_COUNT,
                f"ShardedState.map: where mask has {len(where)} "
                f"entries for {len(self.shards)} buckets")
        shards = [fn(s, *(o.shards[b] for o in others))
                  if where is None or where[b] else s
                  for b, s in enumerate(self.shards)]
        return ShardedState(self.plan, self.metas, self.treedef,
                            shards, self.rank, self.n,
                            versions=[v + 1 if where is None or where[b]
                                      else v
                                      for b, v in
                                      enumerate(self.versions)])

    def zeros_like(self) -> "ShardedState":
        xp = _xp(self.shards)
        shards = [xp.zeros((k,), dtype=dt)
                  for k, dt in zip(self.plan.shard_elems,
                                   self.plan.dtypes)]
        return ShardedState(self.plan, self.metas, self.treedef,
                            shards, self.rank, self.n)

    # -- pack / unpack -----------------------------------------------------
    @classmethod
    def from_full(cls, comm, tree, plan: Optional[ZeroPlan] = None
                  ) -> "ShardedState":
        """Slice this rank's shard out of a REPLICATED pytree (no
        collective — every rank already holds the full values; used to
        seed the optimizer's param/momentum shards). The layout is the
        same ZeroPlan the collectives use, so shards line up with
        ``Reduce_scatter_multi`` gradients element-for-element."""
        import jax

        leaves, treedef = jax.tree.flatten(tree)
        metas = _fuse_metas(leaves)
        if plan is None:
            plan = ZeroPlan(metas, int(_bucket_var.get()), comm.size)
        xp = _xp(leaves)
        rank = comm.rank
        shards = []
        for b, idxs in enumerate(plan.buckets):
            flat = xp.concatenate([xp.reshape(leaves[i], (-1,))
                                   for i in idxs]) \
                if len(idxs) > 1 else xp.reshape(leaves[idxs[0]], (-1,))
            pad = plan.padded[b] - plan.elems[b]
            if pad:
                flat = xp.pad(flat, (0, pad))
            k = plan.shard_elems[b]
            shards.append(flat[rank * k:(rank + 1) * k])
        return cls(plan, metas, treedef, shards, rank, comm.size)

    def unpack(self, fulls) -> object:
        """Full padded flat bucket arrays -> the original pytree
        (drops the pad tail, restores leaf shapes; the inverse of the
        bucket concat)."""
        import jax

        xp = _xp(fulls)
        outs: List[object] = [None] * sum(
            len(idxs) for idxs in self.plan.buckets)
        for b, idxs in enumerate(self.plan.buckets):
            off = 0
            for i in idxs:
                shape = self.metas[i][0]
                k = _elems_of(shape)
                outs[i] = xp.reshape(fulls[b][off:off + k], shape)
                off += k
        return jax.tree.unflatten(self.treedef, outs)


class ErrorFeedback:
    """Per-bucket compression-residual carry for ZeRO gradient cycles
    (Seide et al. 2014 1-bit SGD; Lin et al. 2018 DGC): each step
    transmits Q(g + e) and keeps e' = (g + e) - Q(g + e) locally, so
    quantization error is re-injected next step instead of lost and
    SGD tracks the exact-gradient trajectory. Quantization happens at
    the SOURCE — elementwise, deterministic, before the exact reduce —
    which makes the scheme self-consistent no matter which collective
    transport (flat, hier, compressed-DCN) carries the payload.

    Layout-matched to the same deterministic :class:`ZeroPlan` the
    zero collectives derive, so the fp8 scale is per BUCKET (the
    compressed-DCN granularity) and the residual is one unpadded flat
    array per compressible bucket. Buckets whose dtype the wire format
    cannot narrow (ints, dtypes <= the wire width) pass through
    untouched and carry no residual."""

    __slots__ = ("wire", "plan", "residuals", "_active")

    def __init__(self, wire: str) -> None:
        from ompi_tpu.util import jaxcompat as _jc

        if _jc.wire_dtype(wire) is None:
            raise errors.MPIError(
                errors.ERR_ARG,
                f"error_feedback={wire!r}: expected 'bf16', "
                "'fp8_e4m3' or 'fp8_e5m2'")
        self.wire = wire
        self.plan: Optional[ZeroPlan] = None
        self.residuals: List[object] = []
        self._active: Tuple[bool, ...] = ()

    def _bind(self, plan: ZeroPlan) -> None:
        """(Re)bind to a bucket layout; a layout change resets the
        carried residuals (they index a different packing)."""
        from ompi_tpu.util import jaxcompat as _jc

        self.plan = plan
        wsz = _jc.wire_itemsize(self.wire)
        active = []
        for dt in plan.dtypes:
            try:
                ndt = _jc.np_dtype(dt)
            except TypeError:
                active.append(False)
                continue
            active.append(ndt.kind == "f" and wsz < ndt.itemsize)
        self._active = tuple(active)
        self.residuals = [None] * len(plan.buckets)

    def apply(self, tree, n: int):
        """Same-treedef pytree with every compressible bucket replaced
        by Q(bucket + residual), the new residual carried for the next
        step. ``n`` is the comm size (the plan's pad modulus), so the
        packing here is element-for-element the one the zero
        collectives will transmit."""
        import jax

        from ompi_tpu.parallel import hierarchical as H
        from ompi_tpu.util import jaxcompat as _jc

        leaves, treedef = jax.tree.flatten(tree)
        metas = _fuse_metas(leaves)
        plan = ZeroPlan(metas, int(_bucket_var.get()), int(n))
        if self.plan is None or plan.buckets != self.plan.buckets \
                or plan.dtypes != self.plan.dtypes:
            self._bind(plan)
        xp = _xp(leaves)
        outs = list(leaves)
        wsz = _jc.wire_itemsize(self.wire)
        ef_bytes = 0
        for b, idxs in enumerate(plan.buckets):
            if not self._active[b]:
                continue
            flat = xp.concatenate(
                [xp.reshape(leaves[i], (-1,)) for i in idxs]) \
                if len(idxs) > 1 else xp.reshape(leaves[idxs[0]], (-1,))
            r = self.residuals[b]
            if r is not None:
                flat = flat + r
            q = H.wire_quantize(flat, self.wire)
            self.residuals[b] = flat - q
            off = 0
            for i in idxs:
                shape = metas[i][0]
                k = _elems_of(shape)
                outs[i] = xp.reshape(q[off:off + k], shape)
                off += k
            ef_bytes += plan.elems[b] * wsz
        pvar.record("zero_ef_steps")
        pvar.record("zero_ef_bytes", ef_bytes)
        return jax.tree.unflatten(treedef, outs)


# ---------------------------------------------------------------------------
# host-buffer fallback cycle (numpy leaves, no device plane required):
# the same ZeroPlan layout over the stacked host collectives — one
# allreduce/allgather per bucket, shard sliced locally. Correct and
# O(1/n)-sharded; the overlap + single-launch wins are device-path.


def host_reduce_scatter_multi(comm, bufs, op=op_mod.SUM
                              ) -> ShardedState:
    """Bucketed reduce_scatter of numpy leaves: per bucket ONE host
    allreduce of the padded flat concat, then slice this rank's
    chunk. Same ZeroPlan layout (and leaf order) as the device path."""
    import jax

    from ompi_tpu.datatype.convertor import dtype_of

    leaves, treedef = jax.tree.flatten(bufs)
    metas = _fuse_metas(leaves)
    plan = ZeroPlan(metas, int(_bucket_var.get()), comm.size)
    rank, k_shards = comm.rank, []
    for b, idxs in enumerate(plan.buckets):
        flat = np.concatenate(
            [np.ascontiguousarray(leaves[i]).reshape(-1)
             for i in idxs])
        pad = plan.padded[b] - plan.elems[b]
        if pad:
            flat = np.pad(flat, (0, pad))
        out = np.empty_like(flat)
        comm.coll.allreduce(comm, flat, out, out.size, dtype_of(out),
                            op)
        k = plan.shard_elems[b]
        k_shards.append(out[rank * k:(rank + 1) * k].copy())
        pvar.record("zero_rs_launches")
    pvar.record("zero_fused_bytes", plan.nbytes)
    pvar.record("zero_pad_bytes", plan.pad_bytes)
    return ShardedState(plan, metas, treedef, k_shards, rank,
                        comm.size)


def host_allgather_bucket(comm, state: ShardedState, b: int):
    """Gather ONE bucket of a numpy ShardedState: the member leaves
    (in ``plan.buckets[b]`` order) reshaped to their original shapes.
    The bucket-granular form the optimizer's dirty-skip path uses —
    unchanged buckets reuse the previous cycle's gathered leaves
    instead of relaunching."""
    plan = state.plan
    if not 0 <= b < len(plan.buckets):
        raise errors.MPIError(
            errors.ERR_COUNT,
            f"host_allgather_bucket: bucket {b} out of range for a "
            f"{len(plan.buckets)}-bucket plan")
    parts = comm.coll.allgather_obj(
        comm, np.ascontiguousarray(state.shards[b]))
    full = np.concatenate(parts)
    pvar.record("zero_ag_launches")
    outs, off = [], 0
    for i in plan.buckets[b]:
        shape = state.metas[i][0]
        k = _elems_of(shape)
        outs.append(full[off:off + k].reshape(shape))
        off += k
    return outs


def host_allgather_multi(comm, state: ShardedState):
    """Bucketed allgather of numpy shards back to the full pytree:
    per bucket ONE host allgather of the shard, concat in rank order
    (= the pack order), unpack."""
    fulls = []
    for b, shard in enumerate(state.shards):
        parts = comm.coll.allgather_obj(comm, np.ascontiguousarray(
            shard))
        fulls.append(np.concatenate(parts))
        pvar.record("zero_ag_launches")
    pvar.record("zero_fused_bytes", state.plan.nbytes)
    return state.unpack(fulls)
