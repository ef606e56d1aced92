"""coll/xla — device-executed collectives on MPI communicators.

THE north-star component (SURVEY.md §2.3/§2.8, BASELINE.md config #1):
replaces the reference's coll/accelerator staging design
(ompi/mca/coll/accelerator/coll_accelerator_allreduce.c:32-115 — D2H,
host collective, H2D) with collectives that *never leave the device*.

How: the communicator's group maps onto the multi-controller device
plane (:mod:`ompi_tpu.runtime.device_plane` — one device per rank,
bootstrapped like the accelerator modex in
opal/mca/accelerator/accelerator.h:668-711). Per communicator we build a
1-D mesh over the member devices ordered by comm rank; each collective
compiles once per (kind, shape, dtype, op, mode) into an XLA program via
``shard_map`` — psum/all_gather/all_to_all lower to ICI transfers on TPU
and gloo on the CPU test backend. Compiled programs are cached on the
communicator exactly as the reference caches per-comm algorithm
schedules (coll_base_comm_select.c:236-330 stacking).

Determinism contract (BASELINE.md "bit-identical vs basic"):
``deterministic='linear'`` folds contributions in exact rank order —
bit-identical to coll/basic's linear reduce (coll_basic_reduce.c
semantics); ``deterministic='ring'`` fixes a ring chunk order that is
stable run-to-run. Default lets XLA schedule (fastest).

Fallback: any buffer/op the device path cannot express (e.g. MINLOC
struct dtypes) falls through to the coll/accelerator staging functions —
the same slot signature, one priority level down.
"""

from __future__ import annotations

import functools
import threading
from typing import NamedTuple, Optional

import numpy as np

from ompi_tpu import errors, op as op_mod
from ompi_tpu.coll import CollModule, accelerator as staging, framework
from ompi_tpu.coll import dispatch as _dispatch
from ompi_tpu.core import cvar, output, pvar
from ompi_tpu.monitoring import expert_load as _expert_load
from ompi_tpu.prof import ledger as _prof
from ompi_tpu.trace import recorder as _trace

_out = output.stream("coll_xla")

AXIS = "mpi"  # the mesh axis name a communicator compiles to

_default_det = cvar.register(
    "coll_xla_deterministic", "", str,
    help="default determinism mode for device collectives: '' (XLA "
         "schedules, fastest), 'ring' (fixed ring chunk order), "
         "'linear' (exact rank-order fold, bit-identical to coll/basic)",
    choices=["", "ring", "linear"], level=4)

_scatter_cache_var = cvar.register(
    "coll_xla_scatter_meta_cache", 1, int,
    help="Cache the scatter/scatterv metadata host round per (comm, "
         "root) [1, default]. The cached contract requires a stable "
         "root buffer signature — a root-side change raises ON THE "
         "ROOT ONLY; non-root peers reuse the cached shape and enter "
         "the compiled collective, where they HANG uninterruptibly "
         "until the job is killed (they run no host round the root "
         "could poison). Set 0 to restore a per-call metadata round "
         "for shape-varying scatters without like= templates.",
    level=6)

_rooted_var = cvar.register(
    "coll_xla_rooted_threshold_bytes", 1 << 20, int,
    help="Rooted (reduce/gather) device collectives switch to a "
         "root-collecting schedule when the would-be-replicated "
         "result reaches this size: below it, every rank computes "
         "the full allreduce/allgather (one compiled program, free "
         "for small buffers); at/above it, reduce runs "
         "reduce_scatter + chunk-to-root rounds and gather runs "
         "per-source ppermute-to-root rounds, so non-roots "
         "materialize O(bytes), not O(n*bytes) "
         "(coll_base_reduce.c binomial-semantics analog). 0 forces "
         "rooted always; -1 disables it.", level=5)

_a2av_pad_var = cvar.register(
    "coll_xla_alltoallv_pad_factor", 4, int,
    help="alltoallv pads every cell to the GLOBAL max count; skewed "
         "counts (one hot expert) inflate that to n*max cells. When "
         "the padded volume exceeds this factor x the true payload, "
         "the call falls through to the staging path instead of "
         "allocating the blowup (only on the max_count=None path — "
         "an explicit max_count is the capacity-bounded MoE fast "
         "path and is never second-guessed). 0 disables the bound.",
    level=6)

_a2av_cache_var = cvar.register(
    "coll_xla_a2av_meta_cache", 0, int,
    help="Cache the alltoallv pad-metadata host round per comm while "
         "the caller's (scounts, rcounts) signature is unchanged — "
         "an iterative MoE loop then pays ONE host round total. "
         "OPT-IN [default 0]: enabling it is a PROMISE that count "
         "changes touch every rank's local signature (e.g. global "
         "capacity rebalancing); a change confined to a rank pair "
         "while other ranks' local counts stay identical makes "
         "cache-hit ranks skip the metadata collective that "
         "cache-miss ranks enter — a hang. Counts that never change "
         "should pass max_count= instead (host-free, always safe).",
    level=6)

_bucket_var = cvar.register(
    "coll_xla_bucket_bytes", 4 << 20, int,
    help="target flat-bucket size for the fused (bucketed) device "
         "collectives — allreduce_multi_dev / Allreduce_multi AND the "
         "zero/ scatter-gather pair (Reduce_scatter_multi / "
         "Allgather_multi, whose ZeroPlan pads each bucket to a "
         "multiple of the comm size): same-dtype buffers coalesce "
         "into flat buckets that close once they reach this many "
         "bytes, and each bucket runs ONE compiled program (the "
         "NCCL/Horovod/DDP gradient-bucketing analog). The "
         "close-at-threshold rule bounds compiled launches to "
         "ceil(total_bytes/bucket_bytes) + n_dtypes. 0 fuses each "
         "dtype into a single bucket regardless of size.", level=5)

_cache_max_var = cvar.register(
    "coll_xla_cache_max", 0, int,
    help="LRU bound on the per-comm compiled-program and bucket-plan "
         "caches (each of _Ctx.fns / _Ctx.plans independently): under "
         "shape churn these otherwise grow without bound "
         "(coll_xla_fns_size / coll_xla_plans_size pvars are the "
         "monitor). 0 [default] = unbounded. Eviction drops only the "
         "cache entry — handles that already hold the compiled "
         "launcher (persistent/partitioned inits, in-flight requests) "
         "keep working; the next cold call recompiles. Evictions "
         "count in the coll_xla_cache_evictions pvar.", level=6)

_hier_var = cvar.register(
    "coll_xla_hier", "auto", str,
    help="hierarchical ICI x DCN execution for comms spanning slices "
         "(coll/han's split-level algorithms on device, coll_han.h:"
         "62-63): 'auto' groups member devices by slice_index when "
         "comm ranks are slice-contiguous, 'off' always flat, an "
         "integer N forces N slices (testing on the virtual mesh). "
         "Deterministic modes always use the flat 1-D schedule — the "
         "split-level fold order differs from the rank-order "
         "contract.", level=5)

#: ops whose reduction is expressible as a traced elementwise fold
_TRACEABLE_OPS = {
    "MPI_SUM", "MPI_PROD", "MPI_MIN", "MPI_MAX", "MPI_LAND", "MPI_LOR",
    "MPI_LXOR", "MPI_BAND", "MPI_BOR", "MPI_BXOR",
}


def _det(deterministic: Optional[str]) -> Optional[str]:
    if deterministic is not None:
        return deterministic or None
    return _default_det.get() or None


#: every blocking slot here runs its prepared launcher through the one
#: dispatch seam (counter, monitoring, tune, flight: coll/dispatch.py)
_run = functools.partial(_dispatch.run, "xla")


#: the name ``_Ctx.compiled`` chose for the program being built, for
#: ``_Ctx.smap`` to give to the jitted body (per thread: builds nest
#: under whichever thread misses the cache); and, while a sink is up,
#: ``launched``: the arguments of this thread's last ``launch`` span,
#: for the request that is made of that launch (``_launched``)
_naming = threading.local()


def _launched(launch, *args, **kwargs):
    """Run ``launch(*args, **kwargs)`` for a request. Returns (its
    result, what the request remembers for its ``wait`` span): the
    arguments of the LAST ``launch`` span under it — ``program``, and
    the ``call`` of the API call it was made in, if it was made in
    one — or None with no sink up or no device program launched (a
    staged fallback)."""
    if not _trace.active():
        return launch(*args, **kwargs), None
    _naming.launched = None
    return launch(*args, **kwargs), _naming.launched


def _wait(arrays, launched) -> None:
    """Block until ``arrays`` (a pytree) are ready: every wait of the
    device path. With no sink up it costs the ``active()`` guard;
    with one it is span ``wait`` — the caller's share of a
    nonblocking, persistent or partitioned collective, against the
    ``launch`` it names by ``program`` and ``launch_call``
    (``launched``: what ``_launched`` gave when it was launched)."""
    import jax

    if not _trace.active():
        jax.block_until_ready(arrays)
        return
    args = {"program": launched["program"] if launched else "?"}
    if launched and "call" in launched:
        args["launch_call"] = launched["call"]
    with _trace.span("wait", "coll_xla", **args):
        jax.block_until_ready(arrays)


def program_name(key) -> str:
    """The stable name of the program (or plan) behind a cache key:
    ``ompi_<kind>``. Keys are ``(kind, ...)`` or ``_key(x, kind,
    ...)`` = ``(shape, dtype, kind, ...)``. A fixed operand order the
    key carries ('linear', 'ring': a deterministic mode, a Pallas
    algorithm) is part of the name — ``ompi_allreduce_linear`` is
    another program than ``ompi_allreduce``."""
    kind = key[0] if isinstance(key[0], str) else key[2]
    order = next((e for e in key[1:] if e in ("linear", "ring")), None)
    return f"ompi_{kind}_{order}" if order else f"ompi_{kind}"


class _Scalar(NamedTuple):
    """``to_global``'s view of a 0-d operand: global shape ``(n,)``,
    block ``(1,)``. A tuple type, so jax carries it through ``jit``
    and ``shard_map`` as a one-leaf pytree and ``smap``'s program can
    see, operand by operand, which block already has its rank axis."""
    view: object


def _is_scalar(a) -> bool:
    return isinstance(a, _Scalar)


def _block(a):
    """Operand block -> the ``(1, *shape)`` block a body sees."""
    return a.view if isinstance(a, _Scalar) else a.reshape((1,) + a.shape)


class _Ctx:
    """Per-communicator compiled-collective state (the analog of the
    reference's per-comm coll module data)."""

    def __init__(self, comm) -> None:
        from ompi_tpu.runtime import device_plane

        devs = [device_plane.device_for_world_rank(w)
                for w in comm.group.ranks]
        self._setup(devs, device_plane.my_device())

    @classmethod
    def local(cls) -> "_Ctx":
        """A 1-device context over the local default device, no plane
        required — the bench/diagnostic lane: a psum over one device
        is an identity collective, so timing it isolates the pure
        host dispatch cost of the compiled-collective hot path."""
        import jax

        obj = cls.__new__(cls)
        dev = jax.devices()[0]
        obj._setup([dev], dev)
        return obj

    def _setup(self, devs, my) -> None:
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        self.jax = jax
        self.P = P
        self.mesh = Mesh(np.array(devs), (AXIS,))
        self.my = my
        self.n = len(devs)
        self.in_sharding = NamedSharding(self.mesh, P(AXIS))
        self.fns = {}  # (kind, shape, dtype, ...) -> compiled callable
        self.plans = {}  # fused-allreduce bucket plans per signature
        self.programs = {}  # compiled callable -> its stable name
        self._cold = set()  # built, never launched: the first launch
        # is where jax compiles the program or loads it from the cache
        # hierarchical ICI x DCN mesh (rank-major rows = slices) when
        # the comm spans slices and ranks are slice-contiguous
        self.mesh2d = None
        n_slices = self._detect_slices(devs)
        if n_slices and 1 < n_slices < self.n:
            from ompi_tpu.parallel import hierarchical as H

            grid = np.array(devs).reshape(n_slices,
                                          self.n // n_slices)
            self.mesh2d = Mesh(grid, (H.DCN_AXIS, H.ICI_AXIS))
            self.in_sharding2d = NamedSharding(
                self.mesh2d, P((H.DCN_AXIS, H.ICI_AXIS)))

    @staticmethod
    def _detect_slices(devs) -> int:
        """Number of DCN groups (0 = stay flat). 'auto' requires comm
        rank order to be slice-contiguous with equal-size slices so
        mesh rows ARE physical slices (H.slice_split); anything else
        degrades to flat (correct, just not hierarchy-optimized)."""
        mode = _hier_var.get()
        if mode == "off":
            return 0
        if mode != "auto":
            try:
                n = int(mode)
            except ValueError:
                return 0
            return n if n > 1 and len(devs) % n == 0 else 0
        from ompi_tpu.parallel import hierarchical as H

        return H.slice_split(devs)

    def replica_groups(self):
        """Device-id groups this comm's collectives compile to
        (introspection parity with DeviceCommunicator.replica_groups)."""
        return [[d.id for d in self.mesh.devices.tolist()]]

    # -- plumbing ---------------------------------------------------------
    def to_global(self, x, sharding=None):
        """Local device array -> this rank's part of the global array
        a compiled collective takes: shape ``(n * x.shape[0],
        *x.shape[1:])``, sharded on dimension 0 over the comm axis/
        axes (rank r's contribution is block r) — the convention a
        ``P(AXIS)`` OUTPUT already has. ``smap``'s program gives each
        block its leading axis back, so a body sees ``(1, *x.shape)``.

        The view is built from the caller's buffer itself: no
        indexing, no device program, no copy. It therefore ALIASES
        ``x``. jax arrays are immutable and no program of this
        package donates an argument (tests/test_coll_xla_global_view
        walks ``ctx.fns`` to pin that), so a launch never changes
        ``x``; but a view does not outlive its operand's buffer — a
        persistent or partitioned request binds its views once, and
        a ``Start()`` after the user ``delete()``d the operand raises
        jax's "Array has been deleted".

        A 0-d operand has no dimension to shard: it is expanded
        eagerly (``x[None]``: a device program and a copy, counted in
        ``coll_xla_global_view_copies``) and comes back as a
        :class:`_Scalar`, whose ``(1,)`` block is already what the
        body expects.

        device_put is skipped when the buffer already lives on
        ``my`` — it runs on every collective call, and for resident
        arrays (the steady-state training case) it only adds a
        dispatch round. Span ``to_global`` is what a caller pays for
        the global view."""
        if not _trace.active():
            return self._to_global(x, sharding, _trace.OFF)
        with _trace.span("to_global", "coll_xla") as sp:
            return self._to_global(x, sharding, sp)

    def _to_global(self, x, sharding, sp):
        jax = self.jax
        try:
            resident = x.device == self.my
        except (AttributeError, ValueError):
            resident = False  # numpy / multi-shard input: stage it
        sp.set(resident=int(resident))
        if resident:
            pvar.record("coll_xla_device_put_skipped")
        elif _prof.PROFILER is None:
            x = jax.device_put(x, self.my)
        else:
            t0 = _prof.now()
            x = jax.device_put(x, self.my)
            x.block_until_ready()
            _prof.PROFILER.xfer("h2d", getattr(x, "nbytes", 0), t0,
                                _prof.now(), site="to_global")
        sharding = sharding or self.in_sharding
        shape = x.shape
        if not shape:
            pvar.record("coll_xla_global_view_copies")
            return _Scalar(jax.make_array_from_single_device_arrays(
                (self.n,), sharding, [x[None]]))
        return jax.make_array_from_single_device_arrays(
            (self.n * shape[0],) + shape[1:], sharding, [x])

    def my_shard(self, out):
        """This rank's shard of an AXIS-sharded result."""
        if not _trace.active():
            return out.addressable_data(0)
        with _trace.span("my_shard", "coll_xla"):
            return out.addressable_data(0)

    def bind(self, fn, x, sharding=None):
        """Bind operand ``x`` to the compiled program ``fn`` NOW (its
        global view is built here, once): the zero-argument launcher
        whose every call is one dispatch yielding this rank's shard —
        what a blocking slot runs at once and a persistent request
        holds."""
        g = self.to_global(x, sharding)
        return lambda: self.my_shard(self.launch(fn, g))

    def compiled(self, key, build):
        """Get-or-build a compiled program. Hit/miss/size pvars make
        cache churn (shape-varying workloads recompiling every call)
        visible via MPI_T instead of only via wall time. Bounded LRU
        when cvar coll_xla_cache_max > 0 (insertion order IS recency:
        hits reinsert).

        ``build()`` returns a lazy ``jax.jit`` object: nothing compiles
        here. The program is named here (``program_name(key)``, which
        ``smap`` gives the jitted body) and marked cold; its first
        ``launch`` is timed as the compile."""
        fn = self.fns.get(key)
        if fn is None:
            pvar.record("coll_xla_cache_misses")
            name = _naming.program = program_name(key)
            try:
                fn = self.fns[key] = build()
            finally:
                _naming.program = None
            self.programs[fn] = name
            self._cold.add(fn)
            pvar.record_hwm("coll_xla_fns_size", len(self.fns))
            self._evict(self.fns)
        else:
            pvar.record("coll_xla_cache_hits")
            self.fns[key] = self.fns.pop(key)  # LRU touch
        return fn

    def plan(self, key, build):
        """Get-or-build a fused-bucket plan (same contract as
        ``compiled`` — steady-state steps must pay zero re-planning)."""
        p = self.plans.get(key)
        if p is None:
            pvar.record("coll_xla_plan_cache_misses")
            with _trace.span("plan_build", "coll_xla",
                             plan=program_name(key)):
                p = self.plans[key] = build()
            pvar.record_hwm("coll_xla_plans_size", len(self.plans))
            self._evict(self.plans)
        else:
            pvar.record("coll_xla_plan_cache_hits")
            self.plans[key] = self.plans.pop(key)  # LRU touch
        return p

    def _evict(self, cache) -> None:
        mx = int(_cache_max_var.get())
        while mx > 0 and len(cache) > mx:
            old = cache.pop(next(iter(cache)))  # oldest-touched first
            if cache is self.fns:
                self.programs.pop(old, None)
                self._cold.discard(old)
            pvar.record("coll_xla_cache_evictions")

    def launch(self, fn, *args):
        """Dispatch one compiled collective program. Every device-path
        dispatch funnels through here so the launch counter is exact —
        the fusion regression tests assert on it. With no sink up a
        warm launch costs the cold-set lookup and the ``active()``
        guard; with one, span ``launch`` covers DISPATCH time only —
        PJRT execution is asynchronous — and leaves its arguments for
        the request that is made of it (``_launched``).

        A program's FIRST launch is where jax compiles it or loads it
        from the persistent cache: it is always timed (two clock reads
        against a compile) into ``coll_xla_cold_launch_ns`` /
        ``coll_xla_cold_launches``, and is span ``compile`` around a
        ``launch`` with ``cold=1``; what jax did inside it — trace,
        lowering, XLA compile or cache load — is the compile ledger's
        (``prof/compile.py``: spans ``compile.<phase>`` with this
        call's number)."""
        pvar.record("coll_xla_launches")
        if fn in self._cold:
            return self._launch_cold(fn, args)
        if not _trace.active():
            return fn(*args)
        with _trace.span("launch", "coll_xla",
                         program=self.programs.get(fn, "?"),
                         nbytes=self._nbytes(args), cold=0) as sp:
            _naming.launched = sp.args
            return fn(*args)

    def _launch_cold(self, fn, args):
        self._cold.discard(fn)
        name = self.programs.get(fn, "?")
        t0 = _trace.now()
        with _trace.span("compile", "coll_xla", program=name), \
                _trace.span("launch", "coll_xla", program=name,
                            nbytes=self._nbytes(args), cold=1) as sp:
            _naming.launched = sp.args  # None with no sink up
            out = fn(*args)
        dt = _trace.now() - t0
        pvar.record("coll_xla_cold_launches")
        pvar.record("coll_xla_cold_launch_ns", dt)
        return out

    def _nbytes(self, args) -> int:
        """Operand bytes of one rank (the global views' bytes / n;
        a fused bucket passes its leaves as one tuple)."""
        return sum(getattr(x, "nbytes", 0)
                   for x in self.jax.tree.leaves(args)) // self.n

    def release(self) -> None:
        """Drop the compiled-program and plan caches (comm destructor
        path: long-lived jobs with shape churn must not grow these
        invisibly after the comm is freed)."""
        self.fns.clear()
        self.plans.clear()
        self.programs.clear()
        self._cold.clear()

    def smap(self, body, out_varying: bool, mesh=None, spec=None):
        """jit(shard_map(body)) over the comm mesh (or the 2-level
        ICI x DCN mesh when passed). Body sees the local (1, *shape)
        block; out_varying selects the sharded vs replicated spec.

        ``to_global`` hands in the rank's buffer as it is (block
        ``shape``); the rank axis is put in front of every operand
        block HERE, inside the traced program, where it is a bitcast
        that XLA folds into the body's ``a[0]``. ``to_global`` and
        ``smap`` are the one seam: every program fed by ``to_global``
        is built by ``smap``.

        The program gets the stable name ``compiled`` chose for its
        key (module ``jit_ompi_<kind>`` in a device trace, instead of
        ``jit__lambda_``), and its collective sits under
        ``jax.named_scope(<kind>)``: metadata, the HLO is the same."""
        from ompi_tpu.util import jaxcompat

        jax, P = self.jax, self.P
        spec = spec if spec is not None else P(AXIS)
        out_spec = spec if out_varying else P()
        name = getattr(_naming, "program", None) or "ompi_program"

        def program(*a):
            a = jax.tree.map(_block, a, is_leaf=_is_scalar)
            with jax.named_scope(name[len("ompi_"):]):
                return body(*a)

        program.__name__ = program.__qualname__ = name
        return jax.jit(jaxcompat.shard_map(
            program, mesh=mesh if mesh is not None else self.mesh,
            in_specs=spec, out_specs=out_spec, check_vma=False))

    def to_global_hier(self, x):
        return self.to_global(x, self.in_sharding2d)

    def smap_hier(self, body, out_varying: bool):
        """Mesh rows are slices; row-major device order = comm rank."""
        from ompi_tpu.parallel import hierarchical as H

        return self.smap(body, out_varying, mesh=self.mesh2d,
                         spec=self.P((H.DCN_AXIS, H.ICI_AXIS)))


def _ctx(comm) -> _Ctx:
    ctx = getattr(comm, "_coll_xla_ctx", None)
    if ctx is None:
        ctx = comm._coll_xla_ctx = _Ctx(comm)
    return ctx


def _key(x, *extra):
    return (x.shape, str(x.dtype)) + extra


def _op_ok(op) -> bool:
    op = op_mod.BUILTIN.get(op) if not isinstance(op, op_mod.Op) else op
    if op is None:
        return False
    if op.name in _TRACEABLE_OPS:
        return True
    # user-defined ops run on device iff marked jax-traceable
    return bool(getattr(op, "traceable", False))


# ---------------------------------------------------------------------------
# slots — signatures match coll/accelerator's *_dev (the fallback)


def _slot(op: str):
    """Span ``ompi:coll_xla.<op>`` around a blocking slot: everything
    the slot does on the host (gates, context, key and cache lookup,
    the dispatch seam) is its self time; ``to_global``, ``launch``
    and ``my_shard`` are its children. ``nbytes`` is the first
    operand's (a barrier has none: 0)."""
    def deco(fn):
        @functools.wraps(fn)
        def slot(comm, *args, **kwargs):
            if not _trace.active():
                return fn(comm, *args, **kwargs)
            with _trace.span(op, "coll_xla",
                             nbytes=_dispatch.nbytes_of(args[0])
                             if args else 0):
                return fn(comm, *args, **kwargs)
        return slot
    return deco


def _allreduce_prep(comm, sendbuf, op=op_mod.SUM,
                    deterministic: Optional[str] = None):
    """Plan + compile + bind the allreduce NOW; returns a zero-arg
    launcher whose every call is one cached-executable dispatch. The
    blocking slot calls the launcher immediately; the MPI-4 persistent
    init holds it so Start()+Wait() pays zero re-planning (jax arrays
    are immutable, so the operand bound here never changes)."""
    det = _det(deterministic)
    from ompi_tpu.parallel import collectives as C

    ctx = _ctx(comm)
    opn = op if isinstance(op, op_mod.Op) else op_mod.BUILTIN[op]
    hier = det is None and ctx.mesh2d is not None

    def build():
        if hier:  # han split-level over ICI x DCN (deterministic
            # modes stay flat: the split fold order differs from the
            # rank-order bit-identical contract)
            from ompi_tpu.parallel import hierarchical as H

            return ctx.smap_hier(lambda a: H.allreduce(a[0], op=opn),
                                 out_varying=False)
        return ctx.smap(lambda a: C.allreduce(a[0], AXIS, opn, det),
                        out_varying=False)

    fn = ctx.compiled(_key(sendbuf, "allreduce", opn.name, det), build)
    return ctx.bind(fn, sendbuf, ctx.in_sharding2d if hier else None)


@_slot("allreduce")
def allreduce_dev(comm, sendbuf, op=op_mod.SUM,
                  deterministic: Optional[str] = None):
    if not _op_ok(op):
        return staging.allreduce_dev(comm, sendbuf, op)
    if comm.size == 1:
        return sendbuf
    return _run("allreduce", comm, sendbuf,
                _allreduce_prep(comm, sendbuf, op, deterministic),
                algorithm=_det(deterministic))


#: test/diagnostic hook: the last rooted schedule's per-round,
#: per-rank output element count (proves non-roots moved O(bytes))
_last_rooted_plan: Optional[dict] = None


def _rooted(nbytes_result: int) -> bool:
    thr = _rooted_var.get()
    return thr >= 0 and nbytes_result >= thr


def _gather_rooted(ctx, comm, x, root: int):
    """Collect every rank's ``x`` on the root: one single-pair
    ppermute program per source (src -> root), each moving and
    allocating only ONE x-sized block per rank — non-roots never
    materialize the n-fold result (coll_base_gather.c linear
    semantics, on device). Root stacks the blocks locally (its own
    device, outside the collective programs). Returns (n, *x.shape)
    on root, None elsewhere."""
    global _last_rooted_plan
    import jax.numpy as jnp
    from jax import lax

    n, me = ctx.n, comm.rank
    _last_rooted_plan = {"kind": "gather_rooted", "rounds": n - 1,
                        "round_out_elems": int(x.size)}
    parts = [None] * n
    if me == root:
        parts[root] = x
    for src in range(n):
        if src == root:
            continue

        def build(src=src):
            return ctx.smap(
                lambda a: lax.ppermute(a[0], AXIS,
                                       perm=[(src, root)]),
                out_varying=True)

        fn = ctx.compiled(_key(x, "gather_rooted", src, root), build)
        got = ctx.my_shard(ctx.launch(fn, ctx.to_global(x)))
        if me == root:
            parts[src] = got
    if me != root:
        return None
    return jnp.stack(parts)


def _reduce_binomial(ctx, comm, x, opn, root: int):
    """Binomial ppermute reduction tree for commutative non-SUM ops
    above the rooted threshold (coll_base_reduce.c binomial, on
    device): ceil(log2 n) rounds of disjoint (src -> dst) single-pair
    ppermutes + a masked elementwise combine. Every rank sends its
    partial exactly once and every round's output stays x-sized —
    non-roots do O(bytes) traffic and never materialize the n-fold
    allreduce result (reduce_scatter has no native lowering for these
    ops, so the SUM path's psum_scatter program is unavailable)."""
    global _last_rooted_plan
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.parallel.collectives import _JNP_FN

    n, me = ctx.n, comm.rank
    combine = _JNP_FN[opn.name]
    rounds = []
    mask = 1
    while mask < n:
        pairs = []
        for v in range(n):  # vrank space: v = (rank - root) mod n
            if v % (2 * mask) == mask:  # sender this round
                pairs.append((((v + root) % n),
                              ((v - mask + root) % n)))
        if pairs:
            rounds.append(tuple(pairs))
        mask <<= 1
    _last_rooted_plan = {"kind": "reduce_binomial",
                         "rounds": len(rounds),
                         "round_out_elems": int(x.size)}
    acc = x
    for rnd, pairs in enumerate(rounds):
        dsts = tuple(sorted({d for _, d in pairs}))

        def build(pairs=pairs, dsts=dsts):
            def body(a):
                cur = a[0]
                got = lax.ppermute(cur, AXIS, perm=list(pairs))
                idx = lax.axis_index(AXIS)
                recv = jnp.zeros((), bool)
                for d in dsts:
                    recv = recv | (idx == d)
                return jnp.where(recv, combine(cur, got), cur)

            return ctx.smap(body, out_varying=True)

        fn = ctx.compiled(_key(x, "reduce_binom", opn.name, rnd,
                               root, n), build)
        acc = ctx.my_shard(ctx.launch(fn, ctx.to_global(acc)))
    return acc if me == root else None


def _reduce_rooted_sum(ctx, comm, x, opn, root: int):
    """SUM above the rooted threshold: reduce_scatter leaves each
    rank ONE 1/n chunk (O(bytes/n) output), then the chunks ride
    single-pair ppermutes to the root — non-roots do O(bytes) HBM/ICI
    total, never the n-fold allreduce result (coll_base_reduce.c
    binomial role)."""
    import jax.numpy as jnp

    from ompi_tpu.parallel import collectives as C

    n = comm.size
    flat = x.reshape(-1)
    pad = (-flat.size) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))

    def build():
        return ctx.smap(
            lambda a: C.reduce_scatter(a[0], AXIS, opn,
                                       scatter_dim=0, tiled=True),
            out_varying=True)

    fn = ctx.compiled(_key(flat, "reduce_rooted_rs", opn.name), build)
    chunk = ctx.my_shard(ctx.launch(fn, ctx.to_global(flat)))
    stacked = _gather_rooted(ctx, comm, chunk, root)
    if comm.rank != root:
        return None
    return stacked.reshape(-1)[:x.size].reshape(x.shape)


def _rooted_schedule(opn):
    """SUM: reduce_scatter + chunks to the root. Another commutative
    op: the binomial ppermute tree (O(bytes) non-roots; psum_scatter
    has no lowering for it). None: the op has neither."""
    if opn.name == "MPI_SUM":
        return _reduce_rooted_sum
    from ompi_tpu.parallel.collectives import _JNP_FN

    return _reduce_binomial if opn.name in _JNP_FN else None


@_slot("reduce")
def reduce_dev(comm, sendbuf, op=op_mod.SUM, root: int = 0,
               deterministic: Optional[str] = None):
    if not _op_ok(op):
        return staging.reduce_dev(comm, sendbuf, op, root)
    n = comm.size
    opn = op if isinstance(op, op_mod.Op) else op_mod.BUILTIN[op]
    nbytes = int(sendbuf.size) * np.dtype(sendbuf.dtype).itemsize
    # small buffers / deterministic modes keep the one-program full
    # reduction (the rank-order contract needs the flat schedule
    # anyway, and it is free for small buffers); so does an op with
    # neither rooted schedule
    rooted = None
    if n > 1 and _det(deterministic) is None and _rooted(nbytes * n):
        rooted = _rooted_schedule(opn)
    if rooted is None:
        out = allreduce_dev(comm, sendbuf, op, deterministic)
        return out if comm.rank == root else None
    ctx = _ctx(comm)
    return _run("reduce", comm, sendbuf,
                lambda: rooted(ctx, comm, sendbuf, opn, root),
                nbytes=nbytes, root=root)


def _bcast_prep(comm, buf, root: int = 0):
    ctx = _ctx(comm)
    hier = ctx.mesh2d is not None

    def build():
        if hier:
            from ompi_tpu.parallel import hierarchical as H

            ici = ctx.mesh2d.devices.shape[1]
            return ctx.smap_hier(
                lambda a: H.bcast(a[0], root_dcn=root // ici,
                                  root_ici=root % ici),
                out_varying=False)
        return ctx.smap(_bcast_body(root), out_varying=False)

    fn = ctx.compiled(_key(buf, "bcast", root), build)
    return ctx.bind(fn, buf, ctx.in_sharding2d if hier else None)


@_slot("bcast")
def bcast_dev(comm, buf, root: int = 0):
    if comm.size == 1:
        return buf
    return _run("bcast", comm, buf, _bcast_prep(comm, buf, root),
                root=root)


def _bcast_body(root: int):
    from ompi_tpu.parallel import collectives as C

    return lambda a: C.bcast(a[0], AXIS, root)


def _allgather_prep(comm, sendbuf):
    from jax import lax

    ctx = _ctx(comm)

    def build():
        return ctx.smap(lambda a: lax.all_gather(a[0], AXIS),
                        out_varying=False)

    fn = ctx.compiled(_key(sendbuf, "allgather"), build)
    return ctx.bind(fn, sendbuf)


@_slot("allgather")
def allgather_dev(comm, sendbuf):
    if comm.size == 1:
        return sendbuf[None] if hasattr(sendbuf, "shape") else sendbuf
    return _run("allgather", comm, sendbuf,
                _allgather_prep(comm, sendbuf))


@_slot("gather")
def gather_dev(comm, sendbuf, root: int = 0):
    n = comm.size
    nbytes = int(sendbuf.size) * np.dtype(sendbuf.dtype).itemsize
    if n == 1 or not _rooted(nbytes * n):
        out = allgather_dev(comm, sendbuf)
        return out if comm.rank == root else None
    # rooted: per-source ppermute-to-root rounds; non-roots allocate
    # one sendbuf-sized block per round, never the (n, ...) result
    ctx = _ctx(comm)
    return _run("gather", comm, sendbuf,
                lambda: _gather_rooted(ctx, comm, sendbuf, root),
                nbytes=nbytes, root=root)


def _alltoall_prep(comm, sendbuf):
    if sendbuf.shape[0] % comm.size:
        raise errors.MPIError(
            errors.ERR_COUNT,
            f"alltoall: dim0 {sendbuf.shape[0]} not divisible by "
            f"comm size {comm.size}")
    from ompi_tpu.parallel import collectives as C

    ctx = _ctx(comm)
    hier = ctx.mesh2d is not None

    def build():
        if hier:  # two-phase: every byte crosses DCN exactly once;
            # output is source-rank-major, the MPI alltoall order
            from ompi_tpu.parallel import hierarchical as H

            return ctx.smap_hier(lambda a: H.alltoall(a[0]),
                                 out_varying=True)
        return ctx.smap(lambda a: C.alltoall(a[0], AXIS, 0, 0),
                        out_varying=True)

    fn = ctx.compiled(_key(sendbuf, "alltoall"), build)
    return ctx.bind(fn, sendbuf, ctx.in_sharding2d if hier else None)


@_slot("alltoall")
def alltoall_dev(comm, sendbuf):
    if comm.size == 1:
        return sendbuf
    return _run("alltoall", comm, sendbuf,
                _alltoall_prep(comm, sendbuf))


def _reduce_scatter_block_prep(comm, sendbuf, op=op_mod.SUM,
                               deterministic: Optional[str] = None):
    det = _det(deterministic)
    if sendbuf.shape[0] % comm.size:
        raise errors.MPIError(
            errors.ERR_COUNT,
            f"reduce_scatter_block: dim0 {sendbuf.shape[0]} not "
            f"divisible by comm size {comm.size}")
    from ompi_tpu.parallel import collectives as C

    ctx = _ctx(comm)
    opn = op if isinstance(op, op_mod.Op) else op_mod.BUILTIN[op]

    def build():
        return ctx.smap(
            lambda a: C.reduce_scatter(a[0], AXIS, opn, scatter_dim=0,
                                       tiled=True, deterministic=det),
            out_varying=True)

    fn = ctx.compiled(_key(sendbuf, "rsb", opn.name, det), build)
    return ctx.bind(fn, sendbuf)


@_slot("reduce_scatter_block")
def reduce_scatter_block_dev(comm, sendbuf, op=op_mod.SUM,
                             deterministic: Optional[str] = None):
    if not _op_ok(op):
        return staging.reduce_scatter_block_dev(comm, sendbuf, op)
    if comm.size == 1:
        return sendbuf
    return _run("reduce_scatter_block", comm, sendbuf,
                _reduce_scatter_block_prep(comm, sendbuf, op,
                                           deterministic),
                algorithm=_det(deterministic))


def _scatter_meta(comm, key, root: int, root_meta):
    """Per-(comm, kind, root) scatter metadata: the root passes its
    buffer signature; non-roots pass None and get the cached/broadcast
    value.

    The host metadata round runs ONCE per key and is cached like the
    compiled program (r2 VERDICT weak #4: it used to run per call).
    The cache is only valid while the root's signature is stable; a
    root that changes it raises instead of silently diverging from
    peers that would reuse stale metadata — pass ``like=`` (your
    recvbuf) on every rank for the zero-round dynamic path, or delete
    comm._coll_xla_scatter_meta on every rank."""
    if not _scatter_cache_var.get():  # per-call round (pre-cache
        # behavior): shape-varying scatters without like= templates
        if root_meta is not None:
            comm.coll.bcast_obj(comm, root_meta, root)
            return root_meta
        return comm.coll.bcast_obj(comm, None, root)
    cache = getattr(comm, "_coll_xla_scatter_meta", None)
    if cache is None:
        cache = comm._coll_xla_scatter_meta = {}
    if root_meta is not None:  # root side
        cached = cache.get(key)
        if cached is None:
            comm.coll.bcast_obj(comm, root_meta, root)
            cache[key] = root_meta
        elif cached != root_meta:
            raise errors.MPIError(
                errors.ERR_ARG,
                f"{key}: buffer signature changed {cached} -> "
                f"{root_meta} after the metadata round was cached. "
                "Non-root peers reuse the cached shape and are "
                "entering (or already inside) the compiled "
                "collective, where they hang uninterruptibly — KILL "
                "THIS JOB externally, then either pass like= on "
                "every rank (zero-round dynamic path) or set "
                "--mca coll_xla_scatter_meta_cache 0 (per-call "
                "metadata round)")
        return root_meta
    cached = cache.get(key)
    if cached is None:
        cached = cache[key] = comm.coll.bcast_obj(comm, None, root)
    return cached


@_slot("scatter")
def scatter_dev(comm, sendbuf, root: int = 0, like=None):
    if comm.size == 1:
        return sendbuf
    # non-roots pass no data but SPMD needs same-shape operands on
    # every device. Shapes come from (in order): the caller's own
    # recvbuf template (``like`` — MPI semantics guarantee non-roots
    # know their chunk; zero host rounds), else one cached host
    # metadata round per (comm, root).
    import jax.numpy as jnp

    ctx = _ctx(comm)
    # ``like`` is a collective argument (like counts): either every
    # rank passes its recvbuf template (zero-round, shape-dynamic
    # path) or none does (cached metadata round). Mixing hangs, as
    # inconsistent collective arguments do in MPI.
    if comm.rank == root:
        if like is None:
            _scatter_meta(comm, ("scatter", root), root,
                          (tuple(sendbuf.shape), str(sendbuf.dtype)))
        x = sendbuf
    elif like is not None:
        x = ctx.jax.device_put(
            jnp.zeros((comm.size * like.shape[0],) + tuple(
                like.shape[1:]), like.dtype), ctx.my)
    else:
        shape, dtype = _scatter_meta(comm, ("scatter", root), root,
                                     None)
        x = ctx.jax.device_put(jnp.zeros(shape, dtype), ctx.my)
    if x.shape[0] % comm.size:
        raise errors.MPIError(
            errors.ERR_COUNT,
            f"scatter: dim0 {x.shape[0]} not divisible by comm size "
            f"{comm.size}")
    from ompi_tpu.parallel import collectives as C

    def build():
        return ctx.smap(lambda a: C.scatter(a[0], AXIS, root, 0),
                        out_varying=True)

    fn = ctx.compiled(_key(x, "scatter", root), build)
    return _run("scatter", comm, x, ctx.bind(fn, x), root=root)


@_slot("barrier")
def barrier_dev(comm):
    """Device-plane barrier: a 1-element psum every member must enter
    before any member's program completes. Reference: coll/accelerator
    interposes every slot incl. barrier (ompi/mca/coll/accelerator/);
    here the rendezvous itself rides ICI instead of the host. The
    launcher WAITS, so the flight entry covers the rendezvous and a
    member that never arrives is the watchdog's to name."""
    if comm.size == 1:
        return
    launch = _barrier_prep(comm)
    _run("barrier", comm, None, lambda: DeviceRequest(launch()).wait())


@_slot("scatterv")
def scatterv_dev(comm, sendbuf, counts, root: int = 0, like=None):
    """Ragged scatter on device: root pads each segment to max(counts),
    a compiled bcast-from-root + static slice hands rank r its
    counts[r] rows. counts is the full vector (every rank passes it —
    MPI_Scatterv semantics), so shapes agree with zero host rounds;
    non-roots derive trailing dims/dtype from ``like`` (their recvbuf)
    or from the root metadata cache (see scatter_dev)."""
    counts = tuple(int(c) for c in counts)
    if comm.size == 1:
        return sendbuf
    if len(counts) != comm.size:
        raise errors.MPIError(
            errors.ERR_COUNT,
            f"scatterv: {len(counts)} counts for {comm.size} ranks")
    import jax.numpy as jnp
    from jax import lax

    ctx = _ctx(comm)
    m = max(counts)
    if comm.rank == root:
        rest, dtype = sendbuf.shape[1:], sendbuf.dtype
        if like is None:  # prime the shared metadata cache for
            # non-roots without a recvbuf template (same collective-
            # uniformity contract as scatter_dev)
            _scatter_meta(comm, ("scatterv", root), root,
                          (tuple(rest), str(dtype)))
        # pad segments to (n, m, *rest), segment r at row r
        rows = []
        off = 0
        for c in counts:
            seg = sendbuf[off:off + c]
            rows.append(jnp.pad(seg, ((0, m - c),)
                                + ((0, 0),) * len(rest)))
            off += c
        x = jnp.stack(rows)
    else:
        rest, dtype = _nonroot_meta(comm, root, like, counts)
        x = ctx.jax.device_put(
            jnp.zeros((comm.size, m) + rest, dtype), ctx.my)

    def build():
        def body(a):  # a: (1, n, m, *rest) -> my (m, *rest) segment
            from ompi_tpu.parallel import collectives as C

            full = C.bcast(a[0], AXIS, root)  # (n, m, *rest)
            me = lax.axis_index(AXIS)
            return lax.dynamic_index_in_dim(full, me, 0,
                                            keepdims=False)
        return ctx.smap(body, out_varying=True)

    fn = ctx.compiled(_key(x, "scatterv", counts, root), build)
    # only the root's buffer is payload (a non-root's is undefined)
    seg = _run("scatterv", comm, sendbuf if comm.rank == root else None,
               ctx.bind(fn, x), dtype=dtype, root=root, counts=counts)
    # ragged trim is per-rank-local (outside the collective program:
    # sharded outputs must be uniform across devices)
    return seg[:counts[comm.rank]]


def _nonroot_meta(comm, root, like, counts):
    """(trailing dims, dtype) for a non-root scatterv participant:
    from its own recvbuf template when given (zero host rounds — the
    MPI-idiomatic path), else from the metadata cache primed by one
    host bcast (see _scatter_meta)."""
    if like is not None:
        return tuple(like.shape[1:]), like.dtype
    rest, dtype = _scatter_meta(comm, ("scatterv", root), root, None)
    return tuple(rest), np.dtype(dtype)


@_slot("allgatherv")
def allgatherv_dev(comm, sendbuf, counts):
    """Ragged allgather on device: pad every block to max(counts),
    one compiled all_gather, then static slices reassemble the packed
    (sum(counts), ...) result — no host staging (the reference's
    accelerator path stages v-variants D2H; VERDICT r2 missing #4).
    counts is the full vector, identical on every rank, so the padded
    shapes agree with zero extra host rounds."""
    counts = tuple(int(c) for c in counts)
    if comm.size == 1:
        return sendbuf
    if len(counts) != comm.size:
        raise errors.MPIError(
            errors.ERR_COUNT,
            f"allgatherv: {len(counts)} counts for {comm.size} ranks")
    import jax.numpy as jnp
    from jax import lax

    ctx = _ctx(comm)
    m = max(counts)
    rest = sendbuf.shape[1:]
    x = jnp.pad(sendbuf, ((0, m - counts[comm.rank]),)
                + ((0, 0),) * len(rest))

    def build():
        def body(a):  # a: (1, m, *rest) -> packed (sum(counts), *rest)
            g = lax.all_gather(a[0], AXIS)  # (n, m, *rest)
            parts = [lax.slice_in_dim(g, r, r + 1)[0][:counts[r]]
                     for r in range(len(counts))]
            return jnp.concatenate(parts, axis=0)
        return ctx.smap(body, out_varying=False)

    fn = ctx.compiled(_key(x, "allgatherv", counts), build)
    return _run("allgatherv", comm, sendbuf, ctx.bind(fn, x))


@_slot("gatherv")
def gatherv_dev(comm, sendbuf, counts, root: int = 0):
    out = allgatherv_dev(comm, sendbuf, counts)
    return out if comm.rank == root else None


@_slot("alltoallv")
def alltoallv_dev(comm, sendbuf, scounts, rcounts, max_count=None, *,
                  _expert_tokens: bool = True):
    """Ragged all-to-all on device: segments pad to a uniform cell
    size M, one compiled all_to_all, static slices repack. M must be
    the GLOBAL max cell (a rank's own rows/columns don't bound cells
    between other peers), so it costs one tiny host max-allreduce per
    call — unless the caller passes ``max_count`` (e.g. a fixed MoE
    expert capacity, the common TPU dispatch pattern), which makes the
    path entirely host-free and is the recommended usage.

    ``_expert_tokens=False`` keeps the call out of the per-expert
    routed-token stats: scounts here index RANKS, and only the EP
    dispatch pattern (destination shard == expert) may feed the
    expert-imbalance view — the serve plane's DCN overflow legs
    exchange by rank and must not skew it."""
    scounts = tuple(int(c) for c in scounts)
    rcounts = tuple(int(c) for c in rcounts)
    if comm.size == 1:
        return sendbuf
    import jax.numpy as jnp
    from jax import lax

    ctx = _ctx(comm)
    if max_count is None:
        # the one host metadata round carries (max cell, payload) —
        # the global max sizes the padding, the global total bounds
        # the blowup UNIFORMLY across ranks (a per-rank decision
        # would diverge into different collectives). An unchanged
        # (scounts, rcounts) signature reuses the cached outcome, so
        # an iterative MoE loop pays the round once (r4 weak #2).
        sig = (scounts, rcounts)
        cached = (getattr(comm, "_coll_xla_a2av_meta", None)
                  if _a2av_cache_var.get() else None)
        if cached is not None and cached[0] == sig:
            m, fell_back = cached[1]
            pvar.record("coll_xla_a2av_meta_cached")
        else:
            pairs = comm.coll.allgather_obj(
                comm, (max(max(scounts), max(rcounts)), sum(scounts)))
            m = max(p[0] for p in pairs)
            factor = _a2av_pad_var.get()
            padded_cells = comm.size * comm.size * m
            true_cells = max(sum(p[1] for p in pairs), 1)
            fell_back = (factor > 0
                         and padded_cells > factor * true_cells)
            if _a2av_cache_var.get():
                comm._coll_xla_a2av_meta = (sig, (m, fell_back))
        if fell_back:
            # pathological skew (one hot expert): the staged path
            # moves the ragged counts without padding
            pvar.record("coll_xla_alltoallv_fallback")
            return staging.alltoallv_dev(comm, sendbuf, scounts,
                                         rcounts)
    else:
        m = int(max_count)
        if max(max(scounts), max(rcounts)) > m:
            raise errors.MPIError(
                errors.ERR_COUNT,
                f"alltoallv: max_count {m} below local max "
                f"{max(max(scounts), max(rcounts))}")
    if _expert_tokens:
        # the EP dispatch site — each destination shard is an expert,
        # so scounts IS the per-expert routed-token vector (ROADMAP
        # item 5's imbalance feed)
        _expert_load(scounts)
    rest = sendbuf.shape[1:]
    rows = []
    off = 0
    for c in scounts:
        rows.append(jnp.pad(sendbuf[off:off + c],
                            ((0, m - c),) + ((0, 0),) * len(rest)))
        off += c
    x = jnp.stack(rows)  # (n, m, *rest)

    def build():
        def body(a):  # (1, n, m, *rest) -> received cells (n, m, *rest)
            return lax.all_to_all(a, AXIS, split_axis=1, concat_axis=0,
                                  tiled=False)[:, 0]
        return ctx.smap(body, out_varying=True)

    fn = ctx.compiled(_key(x, "alltoallv", m), build)
    # accounted by the actual splits, not the padded cells: bytes to
    # peer r = scounts[r] rows (after the fallback decision, so the
    # device path never counts a host-staged call)
    cells = _run("alltoallv", comm, sendbuf, ctx.bind(fn, x),
                 counts=scounts)  # (n, m, *rest)
    # ragged repack is per-rank-local (outside the collective program:
    # sharded outputs must be uniform across devices)
    return jnp.concatenate(
        [cells[r, :rcounts[r]] for r in range(comm.size)], axis=0)


@_slot("reduce_scatter")
def reduce_scatter_dev(comm, sendbuf, counts, op=op_mod.SUM,
                       deterministic: Optional[str] = None):
    """Ragged MPI_Reduce_scatter on device: full on-device reduction
    (shares allreduce's compiled program and cache entry), then each
    rank slices its counts[rank] rows locally — ragged outputs never
    enter the uniform-shape collective program."""
    if not _op_ok(op):
        return staging.reduce_scatter_dev(comm, sendbuf, counts, op)
    counts = [int(c) for c in counts]
    # erroneous calls raise MPIError so the comm's errhandler sees
    # them (the MPI-4 convention part/host.py documents — a bare
    # ValueError would bypass the API entry's errhandler dispatch)
    if len(counts) != comm.size:
        raise errors.MPIError(
            errors.ERR_COUNT,
            f"reduce_scatter: {len(counts)} counts for "
            f"{comm.size} ranks")
    if sum(counts) != sendbuf.shape[0]:
        raise errors.MPIError(
            errors.ERR_COUNT,
            f"reduce_scatter: counts sum to {sum(counts)} but sendbuf "
            f"dim0 is {sendbuf.shape[0]} (jax slicing would clamp "
            "silently)")
    full = allreduce_dev(comm, sendbuf, op, deterministic)
    off = sum(counts[:comm.rank])
    return full[off:off + counts[comm.rank]]


def _prefix_dev(kind: str, comm, sendbuf, op):
    """A prefix over comm ranks (lax.associative_scan under shard_map
    — log-depth on device), ``kind`` 'scan' or 'exscan'."""
    from ompi_tpu.parallel import collectives as C

    ctx = _ctx(comm)
    opn = op if isinstance(op, op_mod.Op) else op_mod.BUILTIN[op]
    prefix = {"scan": C.scan, "exscan": C.exscan}[kind]

    def build():
        return ctx.smap(lambda a: prefix(a[0], AXIS, opn),
                        out_varying=True)

    fn = ctx.compiled(_key(sendbuf, kind, opn.name), build)
    return _run(kind, comm, sendbuf, ctx.bind(fn, sendbuf))


@_slot("scan")
def scan_dev(comm, sendbuf, op=op_mod.SUM,
             deterministic: Optional[str] = None):
    """Inclusive prefix over comm ranks."""
    if not _op_ok(op):
        return staging.scan_dev(comm, sendbuf, op)
    if comm.size == 1:
        return sendbuf
    return _prefix_dev("scan", comm, sendbuf, op)


@_slot("exscan")
def exscan_dev(comm, sendbuf, op=op_mod.SUM,
               deterministic: Optional[str] = None):
    """Exclusive prefix; rank 0 gets zeros (MPI leaves it undefined)."""
    if not _op_ok(op):
        return staging.exscan_dev(comm, sendbuf, op)
    if comm.size == 1:
        import jax.numpy as jnp

        return jnp.zeros_like(sendbuf)
    return _prefix_dev("exscan", comm, sendbuf, op)


# ---------------------------------------------------------------------------
# fused (bucketed) allreduce — the gradient-bucketing engine


class _FusePlan:
    """dtype-segregated bucket layout for one leaf signature (the
    NCCL/Horovod/DDP gradient-bucket plan). ``buckets`` is a tuple of
    tuples of leaf indices; a bucket closes once its byte total
    reaches ``bucket_bytes`` (overflow allowed), which bounds compiled
    launches at ceil(total_bytes/bucket_bytes) + n_dtypes — the
    invariant the launch-count regression test asserts."""

    __slots__ = ("buckets", "nbytes")

    def __init__(self, metas, bucket_bytes: int) -> None:
        groups: dict = {}
        order = []
        for i, (_shape, dtype, nb) in enumerate(metas):
            if dtype not in groups:
                groups[dtype] = []
                order.append(dtype)
            groups[dtype].append((i, nb))
        buckets = []
        for dt in order:
            cur, cur_bytes = [], 0
            for i, nb in groups[dt]:
                cur.append(i)
                cur_bytes += nb
                if bucket_bytes > 0 and cur_bytes >= bucket_bytes:
                    buckets.append(tuple(cur))
                    cur, cur_bytes = [], 0
            if cur:
                buckets.append(tuple(cur))
        self.buckets = tuple(buckets)
        self.nbytes = sum(m[2] for m in metas)


def _fuse_metas(leaves):
    return tuple((tuple(l.shape), str(l.dtype),
                  int(l.size) * np.dtype(l.dtype).itemsize)
                 for l in leaves)


def _fuse_plan(ctx, metas, treedef, opn, det):
    bb = int(_bucket_var.get())
    return ctx.plan((metas, treedef, opn.name, det, bb),
                    lambda: _FusePlan(metas, bb))


def _bucket_fn(ctx, metas, idxs, opn, det: Optional[str], hier: bool):
    """ONE compiled concat+allreduce+split program for a bucket. The
    cache key depends only on (member signature, op, mode) — the
    all-at-Start fused path and the partitioned path resolve to the
    SAME executable, which is what makes Pallreduce_init bit-identical
    to Allreduce_multi by construction."""
    from ompi_tpu.parallel import collectives as C

    sig = tuple((metas[i][0], metas[i][1]) for i in idxs)

    def build():
        def body(args):
            import jax.numpy as jnp

            flat = (jnp.concatenate(
                [a[0].reshape(-1) for a in args])
                if len(args) > 1 else args[0][0].reshape(-1))
            if hier:
                from ompi_tpu.parallel import hierarchical as H

                red = H.allreduce(flat, op=opn)
            else:
                red = C.allreduce(flat, AXIS, opn, det)
            outs, off = [], 0
            for a in args:  # static split back to member shapes
                n = a[0].size
                outs.append(red[off:off + n].reshape(a.shape[1:]))
                off += n
            return tuple(outs)

        if hier:
            return ctx.smap_hier(body, out_varying=False)
        return ctx.smap(body, out_varying=False)

    return ctx.compiled(("fused_allreduce", sig, opn.name, det, hier),
                        build)


def _fuse_prep(ctx, comm, leaves, treedef, opn,
               det: Optional[str]):
    """Build (or reuse) the bucket plan and each bucket's ONE compiled
    concat+allreduce+split program, bind the operands, and return a
    zero-arg launcher producing the unflattened pytree.

    Bit-identity: under ``deterministic='linear'`` the fold is an
    elementwise rank-order reduction, and concatenation never changes
    an element's per-rank fold order — fused results are bitwise
    identical to the per-buffer loop (tested)."""
    import jax

    metas = _fuse_metas(leaves)
    plan = _fuse_plan(ctx, metas, treedef, opn, det)
    hier = det is None and ctx.mesh2d is not None
    to_g = ctx.to_global_hier if hier else ctx.to_global

    launches = []
    for idxs in plan.buckets:
        fn = _bucket_fn(ctx, metas, idxs, opn, det, hier)
        gs = tuple(to_g(leaves[i]) for i in idxs)
        launches.append((fn, gs, idxs))

    def launch():
        outs = [None] * len(leaves)
        for fn, gs, idxs in launches:
            res = ctx.launch(fn, gs)
            for j, i in enumerate(idxs):
                outs[i] = ctx.my_shard(res[j])
        pvar.record("coll_xla_fused_bytes", plan.nbytes)
        return jax.tree.unflatten(treedef, outs)

    return launch


def _allreduce_multi_prep(comm, bufs, op=op_mod.SUM,
                          deterministic: Optional[str] = None):
    import jax

    leaves, treedef = jax.tree.flatten(bufs)
    opn = op if isinstance(op, op_mod.Op) else op_mod.BUILTIN[op]
    return _fuse_prep(_ctx(comm), comm, leaves, treedef, opn,
                      _det(deterministic))


@_slot("allreduce_multi")
def allreduce_multi_dev(comm, bufs, op=op_mod.SUM,
                        deterministic: Optional[str] = None):
    """Fused allreduce over a list/pytree of device buffers: flatten
    into dtype-segregated flat buckets (target size cvar
    ``coll_xla_bucket_bytes``), ONE compiled psum per bucket, split
    back — amortizing the per-buffer Python dispatch round that
    dominates many-small-gradient steps. Returns a new pytree with
    the input structure."""
    if not _op_ok(op):
        return staging.allreduce_multi_dev(comm, bufs, op,
                                           deterministic=deterministic)
    import jax

    leaves = jax.tree.leaves(bufs)
    if comm.size == 1 or not leaves:
        return bufs
    return _run("allreduce_multi", comm, bufs,
                _allreduce_multi_prep(comm, bufs, op, deterministic),
                dtype=getattr(leaves[0], "dtype", ""),
                algorithm=_det(deterministic))


# ---------------------------------------------------------------------------
# nonblocking device collectives — requests backed by PJRT readiness


class DeviceRequest:
    """MPI request over an asynchronously-dispatched device collective.

    PJRT dispatch is already asynchronous: the jitted program returns a
    jax.Array future immediately and the TPU runs in the background.
    This request EXPOSES that (r2 VERDICT missing #3) instead of hiding
    it — the analog of ob1's accelerator outstanding-copy event arrays
    (ompi/mca/pml/ob1/pml_ob1_accelerator.c:57-89), with the jax.Array
    itself as the completion event. ``.array`` is the result (None on
    non-root reduce/gather sides).

    Duck-types ompi_tpu.pml.request.Request (test/wait/cancel/free and
    the wait_all/test_all helpers hold on the shared contract:
    ``completed`` flag + non-blocking ``test()``).
    """

    def __init__(self, array, launched=None) -> None:
        from ompi_tpu.pml import request as rq

        self.id = next(rq._req_ids)
        self.status = rq.Status()
        self.persistent = False
        self.array = array
        self._launched = launched  # of its launch, for span `wait`
        self._done = array is None

    @property
    def completed(self) -> bool:
        """Live readiness view. The plural helpers (rq.wait_all/
        test_all/...) poll ``.completed`` and spin the host progress
        engine, which never advances a device program — so this MUST
        probe the array, not cache a flag only test()/wait() flip."""
        if not self._done:
            import jax

            try:  # .array may be a pytree (fused allreduce results)
                if all(bool(a.is_ready())
                       for a in jax.tree.leaves(self.array)):
                    self._done = True
            except AttributeError:  # backend without is_ready:
                # readiness polling degrades to blocking (the same
                # guarantee the pre-property test() gave) — never
                # report completion that has not happened
                _wait(self.array, self._launched)
                self._done = True
        return self._done

    def test(self) -> bool:
        return self.completed

    def wait(self, timeout=None):
        if not self._done:
            _wait(self.array, self._launched)
            self._done = True
        return self.status

    def cancel(self) -> None:  # dispatched programs are not cancelable
        pass

    def free(self) -> None:
        pass

    def retrieve_status(self):
        return self.status


def _barrier_prep(comm):
    import jax.numpy as jnp

    from ompi_tpu.parallel import collectives as C

    ctx = _ctx(comm)

    def build():
        return ctx.smap(lambda a: C.allreduce(a[0], AXIS, op_mod.SUM),
                        out_varying=False)

    fn = ctx.compiled(("barrier",), build)
    return ctx.bind(
        fn, ctx.jax.device_put(jnp.ones((1,), jnp.int32), ctx.my))


def ibarrier_dev(comm):
    """Nonblocking device barrier: the 1-element psum is dispatched;
    the request completes when every plane member has entered."""
    if comm.size == 1:
        return DeviceRequest(None)
    return DeviceRequest(*_launched(_run, "barrier", comm, None,
                                    _barrier_prep(comm)))


class PersistentDeviceRequest:
    """MPI-4 persistent device collective (reference: the coll.h
    *_init slot table): init runs the FULL prep — plan, compile, and
    operand bind (jax arrays are immutable, so the bound operand never
    changes) — and every ``start()`` is one cached-executable launch
    of the zero-arg launcher, zero re-planning. jax arrays are
    immutable, so each cycle's result is a fresh array in ``.array``."""

    def __init__(self, launch) -> None:
        from ompi_tpu.pml import request as rq

        self.id = next(rq._req_ids)
        self.status = rq.Status()
        self.persistent = True
        self._launch = launch
        self._inner: Optional[DeviceRequest] = None

    def start(self) -> None:
        if self._launch is None:
            raise errors.MPIError(
                errors.ERR_REQUEST,
                "start: persistent request already freed (MPI calls "
                "starting a freed request erroneous)")
        self._inner = DeviceRequest(*_launched(self._launch))

    def rebind(self, *args, **kwargs) -> None:
        """Rebind the request's operands to fresh values of the SAME
        signature without re-planning or re-compiling — the zero-3
        parameter-stream hook (the optimizer replaces its shard
        arrays every step; the per-layer allgather keeps its cached
        executable and only swaps the bound inputs). Only preps that
        install a ``rebind`` hook support it; the trivial gated paths
        (size-1 comms, empty states) raise ERR_NOT_SUPPORTED and the
        caller re-inits instead (init is free there — there is no
        prep to redo)."""
        if self._launch is None:
            raise errors.MPIError(
                errors.ERR_REQUEST,
                "rebind: persistent request already freed")
        if self.active:
            raise errors.MPIError(
                errors.ERR_REQUEST,
                "rebind: cycle still active — wait() it to "
                "completion before swapping operands")
        rb = getattr(self._launch, "rebind", None)
        if rb is None:
            raise errors.MPIError(
                errors.ERR_NOT_SUPPORTED,
                "rebind: this persistent request binds per start "
                "(trivial/gated path) — free() and re-init instead")
        rb(*args, **kwargs)

    def discard(self) -> None:
        """Drop the completed cycle's result so its device arrays can
        be reclaimed — the zero-3 free-after-use hook (a gathered
        layer's full parameters would otherwise stay pinned by
        ``.array`` until the next start). The request stays usable."""
        self._inner = None

    @property
    def active(self) -> bool:
        """A started cycle whose result is not yet ready (start_all
        refuses to restart these — MPI calls it erroneous)."""
        return self._inner is not None and not self._inner.test()

    @property
    def completed(self) -> bool:
        """Live view over the in-flight cycle, so the plural wait/test
        helpers (which poll .completed) see device completion; an
        INACTIVE persistent request is complete with an empty status,
        per MPI — matching the host _PersistentRequest."""
        return True if self._inner is None else self._inner.test()

    @property
    def array(self):
        return None if self._inner is None else self._inner.array

    def test(self) -> bool:
        return self.completed

    def wait(self, timeout=None):
        if self._inner is None:
            return self.status  # inactive: immediately complete (MPI)
        return self._inner.wait(timeout)

    def retrieve_status(self):
        return self.status

    def cancel(self) -> None:
        pass

    def free(self) -> None:
        # release the launcher's bound operands (the param shards /
        # gathered results it pins) and the last cycle's arrays; a
        # start() after free raises ERR_REQUEST per MPI
        rel = getattr(self._launch, "release", None)
        if rel is not None:
            rel()
        self._launch = None
        self._inner = None


def _pinit(fn):
    """persistent-init variant of a slot WITHOUT a prep phase (the
    staged fallback path): bind the arguments now, re-run the whole
    slot at every start()."""
    def pslot(*args, **kwargs):
        return PersistentDeviceRequest(lambda: fn(*args, **kwargs))
    pslot.__name__ = fn.__name__ + "_init"
    return pslot


def _pprep(prep, blocking, name: str, gates=()):
    """persistent-init slot over a prep function: everything that can
    be hoisted out of the start/wait cycle — planning, compilation,
    sharding construction — runs at init; start() dispatches the
    cached executable. ``gates(comm, buf)`` returning True selects the
    trivial bind-now path (size-1 comms, non-traceable ops), which
    re-runs the blocking slot per start."""
    def pslot(comm, buf, *args, **kwargs):
        for gate in gates:
            if gate(comm, buf, *args, **kwargs):
                return PersistentDeviceRequest(
                    lambda: blocking(comm, buf, *args, **kwargs))
        return PersistentDeviceRequest(
            prep(comm, buf, *args, **kwargs))
    pslot.__name__ = name
    return pslot


def _gate_size1(comm, buf, *a, **k) -> bool:
    return comm.size == 1


def _gate_op(comm, buf, *args, **kwargs) -> bool:
    op = args[0] if args else kwargs.get("op", op_mod.SUM)
    return not _op_ok(op)


allreduce_init_dev = _pprep(
    _allreduce_prep, allreduce_dev, "allreduce_init_dev",
    gates=(_gate_op, _gate_size1))
bcast_init_dev = _pprep(
    _bcast_prep, bcast_dev, "bcast_init_dev", gates=(_gate_size1,))
allgather_init_dev = _pprep(
    _allgather_prep, allgather_dev, "allgather_init_dev",
    gates=(_gate_size1,))
alltoall_init_dev = _pprep(
    _alltoall_prep, alltoall_dev, "alltoall_init_dev",
    gates=(_gate_size1,))
reduce_scatter_block_init_dev = _pprep(
    _reduce_scatter_block_prep, reduce_scatter_block_dev,
    "reduce_scatter_block_init_dev", gates=(_gate_op, _gate_size1))


def _multi_empty(comm, bufs, *a, **k) -> bool:
    import jax

    return not jax.tree.leaves(bufs)


allreduce_multi_init_dev = _pprep(
    _allreduce_multi_prep, allreduce_multi_dev,
    "allreduce_multi_init_dev",
    gates=(_gate_op, _gate_size1, _multi_empty))


# ---------------------------------------------------------------------------
# fused (bucketed) reduce_scatter / allgather — the zero/ sharded
# data-parallel engine. Same _FusePlan dtype buckets, extended with
# pad-to-comm-size (zero.layout.ZeroPlan) so each bucket lowers to ONE
# tiled reduce_scatter/all_gather; plans + executables live in the
# same _Ctx LRU caches as the fused allreduce.


def _zero_plan(ctx, metas, treedef):
    """Pad-and-shard bucket plan, cached per (signature, bucket size,
    comm size). Op/determinism are NOT in the key: the layout is
    geometry only, so one plan serves the RS and AG directions."""
    from ompi_tpu.zero import layout as _zl

    bb = int(_bucket_var.get())
    return ctx.plan(("zero", metas, treedef, bb, ctx.n),
                    lambda: _zl.ZeroPlan(metas, bb, ctx.n))


def _zero_rs_fn(ctx, metas, idxs, pad: int, opn, det: Optional[str]):
    """ONE compiled concat+pad+reduce_scatter program for a bucket.
    Bit-identity: under 'linear' C.reduce_scatter folds in exact rank
    order then slices — elementwise identical to the per-buffer
    allreduce-linear path, and concatenation/zero-padding never
    change an element's fold order. Keyed like the fused allreduce so
    the partitioned path resolves to the SAME executable."""
    from ompi_tpu.parallel import collectives as C

    sig = tuple((metas[i][0], metas[i][1]) for i in idxs)

    def build():
        def body(args):
            import jax.numpy as jnp

            flat = (jnp.concatenate(
                [a[0].reshape(-1) for a in args])
                if len(args) > 1 else args[0][0].reshape(-1))
            if pad:
                flat = jnp.pad(flat, (0, pad))
            return C.reduce_scatter(flat, AXIS, opn, scatter_dim=0,
                                    tiled=True, deterministic=det)

        return ctx.smap(body, out_varying=True)

    return ctx.compiled(("zero_rs", sig, pad, opn.name, det), build)


def _zero_ag_fn(ctx, metas, idxs, elems: int, pad: int):
    """ONE compiled all_gather+split program for a bucket: the local
    shard gathers tiled in rank order (= the pack order), the pad
    tail drops, and the static split restores member leaf shapes."""
    from ompi_tpu.parallel import collectives as C

    sig = tuple((metas[i][0], metas[i][1]) for i in idxs)
    shapes = tuple(metas[i][0] for i in idxs)

    def build():
        def body(a):
            full = C.allgather(a[0], AXIS, tiled=True, gather_dim=0)
            outs, off = [], 0
            for shape in shapes:
                k = 1
                for s in shape:
                    k *= int(s)
                outs.append(full[off:off + k].reshape(shape))
                off += k
            return tuple(outs)

        return ctx.smap(body, out_varying=False)

    return ctx.compiled(("zero_ag", sig, elems, pad), build)


def _zero_empty_state(comm, treedef):
    from ompi_tpu.zero import layout as _zl

    plan = _zl.ZeroPlan((), int(_bucket_var.get()), comm.size)
    return _zl.ShardedState(plan, (), treedef, [], comm.rank,
                            comm.size)


def _reduce_scatter_multi_prep(comm, bufs, op=op_mod.SUM,
                               deterministic: Optional[str] = None):
    """Plan + compile + bind the bucketed reduce_scatter NOW; the
    returned zero-arg launcher runs one cached dispatch per bucket
    and yields the rank's ShardedState (the ZeRO gradient shards)."""
    import jax

    from ompi_tpu.zero import layout as _zl

    leaves, treedef = jax.tree.flatten(bufs)
    if not leaves:
        return lambda: _zero_empty_state(comm, treedef)
    opn = op if isinstance(op, op_mod.Op) else op_mod.BUILTIN[op]
    det = _det(deterministic)
    ctx = _ctx(comm)
    metas = _fuse_metas(leaves)
    plan = _zero_plan(ctx, metas, treedef)
    launches = []
    for b, idxs in enumerate(plan.buckets):
        fn = _zero_rs_fn(ctx, metas, idxs,
                         plan.padded[b] - plan.elems[b], opn, det)
        gs = tuple(ctx.to_global(leaves[i]) for i in idxs)
        launches.append((fn, gs))

    def launch():
        shards = []
        for fn, gs in launches:
            shards.append(ctx.my_shard(ctx.launch(fn, gs)))
            pvar.record("zero_rs_launches")
        pvar.record("zero_fused_bytes", plan.nbytes)
        pvar.record("zero_pad_bytes", plan.pad_bytes)
        return _zl.ShardedState(plan, metas, treedef, shards,
                                comm.rank, ctx.n)

    return launch


@_slot("reduce_scatter_multi")
def reduce_scatter_multi_dev(comm, bufs, op=op_mod.SUM,
                             deterministic: Optional[str] = None):
    """Bucketed reduce_scatter over a pytree of device buffers (the
    ZeRO gradient-sharding step): dtype-segregated flat buckets padded
    to a multiple of comm size (zero.layout.ZeroPlan), ONE compiled
    tiled reduce_scatter per bucket, returning this rank's
    ShardedState — full reduced gradients are never materialized.
    'linear' determinism is bit-identical to the per-buffer
    allreduce+slice path."""
    if not _op_ok(op):
        return staging.reduce_scatter_multi_dev(
            comm, bufs, op, deterministic=deterministic)
    if comm.size == 1:
        # reducing over one rank is the identity: the shard is a
        # local pack+slice, no plane/collective needed (the same
        # trivial fast path the other size-1 device slots take)
        from ompi_tpu.zero import layout as _zl

        return _zl.ShardedState.from_full(comm, bufs)
    import jax

    leaves = jax.tree.leaves(bufs)
    return _run("reduce_scatter_multi", comm, bufs,
                _reduce_scatter_multi_prep(comm, bufs, op,
                                           deterministic),
                dtype=getattr(leaves[0], "dtype", "") if leaves else "",
                algorithm=_det(deterministic))


def _zero_state_check(comm, state) -> None:
    """MPI erroneous-call validation for the allgather direction (the
    part/host.py MPIError convention, applied to the *_multi entry
    points from day one)."""
    from ompi_tpu.zero import layout as _zl

    if not isinstance(state, _zl.ShardedState):
        raise errors.MPIError(
            errors.ERR_ARG,
            f"Allgather_multi: operand is {type(state).__name__}, "
            "expected a ShardedState (the Reduce_scatter_multi / "
            "ShardedState.from_full result)")
    if state.n != comm.size:
        raise errors.MPIError(
            errors.ERR_COUNT,
            f"Allgather_multi: state sharded {state.n} ways on a "
            f"size-{comm.size} communicator")
    if len(state.shards) != len(state.plan.buckets):
        raise errors.MPIError(
            errors.ERR_COUNT,
            f"Allgather_multi: {len(state.shards)} shards for "
            f"{len(state.plan.buckets)} plan buckets")
    for b, s in enumerate(state.shards):
        k = state.plan.shard_elems[b]
        if tuple(s.shape) != (k,) \
                or str(s.dtype) != state.plan.dtypes[b]:
            raise errors.MPIError(
                errors.ERR_COUNT,
                f"Allgather_multi: bucket {b} shard is "
                f"{tuple(s.shape)}/{s.dtype}, plan expects "
                f"({k},)/{state.plan.dtypes[b]} (shard-wise updates "
                "must preserve shape and dtype)")


def _allgather_multi_prep(comm, state):
    """Compile + bind the bucketed allgather NOW (operand = the
    state's current shards; like every persistent device collective
    the binding is per-init — jax arrays are immutable). The returned
    launcher carries two hooks the persistent form exposes for the
    zero-3 parameter stream: ``rebind(new_state)`` swaps the bound
    shard arrays for a same-plan state with NO re-planning or
    re-compiling (the optimizer replaces its shards every step), and
    ``release()`` drops the bound operands so nothing pins them."""
    ctx = _ctx(comm)
    _zero_state_check(comm, state)
    plan, metas = state.plan, state.metas
    launches = []
    for b, idxs in enumerate(plan.buckets):
        fn = _zero_ag_fn(ctx, metas, idxs, plan.elems[b],
                         plan.padded[b] - plan.elems[b])
        launches.append([fn, ctx.to_global(state.shards[b]), idxs])

    import jax

    n_leaves = sum(len(idxs) for idxs in plan.buckets)

    def launch():
        outs = [None] * n_leaves
        for fn, g, idxs in launches:
            if g is None:
                raise errors.MPIError(
                    errors.ERR_REQUEST,
                    "allgather_multi start: operands released — "
                    "rebind() a fresh state first")
            res = ctx.launch(fn, g)
            for j, i in enumerate(idxs):
                outs[i] = ctx.my_shard(res[j])
            pvar.record("zero_ag_launches")
        pvar.record("zero_fused_bytes", plan.nbytes)
        return jax.tree.unflatten(state.treedef, outs)

    def rebind(new_state) -> None:
        _zero_state_check(comm, new_state)
        if new_state.plan.buckets != plan.buckets:
            raise errors.MPIError(
                errors.ERR_ARG,
                "allgather_multi rebind: state packed by a different "
                "plan (the compiled programs are layout-specialized; "
                "re-init for a new bucket layout)")
        for b, entry in enumerate(launches):
            entry[1] = ctx.to_global(new_state.shards[b])

    def release() -> None:
        for entry in launches:
            entry[1] = None

    launch.rebind = rebind
    launch.release = release
    return launch


@_slot("allgather_multi")
def allgather_multi_dev(comm, state):
    """Bucketed allgather of a ShardedState back to the full pytree
    (the ZeRO parameter-rebuild step): ONE compiled tiled all_gather
    per bucket, rank-order concat (= the pack order), pad tail
    dropped, leaf shapes restored."""
    _zero_state_check(comm, state)
    if not state.shards:
        import jax

        return jax.tree.unflatten(state.treedef, [])
    if comm.size == 1:
        # n=1 shards ARE the full padded buckets: unpack locally
        return state.unpack(state.shards)
    return _run("allgather_multi", comm, state,
                _allgather_multi_prep(comm, state),
                dtype=state.plan.dtypes[0])


@_slot("allgather_multi_bucket")
def allgather_multi_bucket_dev(comm, state, b: int):
    """Gather ONE bucket of a ShardedState: the member leaves (in
    ``plan.buckets[b]`` order) of the full tree, through the same
    cached per-bucket executable as allgather_multi_dev. The
    bucket-granular form the ZeroOptimizer dirty-skip path uses —
    buckets whose shards did not change this step reuse the previous
    cycle's gathered leaves instead of relaunching (the
    ``zero_ag_skipped`` accounting lives with the caller)."""
    _zero_state_check(comm, state)
    plan, metas = state.plan, state.metas
    if not 0 <= b < len(plan.buckets):
        raise errors.MPIError(
            errors.ERR_COUNT,
            f"allgather_multi_bucket: bucket {b} out of range for a "
            f"{len(plan.buckets)}-bucket plan")
    idxs = plan.buckets[b]
    if comm.size == 1:
        # the n=1 shard IS the full padded bucket: unpack locally
        flat = state.shards[b]
        outs, off = [], 0
        for i in idxs:
            shape = metas[i][0]
            k = 1
            for s in shape:
                k *= int(s)
            outs.append(flat[off:off + k].reshape(shape))
            off += k
        return outs
    ctx = _ctx(comm)
    fn = _zero_ag_fn(ctx, metas, idxs, plan.elems[b],
                     plan.padded[b] - plan.elems[b])
    g = ctx.to_global(state.shards[b])

    def launch():
        res = ctx.launch(fn, g)
        pvar.record("zero_ag_launches")
        return [ctx.my_shard(r) for r in res]

    # one bucket of an allgather_multi, and accounted as that
    return _run("allgather_multi_bucket", comm, None, launch,
                nbytes=sum(metas[i][2] for i in idxs),
                dtype=plan.dtypes[b], kind="allgather_multi")


def _multi_state_empty(comm, state, *a, **k) -> bool:
    return not getattr(state, "shards", None)


reduce_scatter_multi_init_dev = _pprep(
    _reduce_scatter_multi_prep, reduce_scatter_multi_dev,
    "reduce_scatter_multi_init_dev",
    gates=(_gate_op, _gate_size1, _multi_empty))
allgather_multi_init_dev = _pprep(
    _allgather_multi_prep, allgather_multi_dev,
    "allgather_multi_init_dev",
    gates=(_gate_size1, _multi_state_empty))


# ---------------------------------------------------------------------------
# partitioned fused allreduce (MPI-4 part/ subsystem, device payoff)


def _flush_bucket(req, b: int, trigger: Optional[int], op: str,
                  span: str, subsys: str) -> bool:
    """Dispatch bucket ``b`` of a partitioned cycle: the bucket's
    program IS an ``op`` launch, run through the dispatch seam and
    attributed to the part context so overlap traffic stays
    separable (a request over a bare context has nobody to tell).
    The flush span carries the Pready that triggered it, so a
    timeline shows WHICH partition released each bucket (the Pready
    -> flush causality the overlap design rests on). Returns whether
    the dispatch overlapped the producer."""
    fn, idxs = req._buckets[b]
    overlap = req._n_ready < req._n
    nb = sum(req._metas[i][2] for i in idxs)
    bound = tuple(req._bound[i] for i in idxs)

    flush = functools.partial(req._ctx.launch, fn, bound)
    if req._comm is not None:
        flush = functools.partial(_run, op, req._comm, None, flush,
                                  nbytes=nb, ctx="part",
                                  dtype=req._metas[idxs[0]][1])
    if not _trace.active():
        req._results[b] = flush()
        return overlap
    t0 = _trace.now()
    with _trace.span(span, subsys, bucket=b, trigger_partition=trigger,
                     overlap=overlap, nbytes=nb):
        # the cycle's wait names the launch of its last flush
        req._results[b], req._launched = _launched(flush)
    if _trace.RECORDER is not None:
        _trace.hist(span, nb, _trace.now() - t0)
    return overlap


class PartitionedAllreduceRequest:
    """MPI-4 partitioned fused allreduce handle (Pallreduce_init —
    the part/ subsystem's device-path payoff).

    Partitions are the leaves of the bound pytree in jax.tree.flatten
    order. Init does the full prep: the _FusePlan dtype-bucket layout
    and each bucket's ONE compiled concat+reduce+split program are
    resolved through the SAME _Ctx caches and keys as Allreduce_multi
    (shared executables -> bit-identical under 'linear', zero
    recompiles after init — pvar-verified). start() opens a cycle;
    Pready(i[, value]) marks leaf i ready — optionally rebinding this
    cycle's fresh value — and the moment a bucket's LAST member leaf
    is ready its compiled psum dispatches (PJRT-async), so early
    buckets' communication overlaps production of later gradients
    (the DDP/Horovod backward-hook overlap, through a standard MPI-4
    surface). wait() drains the tail and assembles ``.array``.

    Duck-types the request contract (completed/test/wait/free);
    inactive reads as complete, per MPI."""

    def __init__(self, ctx, leaves, treedef, opn,
                 det: Optional[str], comm=None) -> None:
        from ompi_tpu.pml import request as rq

        self.id = next(rq._req_ids)
        self.status = rq.Status()
        self.persistent = True
        self._ctx = ctx
        self._comm = comm  # traffic attribution (monitoring plane)
        self._treedef = treedef
        self._n = len(leaves)
        metas = _fuse_metas(leaves)
        plan = _fuse_plan(ctx, metas, treedef, opn, det)
        self.nbytes = plan.nbytes
        hier = det is None and ctx.mesh2d is not None
        self._to_g = ctx.to_global_hier if hier else ctx.to_global
        self._metas = metas
        self._buckets = tuple(
            (_bucket_fn(ctx, metas, idxs, opn, det, hier), idxs)
            for idxs in plan.buckets)
        self._leaf_bucket = {i: b
                             for b, (_fn, idxs)
                             in enumerate(self._buckets)
                             for i in idxs}
        # template operands bound now: a Pready without a fresh value
        # (static tensors, tests) reuses them — jax arrays are
        # immutable, so rebinding is per-cycle state, not mutation
        self._bound = [self._to_g(l) for l in leaves]
        self._ready = None  # None = inactive
        self._arr = None

    @property
    def active(self) -> bool:
        return self._ready is not None

    @property
    def array(self):
        """The synced pytree of the last completed cycle."""
        return self._arr

    def start(self) -> None:
        if self.active:
            raise errors.MPIError(
                errors.ERR_REQUEST,
                "Pallreduce start: previous cycle still active — "
                "wait() it to completion first (starting an active "
                "request is erroneous)")
        self._ready = [False] * self._n
        self._n_ready = 0
        self._pending = [len(idxs) for _fn, idxs in self._buckets]
        self._results = [None] * len(self._buckets)
        self._launched = None
        self._fl_tok = _dispatch.cycle_enter(
            "pallreduce_cycle", self._comm, self.nbytes)

    def Pready(self, idx: int, value=None) -> None:
        if self._ready is None:
            raise errors.MPIError(
                errors.ERR_REQUEST,
                f"Pready({idx}): request inactive — call start() "
                "before marking partitions ready")
        if self._ready[idx]:
            raise errors.MPIError(
                errors.ERR_ARG,
                f"Pready({idx}): partition already marked ready "
                "this cycle (double-Pready is erroneous)")
        if value is not None:
            shape, dtype, _nb = self._metas[idx]
            if tuple(value.shape) != shape or str(value.dtype) != dtype:
                raise errors.MPIError(
                    errors.ERR_ARG,
                    f"Pready({idx}): value {tuple(value.shape)}/"
                    f"{value.dtype} does not match the bound template "
                    f"leaf {shape}/{dtype} (compiled programs are "
                    "shape-specialized; re-init for a new signature)")
            self._bound[idx] = self._to_g(value)
        self._ready[idx] = True
        self._n_ready += 1
        pvar.record("part_pready")
        if _trace.active():
            _trace.instant("pready", "part", partition=idx)
        b = self._leaf_bucket[idx]
        self._pending[b] -= 1
        if self._pending[b] == 0:
            self._flush(b, idx)

    def Pready_range(self, lo: int, hi: int) -> None:
        for i in range(lo, hi + 1):
            self.Pready(i)

    def Pready_list(self, idxs) -> None:
        for i in idxs:
            self.Pready(i)

    def _flush(self, b: int, trigger: Optional[int] = None) -> None:
        pvar.record("part_bucket_flushes")
        if _flush_bucket(self, b, trigger, "allreduce",
                         "part_bucket_flush", "part"):
            # dispatched while later partitions are still pending:
            # this bucket's wire time is hidden behind the producer
            pvar.record("part_overlap_flushes")

    @property
    def completed(self) -> bool:
        """Live view for the plural wait/test helpers; inactive is
        complete (MPI). An active cycle with unready partitions is
        incomplete — only wait() raises on it (a poll is not a
        completion demand)."""
        if self._ready is None:
            return True
        if self._n_ready < self._n:
            return False
        import jax

        try:
            return all(bool(a.is_ready())
                       for r in self._results
                       for a in jax.tree.leaves(r))
        except AttributeError:  # backend without is_ready
            _wait(self._results, self._launched)
            return True

    def test(self) -> bool:
        return self.completed

    def _finalize(self) -> None:
        """Close the cycle: split the bucket results back into leaf
        shards, block, publish ``.array``, go inactive."""
        import jax

        outs = [None] * self._n
        for b, (_fn, idxs) in enumerate(self._buckets):
            res = self._results[b]
            for j, i in enumerate(idxs):
                outs[i] = self._ctx.my_shard(res[j])
        _wait(outs, self._launched)
        pvar.record("coll_xla_fused_bytes", self.nbytes)
        self._arr = jax.tree.unflatten(self._treedef, outs)
        self._ready = None  # cycle closed: back to inactive
        tok, self._fl_tok = self._fl_tok, None
        _dispatch.cycle_exit(tok)

    def wait(self, timeout=None):
        if self._ready is None:
            return self.status  # inactive: immediately complete
        if self._n_ready < self._n:
            missing = [i for i, r in enumerate(self._ready) if not r]
            raise errors.MPIError(
                errors.ERR_REQUEST,
                f"Pallreduce wait: partitions {missing} never marked "
                "ready — the bucket collective cannot launch and the "
                "wait would deadlock every rank")
        self._finalize()
        return self.status

    def retrieve_status(self):
        # the plural helpers (rq.wait_all/test_all) complete a request
        # via completed + retrieve_status, never wait(): a fully-ready
        # cycle must finalize here too or .array would stay stale
        if self._ready is not None and self._n_ready == self._n:
            self._finalize()
        return self.status

    def cancel(self) -> None:  # dispatched programs not cancelable
        pass

    def free(self) -> None:
        pass


class _TrivialPartitionedAllreduce:
    """Degenerate Pallreduce handle for the gated cases (size-1 comm,
    non-traceable op, empty pytree): full partitioned bookkeeping —
    identical Pready/start/wait semantics and errors — with the
    reduction itself deferred to wait() through the comm's
    allreduce_multi slot. Correct, no overlap."""

    def __init__(self, comm, bufs, op, deterministic) -> None:
        import jax

        from ompi_tpu.pml import request as rq

        self.id = next(rq._req_ids)
        self.status = rq.Status()
        self.persistent = True
        self._comm = comm
        self._op = op
        self._det = deterministic
        leaves, self._treedef = jax.tree.flatten(bufs)
        self._bound = list(leaves)
        self._n = len(leaves)
        self._ready = None
        self._arr = None

    @property
    def active(self) -> bool:
        return self._ready is not None

    @property
    def array(self):
        return self._arr

    @property
    def completed(self) -> bool:
        return self._ready is None or self._n_ready == self._n

    def start(self) -> None:
        if self.active:
            raise errors.MPIError(
                errors.ERR_REQUEST,
                "Pallreduce start: previous cycle still active")
        self._ready = [False] * self._n
        self._n_ready = 0

    def Pready(self, idx: int, value=None) -> None:
        if self._ready is None:
            raise errors.MPIError(
                errors.ERR_REQUEST,
                f"Pready({idx}): request inactive — call start() "
                "before marking partitions ready")
        if self._ready[idx]:
            raise errors.MPIError(
                errors.ERR_ARG,
                f"Pready({idx}): partition already marked ready "
                "this cycle (double-Pready is erroneous)")
        if value is not None:
            self._bound[idx] = value
        self._ready[idx] = True
        self._n_ready += 1
        pvar.record("part_pready")

    def Pready_range(self, lo: int, hi: int) -> None:
        for i in range(lo, hi + 1):
            self.Pready(i)

    def Pready_list(self, idxs) -> None:
        for i in idxs:
            self.Pready(i)

    def test(self) -> bool:
        return self.completed

    def _finalize(self) -> None:
        import jax

        tree = jax.tree.unflatten(self._treedef, self._bound)
        self._arr = self._comm.coll.allreduce_multi_dev(
            self._comm, tree, self._op, deterministic=self._det)
        self._ready = None

    def wait(self, timeout=None):
        if self._ready is None:
            return self.status
        if self._n_ready < self._n:
            missing = [i for i, r in enumerate(self._ready) if not r]
            raise errors.MPIError(
                errors.ERR_REQUEST,
                f"Pallreduce wait: partitions {missing} never marked "
                "ready")
        self._finalize()
        return self.status

    def retrieve_status(self):
        if self._ready is not None and self._n_ready == self._n:
            self._finalize()
        return self.status

    def cancel(self) -> None:
        pass

    def free(self) -> None:
        pass


def pallreduce_init_dev(comm, bufs, op=op_mod.SUM,
                        deterministic: Optional[str] = None):
    """Partitioned fused allreduce init (MPI-4 part/ on the device
    plane): one partition per pytree leaf; each dtype bucket's single
    compiled psum launches the moment its last member leaf is
    Pready'd, overlapping early buckets' communication with late
    gradients' production. Shares plan + executable caches with
    allreduce_multi_dev."""
    import jax

    leaves, treedef = jax.tree.flatten(bufs)
    if not _op_ok(op) or comm.size == 1 or not leaves:
        return _TrivialPartitionedAllreduce(comm, bufs, op,
                                            deterministic)
    opn = op if isinstance(op, op_mod.Op) else op_mod.BUILTIN[op]
    return PartitionedAllreduceRequest(_ctx(comm), leaves, treedef,
                                       opn, _det(deterministic),
                                       comm=comm)


class PartitionedReduceScatterRequest:
    """MPI-4 partitioned fused reduce_scatter (Preduce_scatter_init —
    the backward-overlap analog of Pallreduce_init for the ZeRO
    gradient-sharding step).

    Partitions are pytree leaves in flatten order. Init resolves the
    ZeroPlan and each bucket's ONE compiled concat+pad+reduce_scatter
    program through the SAME _Ctx caches and keys as
    Reduce_scatter_multi (shared executables -> bit-identical under
    'linear', zero recompiles after init). start() opens a cycle;
    Pready(i[, value]) marks leaf i ready, and the moment a bucket's
    LAST member is ready its reduce_scatter dispatches — early
    buckets' scatter traffic overlaps production of later gradients
    (``zero_overlap_flushes`` counts the buckets that beat the final
    Pready). wait() drains the tail; ``.array`` is the cycle's
    ShardedState."""

    def __init__(self, ctx, comm, leaves, treedef, opn,
                 det: Optional[str]) -> None:
        from ompi_tpu.pml import request as rq

        self.id = next(rq._req_ids)
        self.status = rq.Status()
        self.persistent = True
        self._ctx = ctx
        self._comm = comm
        self._treedef = treedef
        self._n = len(leaves)
        metas = _fuse_metas(leaves)
        plan = _zero_plan(ctx, metas, treedef)
        self._plan = plan
        self.nbytes = plan.nbytes
        self._metas = metas
        self._buckets = tuple(
            (_zero_rs_fn(ctx, metas, idxs,
                         plan.padded[b] - plan.elems[b], opn, det),
             idxs)
            for b, idxs in enumerate(plan.buckets))
        self._leaf_bucket = {i: b
                             for b, (_fn, idxs)
                             in enumerate(self._buckets)
                             for i in idxs}
        self._bound = [ctx.to_global(l) for l in leaves]
        self._ready = None  # None = inactive
        self._arr = None

    @property
    def active(self) -> bool:
        return self._ready is not None

    @property
    def array(self):
        """The ShardedState of the last completed cycle."""
        return self._arr

    def start(self) -> None:
        if self.active:
            raise errors.MPIError(
                errors.ERR_REQUEST,
                "Preduce_scatter start: previous cycle still active — "
                "wait() it to completion first (starting an active "
                "request is erroneous)")
        self._ready = [False] * self._n
        self._n_ready = 0
        self._pending = [len(idxs) for _fn, idxs in self._buckets]
        self._results = [None] * len(self._buckets)
        self._launched = None
        self._fl_tok = _dispatch.cycle_enter(
            "preduce_scatter_cycle", self._comm, self.nbytes)

    def Pready(self, idx: int, value=None) -> None:
        if self._ready is None:
            raise errors.MPIError(
                errors.ERR_REQUEST,
                f"Pready({idx}): request inactive — call start() "
                "before marking partitions ready")
        if self._ready[idx]:
            raise errors.MPIError(
                errors.ERR_ARG,
                f"Pready({idx}): partition already marked ready "
                "this cycle (double-Pready is erroneous)")
        if value is not None:
            shape, dtype, _nb = self._metas[idx]
            if tuple(value.shape) != shape or str(value.dtype) != dtype:
                raise errors.MPIError(
                    errors.ERR_COUNT,
                    f"Pready({idx}): value {tuple(value.shape)}/"
                    f"{value.dtype} does not match the bound template "
                    f"leaf {shape}/{dtype} (compiled programs are "
                    "shape-specialized; re-init for a new signature)")
            self._bound[idx] = self._ctx.to_global(value)
        self._ready[idx] = True
        self._n_ready += 1
        pvar.record("part_pready")
        if _trace.active():
            _trace.instant("pready", "zero", partition=idx)
        b = self._leaf_bucket[idx]
        self._pending[b] -= 1
        if self._pending[b] == 0:
            self._flush(b, idx)

    def Pready_range(self, lo: int, hi: int) -> None:
        for i in range(lo, hi + 1):
            self.Pready(i)

    def Pready_list(self, idxs) -> None:
        for i in idxs:
            self.Pready(i)

    def _flush(self, b: int, trigger: Optional[int] = None) -> None:
        pvar.record("zero_rs_launches")
        if _flush_bucket(self, b, trigger, "reduce_scatter",
                         "zero_bucket_flush", "zero"):
            pvar.record("zero_overlap_flushes")

    @property
    def completed(self) -> bool:
        if self._ready is None:
            return True
        if self._n_ready < self._n:
            return False
        import jax

        try:
            return all(bool(a.is_ready())
                       for r in self._results
                       for a in jax.tree.leaves(r))
        except AttributeError:  # backend without is_ready
            _wait(self._results, self._launched)
            return True

    def test(self) -> bool:
        return self.completed

    def _finalize(self) -> None:
        """Close the cycle: take this rank's shard of each bucket
        result, block, publish the ShardedState, go inactive."""
        import jax

        from ompi_tpu.zero import layout as _zl

        shards = [self._ctx.my_shard(self._results[b])
                  for b in range(len(self._buckets))]
        _wait(shards, self._launched)
        pvar.record("zero_fused_bytes", self.nbytes)
        pvar.record("zero_pad_bytes", self._plan.pad_bytes)
        self._arr = _zl.ShardedState(
            self._plan, self._metas, self._treedef, shards,
            self._comm.rank, self._ctx.n)
        self._ready = None
        tok, self._fl_tok = self._fl_tok, None
        _dispatch.cycle_exit(tok)

    def wait(self, timeout=None):
        if self._ready is None:
            return self.status  # inactive: immediately complete
        if self._n_ready < self._n:
            missing = [i for i, r in enumerate(self._ready) if not r]
            raise errors.MPIError(
                errors.ERR_REQUEST,
                f"Preduce_scatter wait: partitions {missing} never "
                "marked ready — the bucket collective cannot launch "
                "and the wait would deadlock every rank")
        self._finalize()
        return self.status

    def retrieve_status(self):
        if self._ready is not None and self._n_ready == self._n:
            self._finalize()
        return self.status

    def cancel(self) -> None:  # dispatched programs not cancelable
        pass

    def free(self) -> None:
        pass


class _TrivialPartitionedReduceScatter:
    """Degenerate Preduce_scatter handle for the gated cases
    (non-traceable op, empty pytree): identical Pready/start/wait
    bookkeeping and errors, the scatter itself deferred to wait()
    through the comm's reduce_scatter_multi slot. Correct, no
    overlap."""

    def __init__(self, comm, bufs, op, deterministic) -> None:
        import jax

        from ompi_tpu.pml import request as rq

        self.id = next(rq._req_ids)
        self.status = rq.Status()
        self.persistent = True
        self._comm = comm
        self._op = op
        self._det = deterministic
        leaves, self._treedef = jax.tree.flatten(bufs)
        self._bound = list(leaves)
        self._n = len(leaves)
        self._ready = None
        self._arr = None

    @property
    def active(self) -> bool:
        return self._ready is not None

    @property
    def array(self):
        return self._arr

    @property
    def completed(self) -> bool:
        return self._ready is None or self._n_ready == self._n

    def start(self) -> None:
        if self.active:
            raise errors.MPIError(
                errors.ERR_REQUEST,
                "Preduce_scatter start: previous cycle still active")
        self._ready = [False] * self._n
        self._n_ready = 0

    def Pready(self, idx: int, value=None) -> None:
        if self._ready is None:
            raise errors.MPIError(
                errors.ERR_REQUEST,
                f"Pready({idx}): request inactive — call start() "
                "before marking partitions ready")
        if self._ready[idx]:
            raise errors.MPIError(
                errors.ERR_ARG,
                f"Pready({idx}): partition already marked ready "
                "this cycle (double-Pready is erroneous)")
        if value is not None:
            self._bound[idx] = value
        self._ready[idx] = True
        self._n_ready += 1
        pvar.record("part_pready")

    def Pready_range(self, lo: int, hi: int) -> None:
        for i in range(lo, hi + 1):
            self.Pready(i)

    def Pready_list(self, idxs) -> None:
        for i in idxs:
            self.Pready(i)

    def test(self) -> bool:
        return self.completed

    def _finalize(self) -> None:
        import jax

        tree = jax.tree.unflatten(self._treedef, self._bound)
        self._arr = self._comm.coll.reduce_scatter_multi_dev(
            self._comm, tree, self._op, deterministic=self._det)
        self._ready = None

    def wait(self, timeout=None):
        if self._ready is None:
            return self.status
        if self._n_ready < self._n:
            missing = [i for i, r in enumerate(self._ready) if not r]
            raise errors.MPIError(
                errors.ERR_REQUEST,
                f"Preduce_scatter wait: partitions {missing} never "
                "marked ready")
        self._finalize()
        return self.status

    def retrieve_status(self):
        if self._ready is not None and self._n_ready == self._n:
            self._finalize()
        return self.status

    def cancel(self) -> None:
        pass

    def free(self) -> None:
        pass


def preduce_scatter_init_dev(comm, bufs, op=op_mod.SUM,
                             deterministic: Optional[str] = None):
    """Partitioned fused reduce_scatter init (MPI-4 part/ on the
    device plane, ZeRO direction): one partition per pytree leaf;
    each bucket's single compiled reduce_scatter launches the moment
    its last member leaf is Pready'd, overlapping gradient sharding
    with the backward pass. Shares the ZeroPlan + executable caches
    with reduce_scatter_multi_dev; wait() publishes the cycle's
    ShardedState in ``.array``."""
    import jax

    leaves, treedef = jax.tree.flatten(bufs)
    if not _op_ok(op) or comm.size == 1 or not leaves:
        return _TrivialPartitionedReduceScatter(comm, bufs, op,
                                                deterministic)
    opn = op if isinstance(op, op_mod.Op) else op_mod.BUILTIN[op]
    return PartitionedReduceScatterRequest(
        _ctx(comm), comm, leaves, treedef, opn, _det(deterministic))


def _irequest(fn):
    """i-variant of a device slot: same dispatch, no block — the
    blocking slots already return un-awaited futures, so the i-form
    simply wraps them in a readiness-backed request."""
    def islot(*args, **kwargs):
        return DeviceRequest(*_launched(fn, *args, **kwargs))
    islot.__name__ = "i" + fn.__name__
    islot.__doc__ = (f"Nonblocking {fn.__name__}: PJRT-async dispatch "
                     "wrapped in a DeviceRequest.")
    return islot


iallreduce_dev = _irequest(allreduce_dev)
ibcast_dev = _irequest(bcast_dev)
ireduce_dev = _irequest(reduce_dev)
iallgather_dev = _irequest(allgather_dev)
igather_dev = _irequest(gather_dev)
ialltoall_dev = _irequest(alltoall_dev)
ireduce_scatter_block_dev = _irequest(reduce_scatter_block_dev)
iscatter_dev = _irequest(scatter_dev)
iscan_dev = _irequest(scan_dev)
iexscan_dev = _irequest(exscan_dev)
iallgatherv_dev = _irequest(allgatherv_dev)
igatherv_dev = _irequest(gatherv_dev)
ialltoallv_dev = _irequest(alltoallv_dev)
iscatterv_dev = _irequest(scatterv_dev)
ireduce_scatter_dev = _irequest(reduce_scatter_dev)


@framework.register
class CollXla(CollModule):
    NAME = "xla"
    PRIORITY = 50  # above accelerator(40): device buffers stay on device

    def query(self, comm) -> int:
        if comm.size == 1:
            return self.PRIORITY  # trivial local path, no plane needed
        from ompi_tpu.runtime import device_plane

        if not device_plane.active():
            return -1
        if any(device_plane.device_for_world_rank(w) is None
               for w in comm.group.ranks):
            return -1
        return self.PRIORITY

    def slots(self, comm):
        return {
            "allreduce_dev": allreduce_dev,
            # fused gradient-bucket allreduce (+ persistent form)
            "allreduce_multi_dev": allreduce_multi_dev,
            "allreduce_multi_init_dev": allreduce_multi_init_dev,
            # MPI-4 partitioned fused allreduce (part/ device payoff)
            "pallreduce_init_dev": pallreduce_init_dev,
            # zero/ sharded data parallel: bucketed reduce_scatter/
            # allgather (+ persistent forms + partitioned RS)
            "reduce_scatter_multi_dev": reduce_scatter_multi_dev,
            "reduce_scatter_multi_init_dev":
                reduce_scatter_multi_init_dev,
            "allgather_multi_dev": allgather_multi_dev,
            "allgather_multi_init_dev": allgather_multi_init_dev,
            "allgather_multi_bucket_dev": allgather_multi_bucket_dev,
            "preduce_scatter_init_dev": preduce_scatter_init_dev,
            "reduce_dev": reduce_dev,
            "bcast_dev": bcast_dev,
            "allgather_dev": allgather_dev,
            "gather_dev": gather_dev,
            "alltoall_dev": alltoall_dev,
            "reduce_scatter_block_dev": reduce_scatter_block_dev,
            "scatter_dev": scatter_dev,
            "scan_dev": scan_dev,
            "exscan_dev": exscan_dev,
            # v-variants + barrier on device (r2 VERDICT missing #4)
            "barrier_dev": barrier_dev,
            "allgatherv_dev": allgatherv_dev,
            "gatherv_dev": gatherv_dev,
            "alltoallv_dev": alltoallv_dev,
            "scatterv_dev": scatterv_dev,
            "reduce_scatter_dev": reduce_scatter_dev,
            # nonblocking device collectives (r2 VERDICT missing #3)
            "ibarrier_dev": ibarrier_dev,
            "iallreduce_dev": iallreduce_dev,
            "ibcast_dev": ibcast_dev,
            "ireduce_dev": ireduce_dev,
            "iallgather_dev": iallgather_dev,
            "igather_dev": igather_dev,
            "ialltoall_dev": ialltoall_dev,
            "ireduce_scatter_block_dev": ireduce_scatter_block_dev,
            "iscatter_dev": iscatter_dev,
            "iscan_dev": iscan_dev,
            "iexscan_dev": iexscan_dev,
            "iallgatherv_dev": iallgatherv_dev,
            "igatherv_dev": igatherv_dev,
            "ialltoallv_dev": ialltoallv_dev,
            "iscatterv_dev": iscatterv_dev,
            "ireduce_scatter_dev": ireduce_scatter_dev,
            # MPI-4 persistent device collectives (coll.h *_init):
            # prep-at-init — Start()+Wait() is one cached-executable
            # launch, zero re-planning (pvar-verified)
            "allreduce_init_dev": allreduce_init_dev,
            "bcast_init_dev": bcast_init_dev,
            "allgather_init_dev": allgather_init_dev,
            "alltoall_init_dev": alltoall_init_dev,
            "reduce_scatter_block_init_dev":
                reduce_scatter_block_init_dev,
            # neighborhood slots (topology comms only — coll.h:600-618)
            **_neighbor_slots(comm),
        }


def _neighbor_slots(comm):
    from ompi_tpu.coll import xla_neighbor

    return xla_neighbor.slots(comm)
