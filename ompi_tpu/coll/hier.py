"""coll/hier — two-level ICI x DCN hierarchical collective backend.

The device-plane realization of coll/han's architecture (reference:
ompi/mca/coll/han/coll_han.h:22-33,62-63 — hierarchical subgrouping
with per-level algorithm selection): a communicator whose devices span
slices is split into an intra-slice (ICI) x inter-slice (DCN) 2-axis
mesh, and each collective lowers as a composition of per-level phases
with the bulk bytes pinned to the fast axis. Allreduce is the
canonical case: ICI reduce_scatter -> DCN allreduce over 1/ici_size of
the payload -> ICI allgather, so the slow wire carries
``2*(n_dcn-1)/n_dcn * payload/ici_size`` bytes instead of the flat
ring's ``~2*payload``.

Topology comes from ``parallel.hierarchical.parse_split``: 'auto'
groups the comm's devices by ``slice_index`` (real pods), while
``--mca coll_hier_split 2x2`` fakes a nested topology on the virtual
CPU mesh — the whole plane is testable in tier-1. A malformed or
indivisible split spec raises ``MPIError(ERR_ARG)`` at slot-call time
(never inside ``query``, where comm_select would silently swallow it).

Selection is two-dimensional:

- hierarchical-vs-flat per collective: ``coll_hier_force`` >
  ``coll_hier_switchpoints`` table entry (op, dtype, log2-size, mesh
  shape — the same key shape as coll/pallas's table) > default-hier;
  ``deterministic='ring'`` and sub-``coll_hier_min_bytes`` payloads
  always take the flat path.
- per-level inner algorithm: the ICI phase of the split-level
  allreduce may run the coll/pallas ring kernels
  (``coll_hier_inner`` ring|bidir, or 'auto' consulting the pallas
  switchpoint table keyed on the INNER mesh shape) instead of the
  traced XLA lowering.

``deterministic='linear'`` stays hierarchical but switches to the
rank-order compositions (``H.allreduce_rankorder`` and friends):
DCN-first gathers + a statically unrolled flat-rank-order fold,
bit-identical to coll/xla's linear mode by construction — the
bit-identity contract survives the topology change.

Staged fallthrough one priority level down: any unsupported case calls
the coll/pallas slot when pallas stacked for this comm, else coll/xla,
counted by ``hier_fallthrough``. Compiled programs and fused-bucket
plans live in the SAME per-comm ``_Ctx`` caches as coll/xla (distinct
key prefixes), so steady-state steps pay zero recompiles. Every launch
attributes per-level traffic: ``hier_ici_bytes`` / ``hier_dcn_bytes``
pvars, link-map bytes split across the ICI-axis and DCN-axis neighbor
edges (``monitoring.algo.hier_per_peer``), and the per-level table the
monitoring report renders to answer "which level is the bottleneck".
"""

from __future__ import annotations

import functools
import json
from typing import Dict, Optional

import numpy as np

from ompi_tpu import errors, op as op_mod
from ompi_tpu.coll import CollModule, framework
from ompi_tpu.coll import dispatch as _dispatch
from ompi_tpu.coll import pallas as _pallas
from ompi_tpu.coll import pallas_kernels as K
from ompi_tpu.coll import xla as _xla
from ompi_tpu.core import cvar, output, pvar
from ompi_tpu.monitoring import algo as _algo
from ompi_tpu.monitoring import matrix as _mon
from ompi_tpu.parallel import hierarchical as H
from ompi_tpu.trace import recorder as _trace
from ompi_tpu.tune import observe as _tobs

_out = output.stream("coll_hier")

_enable_var = cvar.register(
    "coll_hier", "off", str,
    help="Enable the two-level ICI x DCN hierarchical collective "
         "backend (priority 70, above coll/pallas's 60): 'on' stacks "
         "it for every comm the device plane serves; 'off' [default] "
         "keeps the flat lowerings in charge. Opt-in because it "
         "re-routes every supported collective.",
    choices=["off", "on"], level=4)

_split_var = cvar.register(
    "coll_hier_split", "auto", str,
    help="How the comm's devices split into DCN groups: 'auto' "
         "[default] groups by device.slice_index (flat when ranks "
         "are not slice-contiguous or carry no slice info), 'DxI' "
         "forces a DCN x ICI grid (e.g. '2x4' — CPU topology "
         "faking), an integer N forces N equal slices, 'off' "
         "disables the split. A spec that does not divide the comm "
         "raises MPIError(ERR_ARG) at the first collective.", level=5)

_force_var = cvar.register(
    "coll_hier_force", "", str,
    help="Force the hierarchical-vs-flat decision: 'hier' always "
         "two-level (when a split exists), 'flat' always falls "
         "through (A/B validation, the coll_tuned forced-algorithm "
         "analog). Empty [default] consults the switchpoint table "
         "and built-in thresholds.",
    choices=["", "hier", "flat"], level=5)

_inner_var = cvar.register(
    "coll_hier_inner", "auto", str,
    help="ICI-phase algorithm for the split-level allreduce: 'xla' "
         "the traced lowering, 'ring'/'bidir' the coll/pallas DMA "
         "ring kernels over the inner axis, 'auto' [default] asks "
         "the coll_pallas switchpoint table (keyed on the INNER mesh "
         "shape) when coll_pallas is on, else xla. Unsupported "
         "dtype/op combinations always use xla.",
    choices=["auto", "xla", "ring", "bidir"], level=5)

_min_bytes_var = cvar.register(
    "coll_hier_min_bytes", 0, int,
    help="Payloads below this take the flat path (two phased "
         "programs lose to one latency-optimized flat program at "
         "tiny sizes). 0 [default] keeps every supported size "
         "hierarchical.", level=5)

_switch_var = cvar.register(
    "coll_hier_switchpoints", "", str,
    help="Path to a measured hierarchical-vs-flat switchpoint table: "
         "a JSON list of {op, dtype, mesh, log2, algorithm} rules "
         "with algorithm 'hier' or 'flat' and mesh the [n_dcn, "
         "n_ici] grid; for each (op, dtype, mesh) the rule with the "
         "largest log2 <= the payload's log2 bucket wins (the "
         "coll_pallas_switchpoints shape, one level up). Empty "
         "[default] = hierarchical whenever a split exists.", level=5)

# NOTE: the dcn_dtype cvars register WITHOUT choices= on purpose —
# choices validate at set() time, but this family's contract is the
# bad-split one: an unknown value must surface as MPIError(ERR_ARG)
# at the FIRST COLLECTIVE (uncached, never swallowed by query), so
# an operator typo in an mca file fails where the collectives run.
_dcn_dtype_var = cvar.register(
    "coll_hier_dcn_dtype", "off", str,
    help="Wire dtype for the hier plane's inter-slice (DCN) phase: "
         "'off' [default] transmits the accumulate dtype — bitwise "
         "identical to the uncompressed plane; 'bf16', 'fp8_e4m3', "
         "'fp8_e5m2' cast-compress the DCN payload (gather in the "
         "wire dtype + local upcast-sum; fp8 adds a per-launch scale "
         "factor agreed by pmax in the same program). Applies to SUM "
         "reductions of float payloads only; 'linear' determinism "
         "and non-float dtypes always run exact. Unknown values "
         "raise MPIError(ERR_ARG) at the first collective.", level=5)

_dcn_dtype_op_vars = {
    kind: cvar.register(
        f"coll_hier_dcn_dtype_{kind}", "", str,
        help=f"Per-op override of coll_hier_dcn_dtype for {kind} "
             "launches ('off'/'bf16'/'fp8_e4m3'/'fp8_e5m2'; empty "
             "[default] inherits the global setting) — the per-level "
             "algorithm-choice shape coll/tuned tables use.", level=5)
    for kind in ("allreduce", "allreduce_multi",
                 "reduce_scatter_block")
}

#: wire-format spellings _wire_dtype accepts (resolution/probing in
#: util.jaxcompat; byte model in monitoring.algo.WIRE_ITEMSIZE)
_WIRE_NAMES = H.WIRE_DTYPES


def _wire_dtype(kind: str, dtype: str, det: Optional[str],
                opn) -> Optional[str]:
    """The DCN wire format for this launch, or None = exact.

    Resolution: per-op override > coll_hier_dcn_dtype > off. Unknown
    values raise MPIError(ERR_ARG) HERE — slot-call time, per call,
    the bad-split contract. Compression is declined silently (exact
    lowering, no error) whenever the result must be bit-stable or the
    cast cannot help: 'linear' determinism, non-SUM ops, non-float
    payloads, or a wire format no narrower than the input dtype."""
    v = _dcn_dtype_op_vars.get(kind)
    spec = v.get().strip().lower() if v is not None else ""
    if not spec:
        spec = _dcn_dtype_var.get().strip().lower()
    if not spec or spec == "off":
        return None
    if spec not in _WIRE_NAMES:
        raise errors.MPIError(
            errors.ERR_ARG,
            f"coll_hier_dcn_dtype={spec!r}: expected 'off', 'bf16', "
            "'fp8_e4m3' or 'fp8_e5m2'")
    if det == "linear" or opn.name != "MPI_SUM":
        return None
    from ompi_tpu.util import jaxcompat as _jc

    try:
        ndt = _jc.np_dtype(dtype)
    except TypeError:
        return None
    if ndt.kind != "f":
        return None
    if _jc.wire_itemsize(spec) >= ndt.itemsize:
        return None  # the "compression" would not shrink the wire
    return spec


#: flat-path slots coll/pallas can serve (one priority level down)
_PALLAS_SLOTS = frozenset((
    "allreduce_dev", "allgather_dev", "reduce_scatter_block_dev"))

_PALLAS_COMP = _pallas.CollPallas()


# ---------------------------------------------------------------------------
# topology plan — per-comm, cached beside the _Ctx caches


class _Plan:
    """The comm's 2-level grid: a (n_dcn, n_ici) Mesh over the SAME
    devices (and device order) as the flat _Ctx mesh, so row-major
    (dcn, ici) position IS the comm rank, plus the matching dim-0
    input sharding."""

    __slots__ = ("n_dcn", "n_ici", "mesh", "sharding")

    def __init__(self, devs, n_dcn: int, n_ici: int) -> None:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        self.n_dcn = n_dcn
        self.n_ici = n_ici
        self.mesh = Mesh(np.array(devs).reshape(n_dcn, n_ici),
                         (H.DCN_AXIS, H.ICI_AXIS))
        self.sharding = NamedSharding(
            self.mesh, PartitionSpec((H.DCN_AXIS, H.ICI_AXIS)))


#: cached marker for a valid-but-trivial split (stay flat forever)
_NO_PLAN = object()


def _plan(comm) -> Optional[_Plan]:
    """The comm's grid plan, or None = flat. Cached on the comm
    (freed with it). A malformed/indivisible coll_hier_split raises
    MPIError(ERR_ARG) and is NOT cached — every collective keeps
    surfacing the config error instead of silently running flat."""
    cached = getattr(comm, "_coll_hier_plan", None)
    if cached is not None:
        return None if cached is _NO_PLAN else cached
    ctx = _xla._ctx(comm)
    devs = list(ctx.mesh.devices.reshape(-1))
    split = H.parse_split(_split_var.get(), len(devs), devices=devs)
    if split is None or split[0] < 2 or split[1] < 2:
        comm._coll_hier_plan = _NO_PLAN
        return None
    plan = comm._coll_hier_plan = _Plan(devs, split[0], split[1])
    _out.verbose(1, "comm cid=%s: %dx%d ICI x DCN grid",
                 getattr(comm, "cid", -1), plan.n_dcn, plan.n_ici)
    return plan


# ---------------------------------------------------------------------------
# selection


def _det_ok(deterministic: Optional[str]) -> Optional[str]:
    det = _xla._det(deterministic)
    if det not in (None, "ring", "linear"):
        raise errors.MPIError(
            errors.ERR_ARG,
            f"coll_hier: deterministic={det!r} (expected None, "
            "'ring' or 'linear' — silent fallthrough would void the "
            "fixed-reduction-order guarantee)")
    return det


_sw_cache: dict = {}


def _switchpoint(kind: str, nbytes: int, dtype: str,
                 mesh_shape) -> str:
    """'hier' | 'flat' | '' from the measured table (the coll/pallas
    rule shape: per (op, dtype, mesh) the largest log2 <= the
    payload's bucket wins)."""
    path = _switch_var.get().strip()
    if not path:
        return ""
    table = _sw_cache.get(path)
    if table is None:
        try:
            with open(path, encoding="utf-8") as f:
                entries = json.load(f)
        except (OSError, ValueError) as exc:
            # tune satellite: a fat-fingered table path is a silent
            # perf cliff — warn once per path, count every attempt
            _tobs.table_error("coll_hier_switchpoints", path, exc)
            entries = []
        table = {}
        for e in entries if isinstance(entries, list) else []:
            key = (str(e.get("op", "")), str(e.get("dtype", "")),
                   tuple(int(v) for v in e.get("mesh", ())))
            table.setdefault(key, []).append(
                (int(e.get("log2", 0)), str(e.get("algorithm", ""))))
        for rules in table.values():
            rules.sort()
        _sw_cache[path] = table
    rules = table.get((kind, dtype, tuple(mesh_shape)))
    if not rules:
        return ""
    bucket = _algo.log2_bucket(nbytes)
    best = ""
    for lg, alg in rules:
        if bucket >= lg:
            best = alg
        else:
            break
    return best


def _select(kind: str, comm, nbytes: int, dtype: str,
            det: Optional[str]) -> Optional[_Plan]:
    """The hierarchical-vs-flat decision: the plan, or None = fall
    through. 'ring' determinism is always flat (the two-level chunk
    order cannot reproduce the flat ring's); 'linear' stays
    hierarchical via the rank-order compositions."""
    plan = _plan(comm)  # may raise MPIError(ERR_ARG) on a bad spec
    if plan is None:
        return None
    if det == "ring":
        return None
    if nbytes == 0 or nbytes < _min_bytes_var.get():
        return None
    forced = _force_var.get()
    if forced == "flat":
        return None
    if forced == "hier":
        return plan
    if _switchpoint(kind, nbytes, dtype,
                    (plan.n_dcn, plan.n_ici)) == "flat":
        return None
    return plan


def _inner_algo(kind: str, nbytes: int, dtype: str, opn,
                plan: _Plan, chunk_rows: int) -> str:
    """ICI-phase algorithm for the split-level schedule — per-level
    selection: 'xla' = traced C.* lowering, 'ring'/'bidir' = the
    coll/pallas kernels over the inner axis. 'auto' consults the
    pallas switchpoint table keyed on the INNER mesh shape, only when
    the pallas backend is enabled."""
    mode = _inner_var.get()
    if mode == "xla":
        return "xla"
    if dtype not in _pallas._SUPPORTED_DTYPES \
            or opn.name not in _pallas._SUPPORTED_OPS:
        return "xla"
    if not _pallas._interpret() and not _pallas.dma_fits(
            K.ring_vmem_bytes(plan.n_ici, nbytes)):
        return "xla"  # past what the DMA kernels hold in VMEM
    if mode == "auto":
        if _pallas._enable_var.get() != "on":
            return "xla"
        sw = _pallas._switchpoint(kind, nbytes, dtype, (plan.n_ici,))
        if sw not in ("ring", "bidir"):
            return "xla"
        mode = sw
    if mode == "bidir" and chunk_rows < 2:
        mode = "ring"
    return mode


# ---------------------------------------------------------------------------
# dispatch plumbing


def _pallas_stacked(comm) -> bool:
    try:
        return _PALLAS_COMP.query(comm) >= 0
    except Exception:  # a query error means "not stacked", as in
        return False   # comm_select itself


def _flat_fn(comm, slot: str):
    """The slot one priority level down: coll/pallas when it stacked
    for this comm and serves the slot, else coll/xla — the same
    staged chain comm_select would have resolved without hier."""
    if slot in _PALLAS_SLOTS and _pallas_stacked(comm):
        return getattr(_pallas, slot)
    return getattr(_xla, slot)


def _fallthrough(comm, slot: str, *args, **kw):
    pvar.record("hier_fallthrough")
    return _flat_fn(comm, slot)(comm, *args, **kw)


def _smap(ctx, plan: _Plan, body, out_varying: bool):
    return ctx.smap(body, out_varying, mesh=plan.mesh,
                    spec=ctx.P((H.DCN_AXIS, H.ICI_AXIS)))


def _launch(launcher, op: str, plan: _Plan, comm, nbytes: int,
            dtype: str, **levels):
    """Account one two-level launch per level (``levels``: what
    ``_account`` takes) and run the prepared launcher through the
    dispatch seam (provider 'hier', mesh (n_dcn, n_ici)) under a
    coll_hier trace span naming the grid (the xla launch funnel
    inside adds its own span)."""
    peers = _account(op, comm, nbytes, dtype, plan, **levels)
    if not _pallas._interpret():
        compiled = launcher
        launcher = lambda: K.compiled_or_raise(  # noqa: E731
            f"coll_hier {op}", compiled)
    with _trace.span("launch", "coll_hier", op=op,
                     grid=f"{plan.n_dcn}x{plan.n_ici}"):
        return _dispatch.run(
            "hier", op, comm, None, launcher, nbytes=nbytes,
            dtype=dtype, algorithm="hier",
            mesh=(plan.n_dcn, plan.n_ici), per_peer=peers)


def _itemsize(dtype: str) -> int:
    """Element bytes of a dtype string over the ml_dtypes-extended
    namespace (0 for unparseable — wire accounting then degrades to
    the nominal model)."""
    from ompi_tpu.util import jaxcompat as _jc

    try:
        return _jc.np_dtype(dtype).itemsize
    except TypeError:
        return 0


def _account(kind: str, comm, nbytes: int, dtype: str, plan: _Plan,
             linear: bool = False, wire: Optional[str] = None,
             parts=None) -> dict:
    """Per-level attribution: the per-level byte pvars (nominal DCN
    model + actual wire bytes), the per-level totals the report
    renders, and — returned, for the dispatch seam to account — the
    link map split across the ICI-axis and DCN-axis neighbor edges.
    ``parts`` — a list of
    (nbytes, dtype, wire) — covers the fused multi path, whose
    dtype-segregated buckets can mix compressed float and exact int
    payloads in one launch; the models are linear in nbytes, so the
    per-part sums equal the whole."""
    if parts is None:
        parts = ((nbytes, dtype, wire),)
    ici_b = dcn_b = wire_b = 0.0
    peers: dict = {}
    for nb, dt, w in parts:
        isz = _itemsize(dt) if w else 0
        i_b, d_b = _algo.hier_level_bytes(
            kind, plan.n_dcn, plan.n_ici, nb, linear=linear)
        ici_b += i_b
        dcn_b += d_b
        wire_b += _algo.hier_wire_bytes(
            kind, plan.n_dcn, plan.n_ici, nb, wire=w, itemsize=isz,
            linear=linear)
        for peer, b in _algo.hier_per_peer(
                kind, comm.rank, plan.n_dcn, plan.n_ici, nb,
                linear=linear, wire=w, itemsize=isz).items():
            peers[peer] = peers.get(peer, 0.0) + b
    pvar.record("hier_ici_bytes", int(ici_b))
    pvar.record("hier_dcn_bytes", int(dcn_b))
    pvar.record("hier_dcn_wire_bytes", int(wire_b))
    tm = _mon.TRAFFIC
    if tm is not None:
        tm.hier(kind, ici_b, dcn_b, wire_b)
    return peers


# ---------------------------------------------------------------------------
# lowerings — bodies run inside shard_map over the plan's 2-axis mesh


def _split_level(flat, opn, inner: str, interp: bool,
                 wire: Optional[str] = None):
    """The han split-level allreduce on a flat vector whose length is
    a multiple of n_ici: ICI reduce_scatter -> DCN allreduce of the
    1/n_ici chunk -> ICI allgather. ``inner`` picks the ICI-phase
    kernels; the RS/AG pair always matches so chunk placement
    round-trips. ``wire`` swaps the DCN step for the cast-compressed
    transport (``H.dcn_wire_allreduce``: gather in the wire dtype +
    local upcast-sum, fp8 scale agreed in the same traced body) —
    still one compiled program, the ICI phases untouched."""
    from ompi_tpu.parallel import collectives as C

    def dcn_step(part):
        if wire is not None:
            return H.dcn_wire_allreduce(part, wire, H.DCN_AXIS)
        return C.allreduce(part, H.DCN_AXIS, opn)

    if inner in ("ring", "bidir"):
        fnc = C.combine_fn(opn)
        if inner == "bidir":
            part = K.bidir_reduce_scatter(flat, H.ICI_AXIS, fnc,
                                          interpret=interp)
        else:
            part = K.ring_reduce_scatter(flat, H.ICI_AXIS, fnc,
                                         interpret=interp)
        part = dcn_step(part)
        if inner == "bidir":
            return K.bidir_allgather(part, H.ICI_AXIS,
                                     interpret=interp)
        return K.ring_allgather(part, H.ICI_AXIS, interpret=interp)
    part = C.reduce_scatter(flat, H.ICI_AXIS, opn, scatter_dim=0,
                            tiled=True)
    part = dcn_step(part)
    return C.allgather(part, H.ICI_AXIS, tiled=True, gather_dim=0)


def _allreduce_prep(comm, sendbuf, opn, det: Optional[str],
                    plan: _Plan, wire: Optional[str] = None):
    ctx = _xla._ctx(comm)
    if det == "linear":
        def build():
            return _smap(ctx, plan,
                         lambda a: H.allreduce_rankorder(a[0], op=opn),
                         out_varying=False)

        fn = ctx.compiled(
            _xla._key(sendbuf, "hier_allreduce", "linear", opn.name,
                      plan.n_dcn, plan.n_ici), build)
    else:
        size = int(sendbuf.size)
        pad = (-size) % plan.n_ici
        interp = _pallas._interpret()
        inner = _inner_algo("allreduce", int(sendbuf.nbytes),
                            str(sendbuf.dtype), opn, plan,
                            (size + pad) // plan.n_ici)
        shape = tuple(sendbuf.shape)

        def build():
            def body(a):
                import jax.numpy as jnp

                flat = a[0].reshape(-1)
                if pad:
                    flat = jnp.pad(flat, (0, pad))
                red = _split_level(flat, opn, inner, interp, wire)
                if pad:
                    red = red[:size]
                return red.reshape(shape)

            return _smap(ctx, plan, body, out_varying=False)

        # wire in the key: exact and compressed programs must never
        # collide (toggling coll_hier_dcn_dtype back and forth reuses
        # both cached executables, zero recompiles)
        fn = ctx.compiled(
            _xla._key(sendbuf, "hier_allreduce", "split", opn.name,
                      plan.n_dcn, plan.n_ici, inner, interp, wire),
            build)
    return ctx.bind(fn, sendbuf, plan.sharding)


def allreduce_dev(comm, sendbuf, op=op_mod.SUM,
                  deterministic: Optional[str] = None):
    det = _det_ok(deterministic)
    if not _xla._op_ok(op) or comm.size == 1 \
            or not hasattr(sendbuf, "shape"):
        return _fallthrough(comm, "allreduce_dev", sendbuf, op,
                            deterministic)
    nb, dt = int(sendbuf.nbytes), str(sendbuf.dtype)
    plan = _select("allreduce", comm, nb, dt, det)
    if plan is None:
        return _fallthrough(comm, "allreduce_dev", sendbuf, op,
                            deterministic)
    opn = op if isinstance(op, op_mod.Op) else op_mod.BUILTIN[op]
    # resolve the wire format BEFORE accounting: an unknown
    # coll_hier_dcn_dtype raises here, per call, with nothing counted
    wire = _wire_dtype("allreduce", dt, det, opn)
    return _launch(_allreduce_prep(comm, sendbuf, opn, det, plan, wire),
                   "allreduce", plan, comm, nb, dt,
                   linear=det == "linear", wire=wire)


def _bcast_prep(comm, buf, root: int, plan: _Plan):
    ctx = _xla._ctx(comm)
    ici = plan.n_ici

    def build():
        return _smap(ctx, plan,
                     lambda a: H.bcast(a[0], root_dcn=root // ici,
                                       root_ici=root % ici),
                     out_varying=False)

    fn = ctx.compiled(_xla._key(buf, "hier_bcast", root, plan.n_dcn,
                                plan.n_ici), build)
    return ctx.bind(fn, buf, plan.sharding)


def bcast_dev(comm, buf, root: int = 0):
    if comm.size == 1 or not hasattr(buf, "shape"):
        return _fallthrough(comm, "bcast_dev", buf, root)
    nb, dt = int(buf.nbytes), str(buf.dtype)
    plan = _select("bcast", comm, nb, dt, None)
    if plan is None:
        return _fallthrough(comm, "bcast_dev", buf, root)
    return _launch(_bcast_prep(comm, buf, root, plan), "bcast", plan,
                   comm, nb, dt)


def _allgather_prep(comm, sendbuf, plan: _Plan):
    ctx = _xla._ctx(comm)

    def build():
        return _smap(ctx, plan, lambda a: H.gather_rankorder(a[0]),
                     out_varying=False)

    fn = ctx.compiled(_xla._key(sendbuf, "hier_allgather",
                                plan.n_dcn, plan.n_ici), build)
    return ctx.bind(fn, sendbuf, plan.sharding)


def allgather_dev(comm, sendbuf):
    if comm.size == 1 or not hasattr(sendbuf, "shape"):
        return _fallthrough(comm, "allgather_dev", sendbuf)
    nb, dt = int(sendbuf.nbytes), str(sendbuf.dtype)
    plan = _select("allgather", comm, nb, dt, None)
    if plan is None:
        return _fallthrough(comm, "allgather_dev", sendbuf)
    return _launch(_allgather_prep(comm, sendbuf, plan), "allgather",
                   plan, comm, nb, dt)


def _alltoall_prep(comm, sendbuf, plan: _Plan):
    ctx = _xla._ctx(comm)

    def build():
        # two-phase: every byte crosses DCN exactly once; output is
        # source-rank-major, the MPI alltoall order
        return _smap(ctx, plan, lambda a: H.alltoall(a[0]),
                     out_varying=True)

    fn = ctx.compiled(_xla._key(sendbuf, "hier_alltoall",
                                plan.n_dcn, plan.n_ici), build)
    return ctx.bind(fn, sendbuf, plan.sharding)


def alltoall_dev(comm, sendbuf):
    if comm.size == 1 or getattr(sendbuf, "ndim", 0) < 1 \
            or sendbuf.shape[0] % comm.size:
        # indivisible dim0 falls through: coll/xla raises the same
        # MPIError(ERR_COUNT) the flat contract specifies
        return _fallthrough(comm, "alltoall_dev", sendbuf)
    nb, dt = int(sendbuf.nbytes), str(sendbuf.dtype)
    plan = _select("alltoall", comm, nb, dt, None)
    if plan is None:
        return _fallthrough(comm, "alltoall_dev", sendbuf)
    return _launch(_alltoall_prep(comm, sendbuf, plan), "alltoall",
                   plan, comm, nb, dt)


def _reduce_scatter_block_prep(comm, sendbuf, opn,
                               det: Optional[str], plan: _Plan,
                               wire: Optional[str] = None):
    ctx = _xla._ctx(comm)
    if det == "linear":
        body = lambda a: H.reduce_scatter_block_rankorder(  # noqa: E731
            a[0], op=opn)
    else:
        body = lambda a: H.reduce_scatter_rankmajor(  # noqa: E731
            a[0], op=opn, wire=wire)

    def build():
        return _smap(ctx, plan, body, out_varying=True)

    fn = ctx.compiled(_xla._key(sendbuf, "hier_rsb", opn.name, det,
                                plan.n_dcn, plan.n_ici, wire), build)
    return ctx.bind(fn, sendbuf, plan.sharding)


def reduce_scatter_block_dev(comm, sendbuf, op=op_mod.SUM,
                             deterministic: Optional[str] = None):
    det = _det_ok(deterministic)
    if not _xla._op_ok(op) or comm.size == 1 \
            or getattr(sendbuf, "ndim", 0) < 1 \
            or sendbuf.shape[0] % comm.size:
        return _fallthrough(comm, "reduce_scatter_block_dev", sendbuf,
                            op, deterministic)
    nb, dt = int(sendbuf.nbytes), str(sendbuf.dtype)
    plan = _select("reduce_scatter_block", comm, nb, dt, det)
    if plan is None:
        return _fallthrough(comm, "reduce_scatter_block_dev", sendbuf,
                            op, deterministic)
    opn = op if isinstance(op, op_mod.Op) else op_mod.BUILTIN[op]
    wire = _wire_dtype("reduce_scatter_block", dt, det, opn)
    return _launch(
        _reduce_scatter_block_prep(comm, sendbuf, opn, det, plan, wire),
        "reduce_scatter_block", plan, comm, nb, dt,
        linear=det == "linear", wire=wire)


# ---------------------------------------------------------------------------
# fused bucketed allreduce — ZeRO / GradientSync ride the two-level
# lowering transparently. Bucket plans come from the SAME
# _xla._fuse_plan cache (geometry is mode-independent); the compiled
# bucket programs get hier-prefixed keys in the same _Ctx.fns LRU.


def _hier_bucket_fn(ctx, metas, idxs, opn, det: Optional[str],
                    plan: _Plan, interp: bool,
                    wire: Optional[str] = None):
    """ONE compiled concat + two-level-allreduce + split program per
    bucket. Under 'linear' the body is the rank-order fold —
    concatenation never changes an element's per-rank fold order, so
    fused == per-buffer bit for bit (the same argument as the flat
    fused path, tested). ``wire`` (per bucket — buckets are
    dtype-segregated, so a float bucket can compress while its int
    sibling runs exact in the same multi launch) swaps the DCN step,
    and joins the cache key so exact/compressed never collide."""
    sig = tuple((metas[i][0], metas[i][1]) for i in idxs)
    elems = sum(int(np.prod(metas[i][0], dtype=np.int64))
                for i in idxs)
    pad = (-elems) % plan.n_ici
    if det == "linear":
        inner = "xla"
    else:
        inner = _inner_algo("allreduce",
                            sum(metas[i][2] for i in idxs),
                            metas[idxs[0]][1], opn, plan,
                            (elems + pad) // plan.n_ici)

    def build():
        def body(args):
            import jax.numpy as jnp

            flat = (jnp.concatenate(
                [a[0].reshape(-1) for a in args])
                if len(args) > 1 else args[0][0].reshape(-1))
            if det == "linear":
                red = H.allreduce_rankorder(flat, op=opn)
            else:
                if pad:
                    flat = jnp.pad(flat, (0, pad))
                red = _split_level(flat, opn, inner, interp, wire)
                if pad:
                    red = red[:elems]
            outs, off = [], 0
            for a in args:  # static split back to member shapes
                k = a[0].size
                outs.append(red[off:off + k].reshape(a.shape[1:]))
                off += k
            return tuple(outs)

        return _smap(ctx, plan, body, out_varying=False)

    return ctx.compiled(("hier_fused", sig, opn.name, det,
                         plan.n_dcn, plan.n_ici, inner, interp,
                         wire), build)


def _hier_fuse_prep(comm, leaves, treedef, opn, det: Optional[str],
                    plan: _Plan):
    import jax

    ctx = _xla._ctx(comm)
    metas = _xla._fuse_metas(leaves)
    fplan = _xla._fuse_plan(ctx, metas, treedef, opn, det)
    interp = _pallas._interpret()

    launches = []
    for idxs in fplan.buckets:
        wire = _wire_dtype("allreduce_multi", metas[idxs[0]][1], det,
                           opn)
        fn = _hier_bucket_fn(ctx, metas, idxs, opn, det, plan, interp,
                             wire)
        gs = tuple(ctx.to_global(leaves[i], plan.sharding)
                   for i in idxs)
        launches.append((fn, gs, idxs))

    def launch():
        outs = [None] * len(leaves)
        for fn, gs, idxs in launches:
            res = ctx.launch(fn, gs)
            pvar.record("hier_fused_launches")
            for j, i in enumerate(idxs):
                outs[i] = ctx.my_shard(res[j])
        pvar.record("coll_xla_fused_bytes", fplan.nbytes)
        return jax.tree.unflatten(treedef, outs)

    return launch


def _multi_parts(leaves, det, opn):
    """Dtype-grouped (nbytes, dtype, wire) accounting parts for a
    fused multi launch: the byte models are linear in nbytes, so
    grouped sums account exactly, and resolving every group's wire
    here (before ``_account``) keeps the unknown-cvar MPIError
    per-call with nothing counted."""
    groups: Dict[str, int] = {}
    for b in leaves:
        dt = str(getattr(b, "dtype", ""))
        groups[dt] = groups.get(dt, 0) + int(getattr(b, "nbytes", 0))
    return tuple(
        (nb, dt, _wire_dtype("allreduce_multi", dt, det, opn))
        for dt, nb in groups.items())


def allreduce_multi_dev(comm, bufs, op=op_mod.SUM,
                        deterministic: Optional[str] = None):
    det = _det_ok(deterministic)
    import jax

    leaves = jax.tree.leaves(bufs)
    if not _xla._op_ok(op) or comm.size == 1 or not leaves:
        return _fallthrough(comm, "allreduce_multi_dev", bufs, op,
                            deterministic)
    nb = sum(int(getattr(b, "nbytes", 0)) for b in leaves)
    dt = str(getattr(leaves[0], "dtype", ""))
    plan = _select("allreduce_multi", comm, nb, dt, det)
    if plan is None:
        return _fallthrough(comm, "allreduce_multi_dev", bufs, op,
                            deterministic)
    opn = op if isinstance(op, op_mod.Op) else op_mod.BUILTIN[op]
    _, treedef = jax.tree.flatten(bufs)
    return _launch(
        _hier_fuse_prep(comm, leaves, treedef, opn, det, plan),
        "allreduce_multi", plan, comm, nb, dt,
        linear=det == "linear", parts=_multi_parts(leaves, det, opn))


# ---------------------------------------------------------------------------
# persistent inits — the prep either wraps the hier launcher with
# per-start accounting or hands the whole init to coll/xla's prep
# (flat), so Start()+Wait() cycles pay zero re-planning either way.


def _allreduce_pprep(comm, sendbuf, op=op_mod.SUM,
                     deterministic: Optional[str] = None):
    det = _det_ok(deterministic)
    plan = _select("allreduce", comm,
                   int(getattr(sendbuf, "nbytes", 0)),
                   str(getattr(sendbuf, "dtype", "")), det)
    if plan is None:
        pvar.record("hier_fallthrough")
        return _xla._allreduce_prep(comm, sendbuf, op, deterministic)
    opn = op if isinstance(op, op_mod.Op) else op_mod.BUILTIN[op]
    # wire format resolves at init time, like the plan: a persistent
    # handle keeps the schedule it was built with across Start() calls
    wire = _wire_dtype("allreduce", str(sendbuf.dtype), det, opn)
    return functools.partial(
        _launch, _allreduce_prep(comm, sendbuf, opn, det, plan, wire),
        "allreduce", plan, comm, int(sendbuf.nbytes),
        str(sendbuf.dtype), linear=det == "linear", wire=wire)


def _allreduce_multi_pprep(comm, bufs, op=op_mod.SUM,
                           deterministic: Optional[str] = None):
    det = _det_ok(deterministic)
    import jax

    leaves, treedef = jax.tree.flatten(bufs)
    nb = sum(int(getattr(b, "nbytes", 0)) for b in leaves)
    dt = str(getattr(leaves[0], "dtype", ""))
    plan = _select("allreduce_multi", comm, nb, dt, det)
    if plan is None:
        pvar.record("hier_fallthrough")
        return _xla._allreduce_multi_prep(comm, bufs, op,
                                          deterministic)
    opn = op if isinstance(op, op_mod.Op) else op_mod.BUILTIN[op]
    # per-bucket wire resolves inside _hier_fuse_prep at init time;
    # the accounting parts are captured alongside so every Start()
    # reports what the frozen schedule actually transmits
    return functools.partial(
        _launch, _hier_fuse_prep(comm, leaves, treedef, opn, det, plan),
        "allreduce_multi", plan, comm, nb, dt, linear=det == "linear",
        parts=_multi_parts(leaves, det, opn))


allreduce_init_dev = _xla._pprep(
    _allreduce_pprep, allreduce_dev, "allreduce_init_dev",
    gates=(_xla._gate_op, _xla._gate_size1))
allreduce_multi_init_dev = _xla._pprep(
    _allreduce_multi_pprep, allreduce_multi_dev,
    "allreduce_multi_init_dev",
    gates=(_xla._gate_op, _xla._gate_size1, _xla._multi_empty))


# ---------------------------------------------------------------------------


@framework.register
class CollHier(CollModule):
    NAME = "hier"
    PRIORITY = 70  # above pallas(60): the two-level schedule decides
    # first and falls through the same staged chain (pallas, then
    # xla) for everything it declines

    def query(self, comm) -> int:
        if _enable_var.get() != "on":
            return -1
        if comm.size == 1:
            return -1  # no hierarchy in a singleton
        from ompi_tpu.runtime import device_plane

        if not device_plane.active():
            return -1
        if any(device_plane.device_for_world_rank(w) is None
               for w in comm.group.ranks):
            return -1
        # NOTE: no plan/split validation here — comm_select swallows
        # query exceptions, so a malformed coll_hier_split must
        # surface at the first collective call instead
        return self.PRIORITY

    def slots(self, comm):
        return {
            "allreduce_dev": allreduce_dev,
            "bcast_dev": bcast_dev,
            "allgather_dev": allgather_dev,
            "alltoall_dev": alltoall_dev,
            "reduce_scatter_block_dev": reduce_scatter_block_dev,
            "allreduce_multi_dev": allreduce_multi_dev,
            "allreduce_init_dev": allreduce_init_dev,
            "allreduce_multi_init_dev": allreduce_multi_init_dev,
        }
