"""coll/pallas — hand-rolled ICI DMA collective backend.

A peer to :mod:`ompi_tpu.coll.xla` one priority level up: ring and
bidirectional-ring reduce_scatter / allgather / allreduce implemented
as explicit Pallas kernels (:mod:`ompi_tpu.coll.pallas_kernels` —
``make_async_remote_copy`` DMA rings on TPU, the same
schedule as interpret-mode kernels + ``ppermute`` hops on CPU), plus
the two fused compute+comm kernels the backend exists for:
reduce_scatter fused with the ZeRO stage-1/2 shard update
(``fused_rs_update_dev``) and matmul-overlapped allgather for tensor
parallelism (``allgather_matmul_dev``).

Selection (reference analog: coll/tuned's forced-algorithm params +
measured switchpoints, coll_tuned_decision_fixed.c):

- ``deterministic='linear'`` always runs the rank-order fold kernel —
  bit-identical to coll/xla's linear mode (the contract tier-1
  verifies on >= 3 mesh sizes); ``'ring'`` always the clockwise ring
  (bit-identical to coll/xla's ring mode).
- otherwise a forced ``coll_pallas_*_algorithm`` cvar wins, then a
  ``coll_pallas_switchpoints`` table entry keyed (op, log2-size,
  dtype, mesh-shape) — the same key the monitoring and tune planes
  record — then the built-in size threshold
  (bidirectional ring at/above ``coll_pallas_bidir_min_bytes``).

Staged fallthrough: any unsupported (dtype, op, shape, mesh) case —
and any forced/``'xla'`` switchpoint decision — calls the coll/xla
slot with identical arguments (one priority level down, exactly as
xla itself falls to accelerator/host), counted by the
``pallas_fallthrough`` pvar. The component is opt-in
(``--mca coll_pallas on``): stacking above xla re-routes every
supported collective, which existing provider-asserting tests must
not see by default.
"""

from __future__ import annotations

import json
from typing import Optional

from ompi_tpu import errors, op as op_mod
from ompi_tpu.coll import CollModule, framework
from ompi_tpu.coll import dispatch as _dispatch
from ompi_tpu.coll import pallas_kernels as K
from ompi_tpu.coll import xla as _xla
from ompi_tpu.core import cvar, output, pvar
from ompi_tpu.monitoring import algo as _algo
from ompi_tpu.trace import recorder as _trace
from ompi_tpu.tune import observe as _tobs
from ompi_tpu.util import jaxcompat

_out = output.stream("coll_pallas")

_enable_var = cvar.register(
    "coll_pallas", "off", str,
    help="Enable the hand-rolled Pallas ring collective backend "
         "(priority 60, above coll/xla's 50): 'on' stacks it for "
         "every comm the device plane serves; 'off' [default] leaves "
         "the XLA lowering in charge. Opt-in because it re-routes "
         "every supported collective.",
    choices=["off", "on"], level=4)

_interpret_var = cvar.register(
    "coll_pallas_interpret", "auto", str,
    help="Kernel transport: 'auto' [default] uses the monolithic "
         "make_async_remote_copy DMA kernels on real TPU and the "
         "interpret-mode schedule (pallas_call(interpret=True) "
         "compute kernels + ppermute hops, identical accumulation "
         "order) everywhere else; 'on' forces interpret even on TPU "
         "(debugging); 'off' forces the DMA kernels (fails off-TPU).",
    choices=["auto", "on", "off"], level=6)

_force_allreduce = cvar.register(
    "coll_pallas_allreduce_algorithm", "", str,
    help="Force the pallas allreduce variant: ring|bidir|linear, or "
         "'xla' to fall through to coll/xla (A/B validation, the "
         "coll_tuned_*_algorithm analog). Deterministic modes ignore "
         "a forced ring/bidir/linear — the bit-identity contract "
         "picks the kernel — but 'xla' always falls through.",
    choices=["", "ring", "bidir", "linear", "xla"], level=5)
_force_reduce_scatter = cvar.register(
    "coll_pallas_reduce_scatter_algorithm", "", str,
    help="Force the pallas reduce_scatter_block variant: "
         "ring|bidir|linear|xla (see coll_pallas_allreduce_algorithm).",
    choices=["", "ring", "bidir", "linear", "xla"], level=5)
_force_allgather = cvar.register(
    "coll_pallas_allgather_algorithm", "", str,
    help="Force the pallas allgather variant: ring|bidir|xla "
         "(allgather has no reduction, so no linear fold).",
    choices=["", "ring", "bidir", "xla"], level=5)

_min_bytes_var = cvar.register(
    "coll_pallas_min_bytes", 0, int,
    help="Payloads below this fall through to coll/xla (XLA's "
         "latency-optimized lowering wins at tiny sizes; this is the "
         "low switchpoint). 0 [default] keeps every supported size "
         "on the pallas path.", level=5)
_bidir_min_var = cvar.register(
    "coll_pallas_bidir_min_bytes", 1 << 20, int,
    help="Payloads at/above this use the bidirectional ring (both "
         "ICI link directions carry half the payload) when no "
         "deterministic mode, forced algorithm, or switchpoint-table "
         "entry overrides; below it the clockwise ring. -1 disables "
         "the bidirectional default.", level=5)
_dma_max_var = cvar.register(
    "coll_pallas_dma_max_bytes", 12 << 20, int,
    help="VMEM a monolithic DMA kernel may keep resident. The ring, "
         "fused-update, allgather-matmul and osc round kernels hold "
         "their operands, one landing slot per hop and their outputs "
         "whole in VMEM (a ring reduce_scatter on four chips: 2.25x "
         "the payload; the fused ZeRO update with momentum: 3x the "
         "bucket, so the default admits one 4 MiB bucket). A call "
         "that needs more falls through one level down (counted in "
         "pallas_fallthrough / osc_pallas_fallthrough). 12 MiB is the "
         "largest size all four kernels were run at on a v5e (PR 21). "
         "Only consulted on the TPU (non-interpret) path. "
         "0 = unbounded.",
    level=6)
_switch_var = cvar.register(
    "coll_pallas_switchpoints", "", str,
    help="Path to a measured switchpoint table (the candidate JSON "
         "`python -m ompi_tpu.tune report --tables` writes): a "
         "list of {op, dtype, mesh, log2, algorithm} rules; for each "
         "(op, dtype, mesh) the rule with the largest log2 <= the "
         "payload's log2 bucket wins ('xla' falls through). Empty "
         "[default] uses the built-in thresholds.", level=5)

#: support matrix — everything else falls through to coll/xla
_SUPPORTED_DTYPES = frozenset(("float32", "bfloat16", "int32"))
_SUPPORTED_OPS = frozenset(("MPI_SUM", "MPI_PROD", "MPI_MIN",
                            "MPI_MAX"))
#: allgather_matmul operands (x and w alike): what Mosaic's matmul takes
_MATMUL_DTYPES = frozenset(("float32", "bfloat16"))

_BYTES_PVAR = {"ring": "pallas_ring_bytes",
               "bidir": "pallas_bidir_bytes",
               "linear": "pallas_linear_bytes"}

_FORCE = {"allreduce": _force_allreduce,
          "reduce_scatter_block": _force_reduce_scatter,
          "allgather": _force_allgather}


def _interpret() -> bool:
    mode = _interpret_var.get()
    if mode == "on":
        return True
    if mode == "off":
        return False
    return not jaxcompat.pallas_remote_dma_ok()


def dma_fits(vmem_bytes: int) -> bool:
    """Whether a DMA kernel that keeps ``vmem_bytes`` resident (the
    kernel library's ``*_vmem_bytes``) is admitted on the TPU path."""
    bound = _dma_max_var.get()
    return bound <= 0 or vmem_bytes <= bound


def _det_ok(deterministic: Optional[str]) -> Optional[str]:
    """Normalize the deterministic mode (slot arg over cvar default)
    and reject unknown values on this public coll path."""
    det = _xla._det(deterministic)
    if det not in (None, "ring", "linear"):
        raise errors.MPIError(
            errors.ERR_ARG,
            f"coll_pallas: deterministic={det!r} (expected None, "
            "'ring' or 'linear' — silent fallthrough would void the "
            "fixed-reduction-order guarantee)")
    return det


def _opn(op) -> Optional[op_mod.Op]:
    opn = op if isinstance(op, op_mod.Op) else op_mod.BUILTIN.get(op)
    if opn is None or opn.name not in _SUPPORTED_OPS:
        return None
    return opn


def _fallthrough(xla_fn, *args, **kw):
    pvar.record("pallas_fallthrough")
    return xla_fn(*args, **kw)


_sw_cache: dict = {}


def _switchpoint(kind: str, nbytes: int, dtype: str,
                 mesh_shape) -> str:
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
            _tobs.table_error("coll_pallas_switchpoints", path, exc)
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


def _select(kind: str, comm, sendbuf, det: Optional[str],
            chunk_rows: int) -> Optional[str]:
    """The decision layer: algorithm name, or None = fall through to
    coll/xla. Deterministic modes pin the matching kernel (the
    bit-identity contract); otherwise forced cvar > switchpoint
    table > built-in bidir threshold > ring."""
    ctx = _xla._ctx(comm)
    if ctx.mesh2d is not None:
        return None  # ICI x DCN comms: xla's split-level schedule
    dt = str(getattr(sendbuf, "dtype", ""))
    if dt not in _SUPPORTED_DTYPES:
        return None
    nbytes = int(getattr(sendbuf, "nbytes", 0))
    if nbytes == 0 or nbytes < _min_bytes_var.get():
        return None
    # one rule for every kind: the ring reduce_scatter's residency
    # (allgather and the linear fold hold less, and were proven on
    # the chip no further than the ring)
    if not _interpret() \
            and not dma_fits(K.ring_vmem_bytes(comm.size, nbytes)):
        return None
    forced = _FORCE[kind].get()
    if forced == "xla":
        return None
    if det == "linear":
        return "linear" if kind != "allgather" else "ring"
    if det == "ring":
        return "ring"
    if forced:
        return forced if not (forced == "bidir" and chunk_rows < 2) \
            else "ring"
    sw = _switchpoint(kind, nbytes, dt,
                      tuple(int(d) for d in ctx.mesh.devices.shape))
    if sw == "xla":
        return None
    if sw:
        return sw if not (sw == "bidir" and chunk_rows < 2) else "ring"
    bmin = _bidir_min_var.get()
    if 0 <= bmin <= nbytes and chunk_rows >= 2:
        return "bidir"
    return "ring"


def _launch(launcher, op: str, algo: str, comm, buf, kind=None,
            nbytes=None):
    """Run a prepared kernel launcher through the dispatch seam
    (provider 'pallas', the chosen algorithm, traffic by the
    hand-rolled schedule's own per-peer model under ``kind``, the
    collective the op moves its bytes as) under a coll_pallas trace
    span naming the algorithm (the xla launch funnel inside adds its
    own span). ``nbytes`` overrides ``buf.nbytes`` for multi-buffer
    ops."""
    kind = kind or op
    nb = int(getattr(buf, "nbytes", 0) if nbytes is None else nbytes)
    pvar.record(_BYTES_PVAR[algo], nb)
    if not _interpret():
        compiled = launcher
        launcher = lambda: K.compiled_or_raise(  # noqa: E731
            f"coll_pallas {op}/{algo}", compiled)
    with _trace.span("launch", "coll_pallas", op=op, algorithm=algo):
        return _dispatch.run(
            "pallas", op, comm, buf, launcher, nbytes=nb,
            algorithm=algo, kind=kind,
            per_peer=lambda: _algo.pallas_per_peer(
                kind, algo, comm.rank, comm.size, nb))


# ---------------------------------------------------------------------------
# slots — signatures match coll/xla's (the fallthrough target)


def _allreduce_prep(comm, sendbuf, opn, algo: str):
    from ompi_tpu.parallel import collectives as C

    ctx = _xla._ctx(comm)
    fnc = C.combine_fn(opn)
    interp = _interpret()

    def build():
        if algo == "linear":
            body = lambda a: K.linear_allreduce(  # noqa: E731
                a[0], _xla.AXIS, fnc, interpret=interp)
        else:
            body = lambda a: K.ring_allreduce(  # noqa: E731
                a[0], _xla.AXIS, fnc, interpret=interp,
                bidir=algo == "bidir")
        return ctx.smap(body, out_varying=False)

    fn = ctx.compiled(
        _xla._key(sendbuf, "pallas_allreduce", algo, opn.name, interp),
        build)
    return ctx.bind(fn, sendbuf)


def allreduce_dev(comm, sendbuf, op=op_mod.SUM,
                  deterministic: Optional[str] = None):
    det = _det_ok(deterministic)
    opn = _opn(op)
    if opn is None or comm.size == 1:
        return _fallthrough(_xla.allreduce_dev, comm, sendbuf, op,
                            deterministic)
    size = int(getattr(sendbuf, "size", 0))
    chunk_rows = -(-size // comm.size) if size else 0
    algo = _select("allreduce", comm, sendbuf, det, chunk_rows)
    if algo is None:
        return _fallthrough(_xla.allreduce_dev, comm, sendbuf, op,
                            deterministic)
    return _launch(_allreduce_prep(comm, sendbuf, opn, algo),
                   "allreduce", algo, comm, sendbuf)


def _allgather_prep(comm, sendbuf, algo: str):
    ctx = _xla._ctx(comm)
    interp = _interpret()
    shape = tuple(sendbuf.shape)
    n = ctx.n

    def build():
        def body(a):
            flat = a[0].reshape(-1)
            if algo == "bidir":
                full = K.bidir_allgather(flat, _xla.AXIS,
                                         interpret=interp)
            else:
                full = K.ring_allgather(flat, _xla.AXIS,
                                        interpret=interp)
            return full.reshape((n,) + shape)

        return ctx.smap(body, out_varying=False)

    fn = ctx.compiled(_xla._key(sendbuf, "pallas_allgather", algo,
                                interp), build)
    return ctx.bind(fn, sendbuf)


def allgather_dev(comm, sendbuf):
    if comm.size == 1 or not hasattr(sendbuf, "shape"):
        return _fallthrough(_xla.allgather_dev, comm, sendbuf)
    algo = _select("allgather", comm, sendbuf, None,
                   int(getattr(sendbuf, "size", 0)))
    if algo is None:
        return _fallthrough(_xla.allgather_dev, comm, sendbuf)
    return _launch(_allgather_prep(comm, sendbuf, algo), "allgather",
                   algo, comm, sendbuf)


def _reduce_scatter_prep(comm, sendbuf, opn, algo: str):
    from ompi_tpu.parallel import collectives as C

    ctx = _xla._ctx(comm)
    fnc = C.combine_fn(opn)
    interp = _interpret()

    def build():
        def body(a):
            x = a[0]
            if algo == "linear":
                return K.linear_reduce_scatter(x, _xla.AXIS, fnc,
                                               interpret=interp)
            if algo == "bidir":
                return K.bidir_reduce_scatter(x, _xla.AXIS, fnc,
                                              interpret=interp)
            return K.ring_reduce_scatter(x, _xla.AXIS, fnc,
                                         interpret=interp)

        return ctx.smap(body, out_varying=True)

    fn = ctx.compiled(_xla._key(sendbuf, "pallas_rsb", algo, opn.name,
                                interp), build)
    return ctx.bind(fn, sendbuf)


def reduce_scatter_block_dev(comm, sendbuf, op=op_mod.SUM,
                             deterministic: Optional[str] = None):
    det = _det_ok(deterministic)
    opn = _opn(op)
    if opn is None or comm.size == 1:
        return _fallthrough(_xla.reduce_scatter_block_dev, comm,
                            sendbuf, op, deterministic)
    if getattr(sendbuf, "ndim", 0) < 1 \
            or sendbuf.shape[0] % comm.size:
        # same contract as coll/xla: an indivisible dim 0 is a caller
        # error, not a fallthrough case
        return _fallthrough(_xla.reduce_scatter_block_dev, comm,
                            sendbuf, op, deterministic)
    algo = _select("reduce_scatter_block", comm, sendbuf, det,
                   sendbuf.shape[0] // comm.size)
    if algo is None:
        return _fallthrough(_xla.reduce_scatter_block_dev, comm,
                            sendbuf, op, deterministic)
    return _launch(_reduce_scatter_prep(comm, sendbuf, opn, algo),
                   "reduce_scatter_block", algo, comm, sendbuf)


# ---------------------------------------------------------------------------
# fused slots (pallas-only: no xla equivalent one level down)


def fused_rs_update_dev(comm, grads, pshards, mshards, *,
                        lr: float, mu: float = 0.0, avg: bool = True,
                        deterministic: Optional[str] = None):
    """ZeRO fused reduce_scatter + shard update over the gradient
    pytree: per ZeroPlan bucket, ONE kernel reduce_scatters the flat
    bucket and consumes the reduced chunk in-register with the
    average/momentum/SGD epilogue. Returns ``(new_pshards,
    new_mshards)`` ShardedStates, or **None** when any bucket is
    unsupported or, on TPU, larger than the kernel can hold in VMEM
    (``coll_pallas_dma_max_bytes``) — the caller (ZeroOptimizer) then
    runs the unfused sequence, the same staged-fallthrough shape as
    the other slots.

    Numerics: under ``deterministic='linear'`` (the reproducibility
    mode) only the reduce_scatter runs in-kernel; the epilogue replays
    the exact unfused eager op sequence, so fused == unfused bit for
    bit by construction. The default/'ring' mode fuses the epilogue
    into the kernel — same dtype and op order, but the compiler may
    contract multiply-add inside the single program, so it is
    equivalent to within one rounding of the unfused result."""
    det = _det_ok(deterministic)
    if comm.size == 1:
        pvar.record("pallas_fallthrough")
        return None
    import jax

    from ompi_tpu.parallel import collectives as C
    from ompi_tpu.zero import layout as _zl

    leaves, treedef = jax.tree.flatten(grads)
    if not leaves:
        pvar.record("pallas_fallthrough")
        return None
    ctx = _xla._ctx(comm)
    if ctx.mesh2d is not None:
        pvar.record("pallas_fallthrough")
        return None
    plan = pshards.plan
    metas = _xla._fuse_metas(leaves)
    if metas != tuple(pshards.metas) \
            or any(str(dt) not in _SUPPORTED_DTYPES
                   for dt in plan.dtypes):
        pvar.record("pallas_fallthrough")
        return None
    with_mom = mshards is not None
    inv = 1.0 / comm.size if avg else None
    fnc = C.combine_fn(op_mod.SUM)
    interp = _interpret()
    lrf, muf = float(lr), float(mu)
    # the kernel holds a whole bucket, its landing slots and (fused
    # epilogue) the p/v shards with their outputs in VMEM
    shard_bufs = 1 if det == "linear" else 4 if with_mom else 2
    if not interp and not all(
            dma_fits(K.ring_vmem_bytes(
                comm.size,
                plan.padded[b] * jaxcompat.np_dtype(dt).itemsize,
                shard_bufs))
            for b, dt in enumerate(plan.dtypes)):
        pvar.record("pallas_fallthrough")
        return None

    launches = []
    for b, idxs in enumerate(plan.buckets):
        pad = plan.padded[b] - plan.elems[b]
        sig = tuple((metas[i][0], metas[i][1]) for i in idxs)

        if det == "linear":
            # Reproducibility mode: the kernel ONLY reduce_scatters
            # (rank-order fold, bitwise equal to the unfused bucket
            # RS); the update epilogue runs eagerly in run() with the
            # exact unfused op sequence. Fusing the epilogue into the
            # same program would let the compiler contract p - lr*g
            # into an FMA and break the bit-identity contract.
            def build(idxs=idxs, pad=pad):
                def body(args):
                    import jax.numpy as jnp

                    gs, = args
                    flat = (jnp.concatenate(
                        [g[0].reshape(-1) for g in gs])
                        if len(gs) > 1 else gs[0][0].reshape(-1))
                    if pad:
                        flat = jnp.pad(flat, (0, pad))
                    return K.linear_reduce_scatter(
                        flat, _xla.AXIS, fnc, interpret=interp)

                return ctx.smap(body, out_varying=True)

            fn = ctx.compiled(
                ("pallas_fused_rs_lin", sig, pad, interp), build)
            gs = tuple(ctx.to_global(leaves[i]) for i in idxs)
            launches.append((fn, (gs,), b))
            continue

        def build(idxs=idxs, pad=pad):
            def body(args):
                import jax.numpy as jnp

                gs, p, v = args
                flat = (jnp.concatenate(
                    [g[0].reshape(-1) for g in gs])
                    if len(gs) > 1 else gs[0][0].reshape(-1))
                if pad:
                    flat = jnp.pad(flat, (0, pad))
                vv = v[0] if v is not None else None
                return K.ring_reduce_scatter_update(
                    flat, _xla.AXIS, fnc, p[0], vv, lr=lrf, mu=muf,
                    inv=inv, interpret=interp)

            return ctx.smap(body, out_varying=True)

        fn = ctx.compiled(
            ("pallas_fused_rs", sig, pad, interp, lrf, muf, inv,
             with_mom), build)
        gs = tuple(ctx.to_global(leaves[i]) for i in idxs)
        pg = ctx.to_global(pshards.shards[b])
        vg = ctx.to_global(mshards.shards[b]) if with_mom else None
        launches.append((fn, (gs, pg, vg), b))

    import numpy as np

    def run():
        new_p, new_m = [], []
        for fn, args, b in launches:
            out = ctx.launch(fn, args)
            pvar.record("pallas_fused_launches")
            if det == "linear":
                # eager epilogue, op-for-op the unfused step: each op
                # dispatches as its own program, so rounding points
                # match the unfused cycle exactly
                g = ctx.my_shard(out)
                if avg:
                    g = g * np.asarray(inv, g.dtype)
                if with_mom:
                    v0 = mshards.shards[b]
                    g = np.asarray(muf, v0.dtype) * v0 + g
                    new_m.append(g)
                p0 = pshards.shards[b]
                new_p.append(p0 - np.asarray(lrf, p0.dtype) * g)
                continue
            pn = ctx.my_shard(out[0])
            new_p.append(pn)
            if with_mom:
                new_m.append(ctx.my_shard(out[1]))
        ps = _zl.ShardedState(plan, pshards.metas, pshards.treedef,
                              new_p, comm.rank, comm.size)
        ms = _zl.ShardedState(plan, pshards.metas, pshards.treedef,
                              new_m, comm.rank, comm.size) \
            if with_mom else None
        return ps, ms

    return _launch(run, "fused_rs_update", det or "ring", comm,
                   leaves[0], kind="reduce_scatter_multi",
                   nbytes=plan.nbytes)


def _allgather_matmul_prep(comm, x, w):
    ctx = _xla._ctx(comm)
    interp = _interpret()

    def build():
        def body(args):
            return K.allgather_matmul(args[0][0], args[1][0],
                                      _xla.AXIS, interpret=interp)

        return ctx.smap(body, out_varying=False)

    fn = ctx.compiled(_xla._key(x, "pallas_agmm", tuple(w.shape),
                                str(w.dtype), interp), build)
    g = (ctx.to_global(x), ctx.to_global(w))
    return lambda: ctx.my_shard(ctx.launch(fn, g))


def allgather_matmul_dev(comm, x, w):
    """Tensor-parallel fused allgather@matmul: x is this rank's
    (m, d) row block, w the replicated (d, f) weight; returns the
    full (n*m, f) product with each arriving block multiplied while
    the next ring hop is in flight. Unsupported cases — and, on TPU,
    operands the kernel cannot hold whole in VMEM
    (``coll_pallas_dma_max_bytes``) — compose the plain device
    allgather with a local matmul (same result, no overlap)."""
    import jax.numpy as jnp

    ok = (comm.size > 1
          and getattr(x, "ndim", 0) == 2
          and getattr(w, "ndim", 0) == 2
          and x.shape[1] == w.shape[0]
          and str(x.dtype) == str(w.dtype) in _MATMUL_DTYPES
          and _xla._ctx(comm).mesh2d is None
          and (_interpret() or dma_fits(K.matmul_vmem_bytes(
              comm.size, x, w, x.dtype))))
    if not ok:
        pvar.record("pallas_fallthrough")
        gathered = _xla.allgather_dev(comm, x)
        full = jnp.asarray(gathered).reshape(
            (comm.size * x.shape[0],) + tuple(x.shape[1:]))
        return jnp.dot(full, w)
    pvar.record("pallas_fused_launches")
    return _launch(_allgather_matmul_prep(comm, x, w),
                   "allgather_matmul", "ring", comm, x,
                   kind="allgather")


def zero3_gather_matmul_dev(comm, state, rhs):
    """ZeRO stage-3 fused gather→use fast path: consume a sharded
    2-D weight W (a single-bucket single-leaf ShardedState) directly
    against ``rhs`` as ``allgather_matmul(shard_rows, rhs)`` — the
    gather of W overlaps the matmul, and the full W is NEVER
    materialized as a standalone array. Works because a contiguous
    1/n slice of a row-major (d, f) flatten with d % n == 0 and no
    pad IS rows [r*d/n, (r+1)*d/n): the flat shard reshapes to this
    rank's row block and the tensor-parallel kernel's rank-order
    concat equals the ZeroPlan pack order. Returns the (d, k) product
    or **None** for every other layout — the zero-3 engine then
    gathers through the persistent coll/xla allgather and matmuls
    locally (staged fallthrough)."""
    plan = getattr(state, "plan", None)
    shards = getattr(state, "shards", None)
    ok = (comm.size > 1
          and plan is not None and shards is not None
          and len(plan.buckets) == 1
          and len(plan.buckets[0]) == 1
          and plan.padded[0] == plan.elems[0]
          and getattr(rhs, "ndim", 0) == 2
          and str(getattr(rhs, "dtype", "")) == str(plan.dtypes[0])
          in _MATMUL_DTYPES)
    if ok:
        shape = state.metas[plan.buckets[0][0]][0]
        ok = (len(shape) == 2
              and int(shape[0]) % comm.size == 0
              and int(shape[1]) == int(rhs.shape[0]))
    if not ok:
        pvar.record("pallas_fallthrough")
        return None
    block = shards[0].reshape(int(shape[0]) // comm.size,
                              int(shape[1]))
    return allgather_matmul_dev(comm, block, rhs)


# ---------------------------------------------------------------------------


@framework.register
class CollPallas(CollModule):
    NAME = "pallas"
    PRIORITY = 60  # above xla(50): hand-rolled kernels override the
    # XLA lowering for the ops they implement; everything else keeps
    # resolving to xla's slots

    def query(self, comm) -> int:
        if _enable_var.get() != "on":
            return -1
        if comm.size == 1:
            return -1  # xla's trivial local path is already optimal
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
            "allgather_dev": allgather_dev,
            "reduce_scatter_block_dev": reduce_scatter_block_dev,
            # fused compute+comm kernels (pallas-only slots)
            "fused_rs_update_dev": fused_rs_update_dev,
            "allgather_matmul_dev": allgather_matmul_dev,
            "zero3_gather_matmul_dev": zero3_gather_matmul_dev,
        }
