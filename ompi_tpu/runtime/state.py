"""Instance state — the MPI-4 session/init engine.

Reference: ompi/instance/instance.c (ompi_mpi_instance_init_common:360 —
opal_init, rte init, framework opens, pml select, comm init) and
ompi/runtime/ompi_mpi_init.c:359. MPI_Init maps to init(); MPI-4 Sessions
map to :class:`Session` (each session can hold its own error handling and
group derivation, sharing the singleton instance underneath, as in the
reference where sessions share ompi_mpi_instance).
"""

from __future__ import annotations

import atexit
import threading
from typing import Any, Optional

from ompi_tpu.core import output, registry
from ompi_tpu.runtime import rte
from ompi_tpu.trace import recorder as _trace

_lock = threading.RLock()
_initialized = False
_finalized = False
_instance_up = False
_instance_users = 0
_world = None
_self_comm = None
_out = output.stream("runtime")


def is_initialized() -> bool:
    return _initialized


def is_finalized() -> bool:
    return _finalized


def init_phase(name: str):
    """One phase of mpi.Init(): span ``ompi:init.<name>`` and, always,
    pvar ``init_<name>_ns`` (``devplane.client`` -> ``init_client_ns``).
    The phases tile init_instance(), device_plane.init_plane() and
    init(): what one does not cover, no number covers."""
    return _trace.timed(name, "init",
                        "init_%s_ns" % name.rpartition(".")[2])


def init_instance() -> None:
    """Bring up the INSTANCE — everything below the world model.

    This is ompi_mpi_instance_init_common (instance.c:360): rte/PMIx,
    accelerator + device plane, pml selection, interposition, tool
    hooks. MPI-4 Sessions consume exactly this (no COMM_WORLD is
    built); MPI_Init layers the world model on top — the reference's
    real init engine is the session machinery and ompi_mpi_init is a
    consumer of it (instance.c:822, SURVEY §1.2).
    """
    global _instance_up
    with _lock:
        if _instance_up:
            return
        # the span recorder first (cvar trace_enable / OMPI_TPU_TRACE):
        # the ring then holds every phase of Init and every compile
        # after it; its clock is synced across ranks once the store
        # is up (_init_pml_and_planes)
        if _trace.requested():
            try:
                _trace.enable()
            except Exception as exc:  # tracing must never sink init
                _out.verbose(0, "trace enable failed: %r", exc)
        with init_phase("rte"):
            rte.init()
        _out.verbose(2, "rte up: rank %d/%d job %s",
                     rte.rank, rte.size, rte.jobid)

        # attribution profiler + persistent compile cache: the ledger
        # must be live BEFORE the accelerator/device plane so the very
        # first device_put and XLA compile are attributed, and the
        # compile-cache dir must be set before anything compiles
        from ompi_tpu.runtime import device_plane

        with init_phase("import"):
            from ompi_tpu import prof as _prof

            try:
                if _prof.requested():
                    _prof.enable(rank=rte.rank)
            except Exception as exc:  # profiling must never sink init
                _out.verbose(0, "prof enable failed: %r", exc)
            # one directory for every rank of every job started from
            # this checkout; a directory that cannot be made IS an init
            # error. A device-plane rank loads jax a few lines down
            # anyway: load it first, so the cache accounting sees its
            # first compile.
            if device_plane.requested():
                import jax  # noqa: F401
            _out.verbose(2, "persistent compile cache: %s",
                         _prof.wire_compile_cache())

        with init_phase("accelerator"):
            # accelerator selection happens during core init in the
            # reference (opal/runtime/opal_init.c:202-206)
            from ompi_tpu.accelerator import current as _accel_current
            _accel_current()

            # streaming ingest plane (cvar ingest_enable /
            # OMPI_TPU_INGEST): right after accelerator selection so
            # the upload stream pool and staging rings bind to the
            # selected component, before any comm construction kicks
            # off staging traffic
            from ompi_tpu import ingest as _ingest

            if _ingest.requested():
                try:
                    _ingest.start(rank=rte.rank)
                except Exception as exc:  # ingest must never sink init
                    _out.verbose(0, "ingest enable failed: %r", exc)

        # multi-controller device plane (opt-in; collective over the
        # world, must precede comm construction so coll/xla can qualify
        # during any comm's coll table selection). Raises on every
        # rank when the requested plane did not come up. Its three
        # phases (devplane.distributed/.client/.fence) are its own.
        if device_plane.requested():
            device_plane.init_plane()

        with init_phase("pml"):
            _init_pml_and_planes()
        _instance_up = True
        atexit.register(_atexit_finalize)


def _init_pml_and_planes() -> None:
    """The tail of init_instance(), phase ``pml``: pml selection, then
    every opt-in plane that wraps it or the API table."""
    from ompi_tpu import pml

    pml.select()
    # interposition layers stack over the selected PML before any
    # traffic flows (reference: pml/monitoring wraps at select)
    from ompi_tpu.pml import vprotocol as _pml_v

    if _pml_v._enable_var.get():
        _pml_v.install()
    # traffic-monitoring plane (cvar monitoring_level /
    # OMPI_TPU_MONITORING; --mca pml_monitoring compat-maps to
    # level 1): matrix core + pml interposition shim, before any
    # traffic flows
    from ompi_tpu import monitoring as _monitoring

    if _monitoring.requested():
        try:
            _monitoring.start(rank=rte.rank, nranks=rte.size)
        except Exception as exc:  # monitoring must never sink init
            _out.verbose(0, "monitoring enable failed: %r", exc)
    # collective performance observatory (cvar tune_observe /
    # OMPI_TPU_TUNE): load the PerfDB baseline and raise the
    # OBSERVER guard before any collective dispatches
    from ompi_tpu import tune as _tune

    if _tune.requested():
        try:
            _tune.start(rank=rte.rank, nranks=rte.size)
        except Exception as exc:  # observing must never sink init
            _out.verbose(0, "tune enable failed: %r", exc)
    # debugger hook: SIGUSR1 match-queue dump (MPIR analog)
    from ompi_tpu.tools import msgq as _msgq

    _msgq.install_signal_dump()
    # tracing plane (the recorder came up at the top of
    # init_instance): exchange wall-vs-monotonic clock offsets
    # through the store before any traffic flows, so merged per-rank
    # timelines share rank 0's timebase
    if _trace.requested():
        try:
            _trace.enable(rank=rte.rank)
            _trace.sync_clock()
        except Exception as exc:  # tracing must never sink init
            _out.verbose(0, "trace enable failed: %r", exc)
    # telemetry plane (cvar telemetry_enable / OMPI_TPU_TELEMETRY):
    # flight recorder + metrics sampler + hang watchdog — after
    # tracing so dump-on-hang can flush the span ring
    from ompi_tpu import telemetry as _telemetry

    if _telemetry.requested():
        try:
            _telemetry.start(rank=rte.rank)
        except Exception as exc:  # telemetry must never sink init
            _out.verbose(0, "telemetry enable failed: %r", exc)
    # skew plane (cvar skew_level / OMPI_TPU_SKEW): completed-
    # collective ring + store clock sync — rides the flight
    # recorder's entry/exit instrumentation, so after telemetry
    # (start() enables FLIGHT itself when telemetry is off)
    from ompi_tpu import skew as _skew

    if _skew.requested():
        try:
            _skew.start(rank=rte.rank, nranks=rte.size)
        except Exception as exc:  # observing must never sink init
            _out.verbose(0, "skew enable failed: %r", exc)
    # correctness plane (cvar check_level / OMPI_TPU_CHECK): the
    # runtime sanitizer interposes on the API dispatch table, so
    # it comes up last — after every plane that wraps methods —
    # and validates calls before the PML/coll layers see them
    from ompi_tpu import check as _check

    if _check.requested():
        try:
            _check.start(rank=rte.rank)
        except Exception as exc:  # checking must never sink init
            _out.verbose(0, "check enable failed: %r", exc)


def _acquire() -> None:
    """One more instance user (a Session, or the world model)."""
    global _instance_users
    with _lock:
        init_instance()
        _instance_users += 1


def _release() -> None:
    """Drop an instance user; the last one tears the transports down
    (the reference refcounts ompi_mpi_instance the same way —
    ompi_mpi_instance_retain/release). Resets _instance_up so a later
    Session_init re-initializes a fresh instance instead of handing
    back dead transports (MPI-4 allows sessions after a full
    teardown); the world model's once-only rule lives in _finalized,
    which only finalize() sets."""
    global _instance_users, _instance_up
    with _lock:
        _instance_users = max(0, _instance_users - 1)
        if _instance_users > 0 or not _instance_up:
            return
        from ompi_tpu.prof import ledger as _prof_ledger

        with _prof_ledger.phase("teardown"):
            try:
                if rte.size > 1:
                    # every rank must have drained its last messages
                    # before any transport tears down (unlink/close
                    # races)
                    rte.fence("finalize", timeout=30.0)
            except Exception:
                pass
            # telemetry threads go first: a watchdog sweeping (or a
            # sampler publishing) against a store that the teardown
            # below is about to close would log spurious RPC failures
            from ompi_tpu import telemetry as _telemetry

            try:
                _telemetry.stop()
            except Exception:
                pass
            # skew rings merge while the kvstore is still up — after
            # telemetry.stop (FLIGHT is down, the ring stops being
            # fed) so the Finalize exchange sees a settled ring
            from ompi_tpu import skew as _skew

            try:
                _skew.stop()
            except Exception:
                pass
            # the observatory persists its PerfDB while the kvstore
            # is still up (cross-rank merge + rank-0 fold) — after
            # telemetry (the watchdog may still want regression
            # context until its last sweep), before the pml dies
            from ompi_tpu import tune as _tune

            try:
                _tune.stop()
            except Exception:
                pass
            # traffic matrices dump at Finalize (the common/monitoring
            # contract for --mca pml_monitoring / monitoring_dump) —
            # after telemetry so the sampler's last publish already
            # rolled the monitoring pvars up, before the pml dies
            from ompi_tpu import monitoring as _monitoring

            try:
                _monitoring.stop()
            except Exception:
                pass
            # sanitizer after telemetry (its leak report already ran
            # from the Finalize hook), before the transports die
            from ompi_tpu import check as _check

            try:
                _check.stop()
            except Exception:
                pass
            # ingest teardown before the pml dies: cancels any tail
            # upload, drains the stream workers, unregisters the
            # staging rings (the no-leaked-buffers contract)
            from ompi_tpu import ingest as _ingest

            try:
                _ingest.stop()
            except Exception:
                pass
            from ompi_tpu import pml

            pml.finalize()
            registry.close_all()
        _instance_up = False


def init(thread_level: int = 0):
    """Bring up the world model; returns COMM_WORLD.

    A consumer of the session engine: instance first
    (:func:`init_instance`), then COMM_WORLD/SELF + the ULFM detector
    (ompi_mpi_init.c:359 over instance.c:822)."""
    global _initialized, _world, _self_comm
    with _lock:
        if _finalized:
            raise RuntimeError("init after finalize (MPI semantics)")
        if _initialized:
            return _world
        _acquire()
        with init_phase("world"):
            from ompi_tpu.comm import build_world

            _world, _self_comm = build_world()

            # ULFM detector (opt-in: --mca ft 1); after comm
            # construction so its progress callback can resolve cids
            # (reference: detector starts from ompi_comm_init under
            # OPAL_ENABLE_FT_MPI)
            from ompi_tpu.ft import detector as _ft_detector

            if _ft_detector.enabled() and rte.size > 1:
                _ft_detector.start()
            # init hooks last: everything (comms, transports) is up
            # (reference: hook framework callbacks at the end of
            # ompi_mpi_init)
            from ompi_tpu.core import hook as _hook

            _hook.run_init(_world)
        _initialized = True
        return _world


def world():
    if not _initialized:
        init()
    return _world


def comm_self():
    if not _initialized:
        init()
    return _self_comm


def finalize() -> None:
    """MPI_Finalize: tear down the world model, release its instance
    ref (the last user — an open Session keeps transports alive)."""
    global _finalized, _initialized, _world, _self_comm
    with _lock:
        if _finalized or not _initialized:
            _finalized = True
            return
        # the world model finalizes exactly once, regardless of open
        # sessions (a later Init must raise even while a session keeps
        # the instance alive)
        _finalized = True
        from ompi_tpu.core import hook as _hook

        _hook.run_finalize()
        from ompi_tpu.ft import detector as _ft_detector

        try:
            # FT mode: a rank can die mid-barrier and strand live peers
            # that wait on each other (the classic ULFM hang revoke
            # exists for) — the dead-tolerant store fence in _release
            # is the shutdown rendezvous instead.
            if (_world is not None and rte.size > 1
                    and _ft_detector.get() is None):
                _world.barrier()
        except Exception:
            pass
        _ft_detector.stop()
        _initialized = False
        _world = None
        _self_comm = None
        _release()


def _atexit_finalize() -> None:
    try:
        for s in list(_open_sessions):
            s.finalize()
        if _initialized and not _finalized:
            finalize()
    except Exception:
        pass


_open_sessions: set = set()


class Session:
    """MPI-4 session (reference: ompi/instance/instance.c:360,822 and
    ompi/mpi/c/session_init.c).

    A session is an independent handle on the shared instance — it
    brings up rte/pml/accelerator WITHOUT building COMM_WORLD (the
    no-world-model path): process sets are queried by name, turned
    into groups, and comms are built from groups via the store-brokered
    ``comm_create_from_group`` agreement. MPI_Init is a *consumer* of
    the same engine (init() layers the world model over
    init_instance()), exactly the reference's structure.

    Process sets: ``mpi://WORLD``, ``mpi://SELF`` (mandatory per
    MPI-4) and ``ompi_tpu://HOST`` (this node's ranks — the PMIx
    host-pset analog the reference exposes via PRRTE).
    """

    PSET_WORLD = "mpi://WORLD"
    PSET_SELF = "mpi://SELF"
    PSET_HOST = "ompi_tpu://HOST"

    def __init__(self, info: Optional[dict] = None) -> None:
        from ompi_tpu.info import apply_memkinds, as_info

        # MPI_Session_init accepts an Info; a mpi_memory_alloc_kinds
        # request is answered with the granted subset (the MPI-4.1
        # memkind negotiation happens at session init in the
        # reference, ompi/info/info_memkind.c)
        self.info = apply_memkinds(as_info(info))
        _acquire()
        self._open = True
        _open_sessions.add(self)

    def get_info(self):
        """MPI_Session_get_info (returns a new Info, per MPI)."""
        return self.info.dup()

    # -- process sets (MPI_Session_get_num_psets / get_nth_pset) --------
    def num_psets(self) -> int:
        return len(self.psets())

    def psets(self):
        return [self.PSET_WORLD, self.PSET_SELF, self.PSET_HOST]

    def get_nth_pset(self, n: int) -> str:
        return self.psets()[n]

    def pset_info(self, name: str) -> dict:
        """MPI_Session_get_pset_info: at minimum mpi_size."""
        return {"mpi_size": len(self.group_from_pset(name).ranks)}

    def group_from_pset(self, name: str):
        """MPI_Group_from_session_pset — groups are built directly
        from rte knowledge, no communicator required."""
        if not self._open:
            raise RuntimeError("session finalized")
        from ompi_tpu.comm import Group

        if name == self.PSET_WORLD:
            return Group(rte.world_ranks())
        if name == self.PSET_SELF:
            return Group([rte.rank])
        if name == self.PSET_HOST:
            return Group(_host_ranks())
        raise KeyError(f"unknown process set {name!r}")

    def comm_from_group(self, group, tag: str = "org.ompi_tpu.default"):
        """MPI_Comm_create_from_group (via the session, per MPI-4)."""
        if not self._open:
            raise RuntimeError("session finalized")
        from ompi_tpu.comm import comm_create_from_group

        return comm_create_from_group(group, tag)

    def finalize(self) -> None:
        """MPI_Session_finalize: drops this session's instance ref;
        the last ref tears the transports down."""
        if self._open:
            self._open = False
            _open_sessions.discard(self)
            _release()


def _host_ranks():
    """World ranks on this node (the host pset): one hostname
    exchange through the store, cached for the process lifetime."""
    global _host_ranks_cache
    if _host_ranks_cache is None:
        me = rte.hostname()
        rte.modex_send("pset_host", me)
        _host_ranks_cache = [w for w in rte.world_ranks()
                             if rte.modex_recv("pset_host", w) == me]
    return _host_ranks_cache


_host_ranks_cache = None


def abort(code: int = 1, reason: str = "MPI_Abort") -> None:
    rte.abort(reason, code)
