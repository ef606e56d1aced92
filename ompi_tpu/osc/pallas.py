"""osc/pallas — device-resident one-sided plane.

The TPU-native rendering of the reference's osc/rdma component
(osc_rdma_comm.c: Put/Get/Accumulate as NIC RDMA inside epochs): the
window buffer is an HBM-resident jax array pinned at ``Win_create``,
and every window mutation is a device program over it
(:mod:`ompi_tpu.osc.pallas_kernels`: an XLA update in place of the
host memcpy, a Pallas DMA kernel for the fence transport).

Division of labor per epoch family:

- **Fence** (active target, collective): Put/Accumulate/Get_epoch
  batch DESCRIPTORS; the closing :meth:`PallasWindow.Fence` runs one
  metadata allgather, edge-colors the transfers into partial-matching
  rounds (the device_epoch/xla_neighbor machinery), moves each round
  with ``make_async_remote_copy`` DMA on TPU — semaphore-paced, the
  PR-10 discipline — or a compiled ``ppermute`` on CPU, and applies
  landed payloads with the SAME update program either way. That
  sameness is the test story: tier-1 proves bit-identity against the
  host window on 2/3/4-rank meshes without hardware, exactly how
  coll/pallas is tested.
- **PSCW and passive target** (Lock/Unlock/Flush): synchronization
  rides the host :class:`~ompi_tpu.osc.Window` active-message
  machinery this class subclasses — per-peer exposure via post/
  complete messages, the lock manager, flush acks — while the TARGET-
  side data path is overridden: payloads land in the device window
  through the apply layer under the inherited per-window mutex
  (``_local_mutex`` — the Accumulate atomicity discipline), and reads
  are device slices. Per-pair FIFO delivery means a flush/unlock ack
  still implies every prior op is applied on device.

Epoch discipline is ENFORCED here (the host window is permissive):
any Put/Get/Accumulate outside a Fence/PSCW/Lock epoch raises
``MPIError(ERR_RMA_SYNC)``, as do Unlock-without-Lock and
Complete-without-Start — the erroneous-call matrix tier-1 pins.

Staged fallthrough (the coll/pallas shape): the component is opt-in
(``--mca osc_pallas on``); at creation, unsupported dtype/shape — or
any rank disagreeing — records ``osc_pallas_fallthrough`` and serves
the window via the existing host path; at op time, a valid but
non-elementwise accumulate op records the same pvar and is served
host-assisted through the AM path (read-modify-write under the
window mutex). Addressing is ELEMENT-granular: ``disp`` counts
window elements (the device_epoch convention), and operands must
match the window dtype — an Accumulate dtype mismatch raises
``MPIError(ERR_ARG)``.

On TPU a round kernel Mosaic refuses raises ``MPIError`` carrying the
compiler's message (``coll.pallas_kernels.compiled_or_raise``) — the
window never drops to interpret mode or to the host path behind the
caller's back. The round kernel keeps its payload whole in VMEM, so a
round above ``coll_pallas_dma_max_bytes`` rides a compiled XLA
``ppermute`` instead, counted in ``osc_pallas_fallthrough``. No timing
of this plane has been taken on a chip.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from ompi_tpu import errors, op as op_mod
from ompi_tpu.core import cvar, events as mpit_events, output, pvar
from ompi_tpu.monitoring import algo as _algo
from ompi_tpu.monitoring import matrix as _mon
from ompi_tpu.osc import LOCK_EXCLUSIVE, Window, _is_dev
from ompi_tpu.osc.device_epoch import GetHandle, _color
from ompi_tpu.coll import pallas as _coll_pallas
from ompi_tpu.coll.pallas_kernels import compiled_or_raise
from ompi_tpu.osc import pallas_kernels as K
from ompi_tpu.telemetry import flight as _flight
from ompi_tpu.trace import recorder as _trace
from ompi_tpu.util import jaxcompat

_out = output.stream("osc_pallas")

_enable_var = cvar.register(
    "osc_pallas", "off", str,
    help="Enable the device-resident Pallas one-sided backend: 'on' "
         "serves win_create over a supported jax array with "
         "PallasWindow (device-applied RMA, device-resident fence "
         "epochs); 'off' [default] keeps the host-staging window. "
         "Opt-in because it changes device-window semantics from "
         "documented host staging to device-authoritative.",
    choices=["off", "on"], level=4)

_interpret_var = cvar.register(
    "osc_pallas_interpret", "auto", str,
    help="Fence transport: 'auto' [default] uses the "
         "make_async_remote_copy DMA round kernel on real TPU and "
         "the interpret-mode schedule (identical apply layer + "
         "ppermute hops) everywhere else; 'on' forces interpret even "
         "on TPU (debugging); 'off' forces the DMA kernel "
         "(fails off-TPU).",
    choices=["auto", "on", "off"], level=6)

#: support matrix — everything else falls through to the host window
_SUPPORTED_DTYPES = frozenset(("float32", "bfloat16", "int32"))

FALLTHROUGH_EVENT = mpit_events.register_type(
    "osc_pallas_fallthrough",
    "an osc/pallas window or operation fell through one level down: to "
    "the host path (unsupported dtype/shape/op), or a fence round to "
    "XLA's collective permute (payload past the DMA kernel's VMEM)",
    ("what", "reason"))

_warned: set = set()


def _interpret() -> bool:
    mode = _interpret_var.get()
    if mode == "on":
        return True
    if mode == "off":
        return False
    return not jaxcompat.pallas_remote_dma_ok()


def _fallthrough_note(what: str, reason: str) -> None:
    """Count + warn-once per (what, reason) — the tune.observe
    table_error shape: a fallthrough is a silent perf cliff unless
    it is loud exactly once."""
    pvar.record("osc_pallas_fallthrough")
    key = (what, reason)
    if key not in _warned:
        _warned.add(key)
        _out.verbose(0, "WARNING: osc_pallas %s falls through: %s",
                     what, reason)
    if mpit_events.active("osc_pallas_fallthrough"):
        mpit_events.emit("osc_pallas_fallthrough", what=what,
                         reason=reason)


def _flight_slot(op: str, cid: int, nbytes: int = 0):
    """Guarded flight-recorder slot open; pair with
    :func:`_flight_exit`. The op string is what a watchdog hang dump
    prints verbatim — embed the window name and peer so a stuck epoch
    is attributable from the dump alone."""
    fl = _flight.FLIGHT
    if fl is None:
        return None
    return (fl, fl.enter(op, cid, nbytes))


def _flight_exit(tok) -> None:
    if tok is not None:
        tok[0].exit(tok[1])


class PallasWindow(Window):
    """Device-resident MPI window: the authoritative buffer is a flat
    jax array (``.array`` reshapes it back); all target-side RMA runs
    as device programs; fence epochs lower to edge-colored ICI rounds.

    Created via ``osc.win_create`` under ``--mca osc_pallas on`` (see
    :func:`maybe_window`), or directly with
    :func:`win_create_pallas`."""

    def __init__(self, comm, base, disp_unit: int = 1,
                 info=None) -> None:
        self._shape = tuple(base.shape)
        self._dtype = str(base.dtype)
        self._interp = _interpret()
        self._win = base.reshape(-1)
        self._ctx = None
        self._fence_open = False
        # fence-epoch descriptor queues: puts (target, disp, payload,
        # kind, stride), gets (handle, target, disp, nelems, stride)
        self._fput: List[Tuple] = []
        self._fget: List[Tuple] = []
        self._lock_t0: dict = {}
        super().__init__(comm, base, disp_unit, info=info)
        pvar.record("osc_pallas_windows")

    # -- device state ---------------------------------------------------
    @property
    def array(self):
        """Current window contents as a device array (authoritative —
        no host-mirror re-upload; valid at epoch boundaries)."""
        return self._win.reshape(self._shape)

    def device_array(self):
        return self.array

    @property
    def _xctx(self):
        if self._ctx is None:
            from ompi_tpu.coll import xla as X

            self._ctx = X._ctx(self.comm)
        return self._ctx

    # -- epoch discipline -----------------------------------------------
    def _epoch_for(self, target: int) -> str:
        """The epoch covering an op to ``target``: passive lock >
        PSCW access > open fence. No epoch is erroneous (MPI-3.1
        §11.5 — the host window is permissive here; this backend is
        not, because fence ops queue and would otherwise vanish)."""
        if target in self._granted:
            return "lock"
        if self._access_group is not None \
                and target in self._access_group:
            return "pscw"
        if self._fence_open:
            return "fence"
        raise errors.MPIError(
            errors.ERR_RMA_SYNC,
            f"RMA op on {self.name} outside any epoch: no Fence, "
            f"Start group, or Lock covers rank {target}")

    def _payload(self, buf, what: str) -> np.ndarray:
        """Validate + flatten an origin operand: dtype must MATCH the
        window (element-typed addressing — no byte reinterpretation
        on the device plane)."""
        arr = buf if _is_dev(buf) else np.asarray(buf)
        if str(arr.dtype) != self._dtype:
            raise errors.MPIError(
                errors.ERR_ARG,
                f"{what} operand dtype {arr.dtype} != window dtype "
                f"{self._dtype} on {self.name} (element-typed device "
                "window; cast at the origin)")
        return arr

    @staticmethod
    def _acc_kind(op) -> str:
        name = getattr(op, "name", op)  # op_mod.Op -> "MPI_SUM"
        return str(name).lower().removeprefix("mpi_")

    # -- origin API -------------------------------------------------------
    def _queue_put(self, buf, target: int, disp: int, kind: str,
                   stride: int) -> None:
        import jax.numpy as jnp

        a = jnp.asarray(self._payload(buf, "Put")).reshape(-1)
        pvar.record("osc_pallas_bytes", int(a.size)
                    * np.dtype(self._dtype).itemsize)
        self._fput.append((int(target), int(disp), a, kind,
                           int(stride)))

    def Put(self, buf, target: int, disp: int = 0) -> None:
        pvar.record("osc_pallas_put")
        if self._epoch_for(target) == "fence":
            self._queue_put(buf, target, disp, "put", 1)
            return
        pvar.record("osc_pallas_am_ops")
        super().Put(np.asarray(self._payload(buf, "Put")), target,
                    disp)

    def Put_strided(self, buf, target: int, disp: int = 0,
                    stride: int = 1) -> None:
        pvar.record("osc_pallas_put")
        if self._epoch_for(target) == "fence":
            self._queue_put(buf, target, disp, "put", stride)
            return
        pvar.record("osc_pallas_am_ops")
        super().Put_strided(np.asarray(self._payload(buf, "Put")),
                            target, disp, stride)

    def Accumulate(self, buf, target: int, disp: int = 0,
                   op: op_mod.Op = op_mod.SUM) -> None:
        pvar.record("osc_pallas_acc")
        ep = self._epoch_for(target)
        kind = self._acc_kind(op)
        data = self._payload(buf, "Accumulate")
        if kind not in K.ELEMENTWISE:
            # valid op, unsupported by the apply layer: host-assist
            # read-modify-write via the AM path (atomic under the
            # target's window mutex)
            _fallthrough_note(
                "accumulate", f"op {getattr(op, 'name', op)!r} is "
                "not elementwise; the host path serves it")
            pvar.record("osc_pallas_am_ops")
            super().Accumulate(np.asarray(data), target, disp, op)
            return
        if ep == "fence":
            self._queue_put(data, target, disp, kind, 1)
            return
        pvar.record("osc_pallas_am_ops")
        super().Accumulate(np.asarray(data), target, disp, op)

    def Get(self, buf, target: int, disp: int = 0):
        """Synchronous Get (host-window contract): the target-side
        read is a device slice of its window; the reply rides
        the AM plane. For device-resident fence-batched gets use
        :meth:`Get_epoch`."""
        pvar.record("osc_pallas_get")
        self._epoch_for(target)
        pvar.record("osc_pallas_am_ops")
        if _is_dev(buf):
            from ompi_tpu import accelerator

            scratch = np.empty(buf.shape, np.dtype(str(buf.dtype)))
            Window.Rget(self, scratch, target, disp).wait()
            return accelerator.current().to_device(scratch, like=buf)
        # Window.Rget directly: the Rget OVERRIDE enforces the MPI
        # passive-target-only rule for user calls, which must not
        # apply to this internal transport
        Window.Rget(self, buf, target, disp).wait()
        return None

    def Get_strided(self, buf, target: int, disp: int = 0,
                    stride: int = 1) -> None:
        pvar.record("osc_pallas_get")
        self._epoch_for(target)
        pvar.record("osc_pallas_am_ops")
        super().Get_strided(buf, target, disp, stride)

    def Get_epoch(self, nelems: int, target: int, disp: int = 0,
                  stride: int = 1) -> GetHandle:
        """Device-resident Get: records a descriptor; the handle's
        ``.array`` materializes at the closing Fence, fetched over
        the same colored rounds as puts (data flows target->origin).
        Fence epochs only — PSCW/lock gets use :meth:`Get`."""
        pvar.record("osc_pallas_get")
        if not self._fence_open:
            raise errors.MPIError(
                errors.ERR_RMA_SYNC,
                f"Get_epoch on {self.name} outside a fence epoch")
        if not self._check_target(target):
            return GetHandle()
        h = GetHandle()
        self._fget.append((h, int(target), int(disp), int(nelems),
                           int(stride)))
        return h

    def Get_accumulate(self, origin, result, target: int,
                       disp: int = 0,
                       op: op_mod.Op = op_mod.SUM) -> None:
        """Atomic fetch-and-accumulate: served through the AM plane
        (the target's service loop is the serialization point), with
        the device window read/updated on device under the window
        mutex."""
        pvar.record("osc_pallas_get_acc")
        self._epoch_for(target)
        if self._acc_kind(op) not in K.ELEMENTWISE \
                and getattr(op, "name", op) not in ("MPI_NO_OP",):
            _fallthrough_note(
                "get_accumulate", f"op {getattr(op, 'name', op)!r} "
                "is not elementwise; the host path serves it")
        self._payload(origin, "Get_accumulate")
        pvar.record("osc_pallas_am_ops")
        super().Get_accumulate(origin, result, target, disp, op)

    def Fetch_and_op(self, value, result, target: int, disp: int = 0,
                     op: op_mod.Op = op_mod.SUM) -> None:
        self._epoch_for(target)
        pvar.record("osc_pallas_am_ops")
        super().Fetch_and_op(value, result, target, disp, op)

    def Compare_and_swap(self, value, compare, result, target: int,
                         disp: int = 0) -> None:
        self._epoch_for(target)
        pvar.record("osc_pallas_am_ops")
        super().Compare_and_swap(value, compare, result, target, disp)

    def Rput(self, buf, target: int, disp: int = 0):
        # request-based RMA is passive-target only (MPI-3.1 §11.3.5)
        if target not in self._granted:
            raise errors.MPIError(
                errors.ERR_RMA_SYNC,
                f"Rput on {self.name}: no passive-target (Lock) "
                f"epoch covers rank {target}")
        return super().Rput(buf, target, disp)

    def Rget(self, buf, target: int, disp: int = 0):
        if target not in self._granted:
            raise errors.MPIError(
                errors.ERR_RMA_SYNC,
                f"Rget on {self.name}: no passive-target (Lock) "
                f"epoch covers rank {target}")
        return super().Rget(buf, target, disp)

    # -- synchronization --------------------------------------------------
    def Fence(self) -> None:
        """Active-target fence: flush AM ops, run this epoch's queued
        device descriptors as colored DMA/ppermute rounds, barrier.
        The first Fence opens the epoch chain (nothing queued by
        definition)."""
        pvar.record("osc_pallas_fence")
        self._epoch_event("fence", "enter")
        tok = _flight_slot(f"osc_pallas_fence win={self.name}",
                           getattr(self.comm, "cid", -1))
        rec = _trace.RECORDER
        t0 = _trace.now() if rec is not None else 0.0
        try:
            self.Flush_all()
            if self._fence_open:
                self._flush_fence()
            self.comm.coll.barrier(self.comm)
        finally:
            _flight_exit(tok)
        if rec is not None:
            rec.record("epoch", "osc_pallas", t0, _trace.now(),
                       {"op": "fence", "win": self.name})
        self._fence_open = True
        self._epoch_event("fence", "exit")

    def Lock(self, target: int,
             lock_type: str = LOCK_EXCLUSIVE) -> None:
        tok = _flight_slot(
            f"osc_pallas_lock win={self.name} peer={target}",
            getattr(self.comm, "cid", -1))
        try:
            super().Lock(target, lock_type)
        finally:
            _flight_exit(tok)
        self._lock_t0[target] = _trace.now()

    def Unlock(self, target: int) -> None:
        if target not in self._granted:
            raise errors.MPIError(
                errors.ERR_RMA_SYNC,
                f"Unlock on {self.name}: rank {target} is not locked "
                "by this origin")
        tok = _flight_slot(
            f"osc_pallas_unlock win={self.name} peer={target}",
            getattr(self.comm, "cid", -1))
        try:
            super().Unlock(target)
        finally:
            _flight_exit(tok)
        rec = _trace.RECORDER
        if rec is not None:
            rec.record("epoch", "osc_pallas",
                       self._lock_t0.pop(target, _trace.now()),
                       _trace.now(),
                       {"op": "passive", "win": self.name,
                        "peer": target})

    def Start(self, group_ranks: List[int]) -> None:
        tok = _flight_slot(
            f"osc_pallas_start win={self.name} "
            f"peer={list(group_ranks)}",
            getattr(self.comm, "cid", -1))
        try:
            super().Start(group_ranks)
        finally:
            _flight_exit(tok)

    def Complete(self) -> None:
        if self._access_group is None:
            raise errors.MPIError(
                errors.ERR_RMA_SYNC,
                f"Complete on {self.name} without a matching Start")
        tok = _flight_slot(
            f"osc_pallas_complete win={self.name} "
            f"peer={list(self._access_group)}",
            getattr(self.comm, "cid", -1))
        try:
            super().Complete()
        finally:
            _flight_exit(tok)

    def Wait(self) -> None:
        tok = _flight_slot(
            f"osc_pallas_wait win={self.name} "
            f"peer={list(self._exposure_group or [])}",
            getattr(self.comm, "cid", -1))
        try:
            super().Wait()
        finally:
            _flight_exit(tok)

    # -- target-side data path (device applies) ---------------------------
    def _apply_local(self, data, disp: int, kind: str,
                     stride: int = 1) -> None:
        """Apply one landed payload to the device window via the
        apply layer. Caller holds ``_local_mutex`` (the per-window
        Accumulate atomicity discipline)."""
        import jax.numpy as jnp

        payload = jnp.asarray(np.asarray(data).reshape(-1)).astype(
            self._win.dtype)
        self._apply(payload, disp, kind, stride)

    def _apply(self, payload, disp: int, kind: str,
               stride: int) -> None:
        """One update of the device window (caller holds
        ``_local_mutex``)."""
        self._win = K.apply(self._win, payload, int(disp), kind,
                            int(stride))
        self._dirty = True

    def _target_view(self, disp: int, count: int, dtstr: str,
                     stride: int = 1):
        """COPY of the window slice (element offsets — PJRT buffers
        are immutable, so AM replies always carry copies; mutations
        go through :meth:`_apply_local`)."""
        if count == 0:
            return np.empty(0, np.dtype(self._dtype))
        return np.asarray(K.read(self._win, int(disp), int(count),
                                 int(stride)))

    def _target_put(self, disp: int, data: np.ndarray) -> None:
        with self._local_mutex:
            self._apply_local(data, disp, "put")

    def _target_acc(self, disp: int, opname: str, data: np.ndarray,
                    locked: bool = False) -> None:
        ctx = self._local_mutex if not locked else None
        if ctx:
            ctx.acquire()
        try:
            if opname == "MPI_NO_OP":
                return
            kind = "replace" if opname == "MPI_REPLACE" \
                else self._acc_kind(opname)
            if kind in K.ELEMENTWISE:
                self._apply_local(data, disp, kind)
                return
            # host-assist: exotic op folds on host (same operand
            # order as the host window: np_fn(data, current)), the
            # result replaces the slice via a "replace" apply
            cur = self._target_view(disp, data.size, data.dtype.str)
            op = op_mod.BUILTIN[opname]
            self._apply_local(
                op.np_fn(data.reshape(-1).astype(cur.dtype), cur),
                disp, "replace")
        finally:
            if ctx:
                ctx.release()

    def _handle(self, msg: tuple, src: int) -> None:
        kind = msg[0]
        if kind == "puts":  # strided put: device apply, not view[:]=
            _, disp, stride, data = msg
            if data.size:
                with self._local_mutex:
                    self._apply_local(data, disp, "put", stride)
            self._send(src, ("ack",))
        elif kind == "cas":  # compare into an immutable device slice
            _, req_id, disp, compare, value = msg
            with self._local_mutex:
                old = self._target_view(disp, 1, value.dtype.str)
                if old[0] == compare[0]:
                    self._apply_local(value, disp, "replace")
            self._send(src, ("get_reply", req_id, np.array(old)))
        else:
            super()._handle(msg, src)

    # -- the fence flush --------------------------------------------------
    def _rounds(self, edges):
        """Group same-nelems edges, color each group into partial
        matchings — edges are (src, dst, disp, nelems, ...)."""
        by_n: dict = {}
        for e in edges:
            by_n.setdefault(e[3], []).append(e)
        for n, group in sorted(by_n.items()):
            for rnd in _color(group):
                yield n, rnd

    def _permute(self, payload, perm, nelems: int):
        """CPU transport, and the TPU one for a round too large for
        the DMA kernel: one compiled single-round ppermute (cached per
        (nelems, perm))."""
        from jax import lax

        from ompi_tpu.coll import xla as X

        ctx = self._xctx

        def build():
            return ctx.smap(
                lambda a: lax.ppermute(a[0], X.AXIS, perm=perm),
                out_varying=True)

        fn = ctx.compiled(
            ("osc_pallas", nelems, self._dtype, tuple(perm)), build)
        return ctx.my_shard(fn(ctx.to_global(payload)))

    def _dma(self, payload, tgt: int, src: int):
        """TPU transport: the CID_RMA DMA round kernel; tgt/src are
        runtime scalars, so ONE compiled program serves every
        round."""
        import jax.numpy as jnp

        from ompi_tpu.coll import xla as X

        ctx = self._xctx

        def build():
            # ONE pytree operand (payload, tgt, src), as every smap
            # body takes; [0] drops each local block's comm axis
            return ctx.smap(
                lambda a: K.dma_permute(a[0][0], a[1][0], a[2][0],
                                        X.AXIS, ctx.n),
                out_varying=True)

        fn = ctx.compiled(
            ("osc_pallas_dma", int(payload.shape[0]), self._dtype),
            build)
        operands = (ctx.to_global(payload),
                    ctx.to_global(jnp.asarray([tgt], jnp.int32)),
                    ctx.to_global(jnp.asarray([src], jnp.int32)))
        return compiled_or_raise(
            "osc_pallas dma_permute",
            lambda: ctx.my_shard(fn(operands)))

    def _transport(self, payload, perm, nelems: int):
        pvar.record("osc_pallas_rounds")
        if all(s == d for s, d in perm):
            return payload  # a round of self-edges moves nothing
        if self._interp:
            return self._permute(payload, perm, nelems)
        if not _coll_pallas.dma_fits(
                K.round_vmem_bytes(int(payload.nbytes))):
            # every rank of the round sees the same nelems, so all
            # take this branch together
            _fallthrough_note(
                "fence round",
                "the payload does not fit the DMA kernel's VMEM "
                "(coll_pallas_dma_max_bytes); XLA's collective "
                "permute moves it")
            return self._permute(payload, perm, nelems)
        tgt = src = -1
        for s, d in perm:
            if s == self.rank:
                tgt = d
            if d == self.rank:
                src = s
        return self._dma(payload, tgt, src)

    def _flush_fence(self) -> None:
        import jax.numpy as jnp

        put_desc = [(t, d, int(a.size), k, s)
                    for t, d, a, k, s in self._fput]
        get_desc = [(t, d, n, s) for _h, t, d, n, s in self._fget]
        all_desc = self.comm.coll.allgather_obj(
            self.comm, (put_desc, get_desc))
        puts = [(o, t, d, n, k, s)
                for o, (pd, _) in enumerate(all_desc)
                for t, d, n, k, s in pd]
        gets = [(o, t, d, n, s)
                for o, (_, gd) in enumerate(all_desc)
                for t, d, n, s in gd]
        self._account_fence(puts, gets)
        if puts:
            self._run_fence_puts(puts, jnp)
        if gets:
            self._run_fence_gets(gets, jnp)
        self._fput = []
        self._fget = []

    def _account_fence(self, puts, gets) -> None:
        """Per-link byte attribution for the fence wire traffic: my
        outgoing edges (puts I originate, gets I serve as target)
        walk the CartTopo routes via TrafficMatrix.count — the same
        funnel the AM path's _send uses."""
        itemsize = np.dtype(self._dtype).itemsize
        wire = [(o, t, n) for o, t, _d, n, _k, _s in puts] \
            + [(t, o, n) for o, t, _d, n, _s in gets]
        per = _algo.rma_per_peer(self.rank, wire, itemsize)
        tm = _mon.TRAFFIC
        if tm is not None:
            for peer, b in per.items():
                tm.count("osc", _mon.world_rank(self.comm, peer),
                         int(b))

    def _run_fence_puts(self, puts, jnp) -> None:
        mine = list(self._fput)
        for nelems, rnd in self._rounds(puts):
            perm = [(s, d) for s, d, *_rest in rnd]
            payload = jnp.zeros(nelems, self._win.dtype)
            my_in: Optional[Tuple[int, str, int]] = None
            for s, d, disp, _n, kind, stride in rnd:
                if s == self.rank:
                    # pop MY first queued op matching the descriptor
                    for i, (t, dd, a, k, st) in enumerate(mine):
                        if (t, dd, a.size, k, st) == (
                                d, disp, nelems, kind, stride):
                            payload = a
                            mine.pop(i)
                            break
                if d == self.rank:
                    my_in = (disp, kind, stride)
            recvd = self._transport(payload, perm, nelems)
            if my_in is not None:
                disp, kind, stride = my_in
                with self._local_mutex:
                    self._apply(recvd, disp, kind, stride)

    def _run_fence_gets(self, gets, jnp) -> None:
        # data flows target -> origin: edges (src=target, dst=origin)
        holders = list(self._fget)
        edges = [(t, o, d, n, s) for o, t, d, n, s in gets]
        for nelems, rnd in self._rounds(edges):
            perm = [(s, d) for s, d, *_rest in rnd]
            payload = jnp.zeros(nelems, self._win.dtype)
            my_in: Optional[Tuple[int, int, int]] = None
            for s, d, disp, _n, stride in rnd:
                if s == self.rank:  # I am the target: device read
                    payload = K.read(self._win, disp, nelems, stride)
                if d == self.rank:
                    my_in = (s, disp, stride)
            recvd = self._transport(payload, perm, nelems)
            if my_in is not None:
                for i, (h, t, d, n, s) in enumerate(holders):
                    if h.array is None and (t, d, n, s) == (
                            my_in[0], my_in[1], nelems, my_in[2]):
                        h.array = recvd
                        holders.pop(i)
                        break

    def Free(self) -> None:
        if self._fput or self._fget:
            raise errors.MPIError(
                errors.ERR_RMA_SYNC,
                f"Free on {self.name} with {len(self._fput)} put / "
                f"{len(self._fget)} get descriptors still queued — "
                "close the fence epoch first")
        super().Free()


def maybe_window(comm, base, disp_unit: int = 1,
                 info=None) -> Optional[PallasWindow]:
    """The staged creation-time selection ``osc.win_create`` calls
    first: returns a :class:`PallasWindow` when the backend is
    enabled AND every rank passes a supported device array (agreed by
    one metadata allgather — dtype-uniform across ranks; per-rank
    sizes are fine), else records the fallthrough and returns None
    so the host window serves the request."""
    if _enable_var.get() != "on":
        return None
    ok = bool(
        base is not None and _is_dev(base)
        and str(getattr(base, "dtype", "")) in _SUPPORTED_DTYPES
        and getattr(base, "size", 0) > 0
        and disp_unit in (1, np.dtype(str(base.dtype)).itemsize))
    dt = str(getattr(base, "dtype", ""))
    meta = comm.coll.allgather_obj(comm, (ok, dt))
    if not all(m[0] for m in meta) or len({m[1] for m in meta}) != 1:
        reasons = sorted({m[1] or "<host buffer>" for m in meta})
        _fallthrough_note(
            "win_create",
            f"unsupported or rank-asymmetric window "
            f"(dtypes {reasons}; supported "
            f"{sorted(_SUPPORTED_DTYPES)}, device arrays only); "
            "the host window serves it")
        return None
    return PallasWindow(comm, base, disp_unit, info=info)


def win_create_pallas(comm, base, disp_unit: int = 1,
                      info=None) -> PallasWindow:
    """Create a device-resident window unconditionally (collective;
    every rank passes a supported jax array) — the explicit spelling
    when the cvar-gated :func:`maybe_window` staging is not wanted."""
    if base is None or not _is_dev(base) \
            or str(base.dtype) not in _SUPPORTED_DTYPES:
        raise errors.MPIError(
            errors.ERR_ARG,
            "win_create_pallas needs a device array with dtype in "
            f"{sorted(_SUPPORTED_DTYPES)} (got "
            f"{getattr(base, 'dtype', type(base).__name__)})")
    return PallasWindow(comm, base, disp_unit, info=info)
