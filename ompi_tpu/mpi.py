"""The public MPI-style API.

Reference: ompi/mpi/c/ (444 per-function bindings doing profiling hook,
SPC counter, param check, then framework dispatch — e.g. allreduce.c:37-127).
Pythonic surface follows the mpi4py convention: lowercase methods move
pickled Python objects, capitalized methods move numpy buffers in place.

Buffer specs for capitalized methods: ``array`` | ``(array, count)`` |
``(array, count, Datatype)``.
"""

from __future__ import annotations

import time as _time

# phase "import" of mpi.Init() (pvar init_import_ns) begins with the
# import of this module — numpy is most of it — and goes on in
# runtime/state.init_instance, where jax is loaded
_T_IMPORT = _time.monotonic_ns()

from typing import Any, List, Optional, Sequence, Tuple  # noqa: E402

import numpy as np  # noqa: E402

from ompi_tpu import errors, op as op_mod, pml
from ompi_tpu.comm import Communicator, Group, UNDEFINED
from ompi_tpu.core import pvar
from ompi_tpu.datatype import Datatype
from ompi_tpu.datatype.convertor import dtype_of
from ompi_tpu.pml import request as rq
from ompi_tpu.pml.request import (  # noqa: F401  (re-exports)
    ANY_SOURCE, ANY_TAG, PROC_NULL, Request, Status, wait_all, wait_any,
    wait_some, test_all, test_any,
)
from ompi_tpu.trace import recorder as _trace

IN_PLACE = "MPI_IN_PLACE"

# re-export ops & common datatypes at the API level
SUM, PROD, MIN, MAX = op_mod.SUM, op_mod.PROD, op_mod.MIN, op_mod.MAX
LAND, LOR, BAND, BOR = op_mod.LAND, op_mod.LOR, op_mod.BAND, op_mod.BOR
MINLOC, MAXLOC = op_mod.MINLOC, op_mod.MAXLOC


def _parse_buf(buf) -> Tuple[Any, int, Optional[Datatype]]:
    """(array|bytearray, count, dtype) from a buffer spec."""
    if isinstance(buf, tuple):
        if _is_dev(buf[0]):
            raise TypeError(
                "(device array, count[, datatype]) tuples are "
                "supported on Send/Recv/Isend/Irecv/Sendrecv and "
                "Bcast/Allreduce/Ibcast/Iallreduce (on-device "
                "pack/unpack); this operation has no device "
                "derived-datatype route — stage with np.asarray for "
                "host-side layouts")
        if len(buf) == 2:
            arr, count = buf
            return arr, count, dtype_of(arr)
        arr, count, dt = buf
        return arr, count, dt
    arr = buf
    if isinstance(arr, np.ndarray):
        return arr, arr.size, dtype_of(arr)
    if type(arr).__module__.split(".")[0] in ("jax", "jaxlib"):
        raise TypeError(
            "device array passed to an operation without a device "
            "path. Device-interposed entries: Send/Recv/Isend/Irecv "
            "(pipelined bounce-buffer staging), the blocking and "
            "nonblocking collectives incl. v-variants (sendbuf "
            "device, recvbuf None -> returns a new device array), "
            "Barrier(device=True), RMA windows. For other operations "
            "stage manually with np.asarray(arr) / jax.device_put.")
    mv = memoryview(arr)
    return arr, mv.nbytes, None


class _PersistentRequest(rq.Request):
    """MPI_Send_init / MPI_Recv_init handles (reference: persistent
    requests restarted by MPI_Start). ``completed``/``status`` proxy
    the live inner request so the plural waits (wait_all/test_any),
    which poll ``r.completed`` while spinning progress, observe
    completion without a per-request test()."""

    def __init__(self, comm, kind: str, args: tuple) -> None:
        super().__init__()
        self.persistent = True
        self.comm = comm
        self.kind = kind
        self.args = args
        self._live: Optional[rq.Request] = None
        self._idle_done = True  # inactive counts as complete (MPI)

    @property
    def completed(self) -> bool:
        if self._live is not None:
            return self._live.completed
        return self._idle_done

    @completed.setter
    def completed(self, v: bool) -> None:  # base __init__ writes here
        self._idle_done = bool(v)

    @property
    def status(self) -> rq.Status:
        if self._live is not None:
            return self._live.status
        return self._idle_status

    @status.setter
    def status(self, st) -> None:  # base __init__ writes here
        self._idle_status = st

    def start(self) -> None:
        p = pml.current()
        if self.kind == "send":
            buf, count, dt, dest, tag = self.args
            self._live = p.isend(self.comm, buf, count, dt, dest, tag)
        else:
            buf, count, dt, src, tag = self.args
            self._live = p.irecv(self.comm, buf, count, dt, src, tag)

    @property
    def active(self) -> bool:
        """A started operation not yet known complete (start_all
        refuses to restart these — MPI calls it erroneous)."""
        return self._live is not None and not self._live.completed

    def test(self) -> bool:
        if not self.completed:
            from ompi_tpu.core import progress

            progress.progress()
        return self.completed

    def wait(self, timeout=None):
        if self._live is None:
            return self.status
        return self._live.wait(timeout=timeout)


def start_all(reqs: Sequence[rq.Request]) -> None:
    """MPI_Startall over any mix of persistent and partitioned
    requests (Send_init/Recv_init, the *_init collectives,
    Psend_init/Precv_init, Pallreduce_init). The whole set is
    validated BEFORE any request starts (all-or-nothing): a
    non-startable entry raises TypeError, and a request whose
    previous cycle is still active raises MPIError(ERR_REQUEST) —
    MPI 4.0 §4.2 calls starting an active request erroneous, and the
    old silent re-start orphaned the in-flight cycle."""
    for r in reqs:
        if not getattr(r, "persistent", False) \
                or not callable(getattr(r, "start", None)):
            raise TypeError(
                f"start_all: request {getattr(r, 'id', r)!r} is not "
                "a startable (persistent/partitioned) request")
    for r in reqs:
        if getattr(r, "active", False):
            raise errors.MPIError(
                errors.ERR_REQUEST,
                f"start_all: request {getattr(r, 'id', '?')} is "
                "still active — wait/test it to completion before "
                "restarting (no request was started)")
    for r in reqs:
        r.start()


#: MPI-4 spelling (MPI_Startall) — same behavior as start_all
Startall = start_all


# ---------------------------------------------------------------------------
# Communicator API methods. Defined here and attached to Communicator to
# keep identity (comm/) separate from surface (this module), mirroring the
# reference's ompi/communicator vs ompi/mpi/c split.
# ---------------------------------------------------------------------------

def _check_rank(comm, rank: int, allow_null: bool = True) -> None:
    if rank == PROC_NULL and allow_null:
        return
    if rank == ANY_SOURCE:
        return
    # intercomm p2p addresses the remote group
    n = comm.remote_group.size if getattr(comm, "is_inter", False) \
        else comm.size
    if not 0 <= rank < n:
        raise errors.RankError(f"rank {rank} out of range for {comm}")


# -- object (pickled) p2p --

def _send(self, obj, dest: int, tag: int = 0) -> None:
    self.check_revoked()
    _check_rank(self, dest)
    pvar.record("send")
    pml.current().send_obj(self, obj, dest, tag)


def _isend(self, obj, dest: int, tag: int = 0) -> rq.Request:
    self.check_revoked()
    _check_rank(self, dest)
    return pml.current().isend_obj(self, obj, dest, tag)


def _recv(self, source: int = ANY_SOURCE, tag: int = ANY_TAG,
          status: Optional[Status] = None):
    self.check_revoked()
    obj_req = pml.current().irecv_obj(self, source, tag)
    st = obj_req.wait()
    if status is not None:
        status.source, status.tag = st.source, st.tag
        status.count, status.error = st.count, st.error
    return obj_req._obj


def _irecv(self, source: int = ANY_SOURCE, tag: int = ANY_TAG):
    self.check_revoked()
    return pml.current().irecv_obj(self, source, tag)


def _sendrecv(self, obj, dest: int, source: int = ANY_SOURCE,
              sendtag: int = 0, recvtag: int = ANY_TAG):
    rreq = pml.current().irecv_obj(self, source, recvtag)
    sreq = pml.current().isend_obj(self, obj, dest, sendtag)
    rreq.wait()
    sreq.wait()
    return rreq._obj


# -- buffer p2p --

def _parse_dev(buf):
    """(arr, count, dt) when ``buf`` routes to the device plane: a
    bare device array, or a (device array, count[, datatype]) tuple —
    the derived-datatype form, packed/unpacked ON DEVICE by the
    convertor's gather/scatter route (datatype.device; reference:
    the accelerator-aware convertor, opal_datatype_copy.h consumed at
    pml_ob1_sendreq.h:399). Returns None for host buffers.

    Built by hand rather than via _parse_buf: dtype inference there
    calls np.asarray, which would silently stage the device array to
    the host."""
    if _is_dev(buf):
        return buf, None, None
    if (isinstance(buf, tuple) and len(buf) in (2, 3)
            and _is_dev(buf[0])):
        return buf[0], buf[1], (buf[2] if len(buf) == 3 else None)
    return None


def _dev_pack(arr, count, dt):
    """Send-side device convertor: pack (one XLA gather) when a
    count/datatype rode the tuple form; identity for bare arrays."""
    if dt is None and count is None:
        return arr
    from ompi_tpu.datatype import device as dtdev

    return dtdev.pack(arr, dt, count)


def _dev_recv_plan(arr, count, dt):
    """(like, transform) for the device receive side: bare templates
    receive as-shaped; tuple forms receive the packed wire form into
    a flat template, then scatter into ``arr`` (one XLA scatter)."""
    if dt is None and count is None:
        return arr, None
    import jax.numpy as jnp

    from ompi_tpu.datatype import device as dtdev

    n = dtdev.packed_elems(dt, count, np.dtype(arr.dtype).itemsize)
    return (jnp.zeros(n, arr.dtype),
            lambda p: dtdev.unpack(p, dt, count, arr))


def _Send(self, buf, dest: int, tag: int = 0) -> None:
    self.check_revoked()
    _check_rank(self, dest)
    d = _parse_dev(buf)
    if d is not None:
        # pipelined bounce-buffer staging (ob1 accelerator analog):
        # D2H of chunk k+1 overlaps the wire send of chunk k; derived
        # datatypes pack on device first (one XLA gather)
        from ompi_tpu.pml import accel_p2p

        arr, count, dt = d
        pvar.record("send")
        return accel_p2p.send_dev(self, _dev_pack(arr, count, dt),
                                  dest, tag)
    arr, count, dt = _parse_buf(buf)
    pvar.record("send")
    pml.current().send(self, arr, count, dt, dest, tag)


def _Isend(self, buf, dest: int, tag: int = 0) -> rq.Request:
    self.check_revoked()
    d = _parse_dev(buf)
    if d is not None:
        # progress-driven pipelined staging (no blocking, no threads)
        from ompi_tpu.pml import accel_p2p

        arr, count, dt = d
        req = accel_p2p.isend_dev(self, _dev_pack(arr, count, dt),
                                  dest, tag)
        req.comm = self  # errhandler dispatch at wait (request.py)
        return req
    arr, count, dt = _parse_buf(buf)
    req = pml.current().isend(self, arr, count, dt, dest, tag)
    req.comm = self
    return req


def _Ssend(self, buf, dest: int, tag: int = 0) -> None:
    self.check_revoked()
    arr, count, dt = _parse_buf(buf)
    pml.current().send(self, arr, count, dt, dest, tag, sync=True)


def _Issend(self, buf, dest: int, tag: int = 0) -> rq.Request:
    arr, count, dt = _parse_buf(buf)
    return pml.current().isend(self, arr, count, dt, dest, tag, sync=True)


def _Rsend(self, buf, dest: int, tag: int = 0) -> None:
    # ready-send: receiver is guaranteed posted; eager path is identical
    _Send(self, buf, dest, tag)


#: MPI_BSEND_OVERHEAD: per-message bookkeeping charge against an
#: attached buffer (the reference's envelope/header share)
BSEND_OVERHEAD = 64

#: None = no buffer attached: the framework buffers IMPLICITLY and
#: without bound (documented Pythonic extension — the copies are heap
#: allocations, not slices of a user arena). Attaching a buffer opts
#: into the strict MPI capacity contract.
_bsend_capacity: Optional[int] = None


def Buffer_attach(buf_or_size) -> None:
    """MPI_Buffer_attach (ompi/mpi/c/buffer_attach.c): cap buffered-
    send memory. Accepts a byte count or a buffer object (only its
    SIZE matters here — copies are heap-allocated, not packed into
    the arena). With a buffer attached, Bsend raises ERR_BUFFER when
    outstanding copies would exceed the capacity."""
    global _bsend_capacity
    if _bsend_capacity is not None:
        raise errors.MPIError(errors.ERR_BUFFER,
                              "a bsend buffer is already attached")
    import numbers

    # numbers.Integral catches numpy ints too — a np.int64 exposes
    # the buffer protocol and would otherwise attach as 8 bytes
    size = (int(buf_or_size)
            if isinstance(buf_or_size, numbers.Integral)
            else memoryview(buf_or_size).nbytes)
    if size < 0:
        raise errors.MPIError(errors.ERR_BUFFER,
                              f"negative buffer size {size}")
    _bsend_capacity = size


def Buffer_detach() -> int:
    """MPI_Buffer_detach: BLOCKS until every outstanding buffered
    send delivers (the MPI contract), then returns the detached
    size."""
    global _bsend_capacity
    if _bsend_capacity is None:
        raise errors.MPIError(errors.ERR_BUFFER,
                              "no bsend buffer attached")
    _flush_bsends()
    size, _bsend_capacity = _bsend_capacity, None
    return size


def _bsend_used() -> int:
    """Reclaim delivered copies, then report the live charge. One
    progress sweep first: rndv completions only flip inside a sweep,
    and MPI reclaims delivered-message space before failing a
    Bsend."""
    from ompi_tpu.core import progress

    progress.progress()
    live = [(r, nb) for r, nb in _pending_bsends if not r.completed]
    _pending_bsends[:] = live
    return sum(nb for _, nb in live)


def _Bsend(self, buf, dest: int, tag: int = 0) -> None:
    """Buffered send: copy now, deliver in background."""
    arr, count, dt = _parse_buf(buf)
    if isinstance(arr, np.ndarray):
        copy = np.array(arr, copy=True)
    else:  # raw buffer: keep byte semantics (dtype_of(bytes) would
        # infer an S-dtype and inflate the size)
        copy = np.frombuffer(bytes(arr), dtype=np.uint8).copy()
    charge = copy.nbytes + BSEND_OVERHEAD
    if _bsend_capacity is not None and \
            _bsend_used() + charge > _bsend_capacity:
        raise errors.MPIError(
            errors.ERR_BUFFER,
            f"bsend of {copy.nbytes} bytes exceeds the attached "
            f"buffer ({_bsend_capacity} bytes, "
            f"{_bsend_used()} in flight)")
    req = pml.current().isend(self, copy, count, dt, dest, tag)
    _pending_bsends.append((req, charge))


def _Recv(self, buf, source: int = ANY_SOURCE, tag: int = ANY_TAG,
          status: Optional[Status] = None):
    """Device path: ``buf`` (a jax array) is the shape/dtype template
    and the received data comes back as a NEW device array (PJRT
    buffers are immutable); the host path fills ``buf`` in place and
    returns the Status."""
    self.check_revoked()
    d = _parse_dev(buf)
    if d is not None:
        from ompi_tpu.pml import accel_p2p

        arr, count, dt = d
        like, tr = _dev_recv_plan(arr, count, dt)
        out, st = accel_p2p.recv_dev(self, like, source, tag)
        if tr is not None:
            out = tr(out)
        if status is not None:
            status.source, status.tag = st.source, st.tag
            status.count, status.error = st.count, st.error
        return out
    arr, count, dt = _parse_buf(buf)
    st = pml.current().recv(self, arr, count, dt, source, tag)
    if status is not None:
        status.source, status.tag = st.source, st.tag
        status.count, status.error = st.count, st.error
    return st


def _Irecv(self, buf, source: int = ANY_SOURCE,
           tag: int = ANY_TAG) -> rq.Request:
    """Device path: ``buf`` is the shape/dtype template; the request's
    ``.array`` holds the received device array after completion."""
    self.check_revoked()
    d = _parse_dev(buf)
    if d is not None:
        from ompi_tpu.pml import accel_p2p

        arr, count, dt = d
        like, tr = _dev_recv_plan(arr, count, dt)
        req = accel_p2p.irecv_dev(self, like, source, tag,
                                  transform=tr)
        req.comm = self  # errhandler dispatch at wait (request.py)
        return req
    arr, count, dt = _parse_buf(buf)
    req = pml.current().irecv(self, arr, count, dt, source, tag)
    req.comm = self
    return req


def _Sendrecv(self, sendbuf, dest: int, recvbuf, source: int = ANY_SOURCE,
              sendtag: int = 0, recvtag: int = ANY_TAG) -> Status:
    rreq = _Irecv(self, recvbuf, source, recvtag)
    sreq = _Isend(self, sendbuf, dest, sendtag)
    st = rreq.wait()
    sreq.wait()
    return st


def _Sendrecv_replace(self, buf, dest: int, source: int = ANY_SOURCE,
                      sendtag: int = 0, recvtag: int = ANY_TAG) -> Status:
    return _Isendrecv_replace(self, buf, dest, source, sendtag,
                              recvtag).wait()


class _PairRequest(rq.Request):
    """One request over a (recv, send) pair — MPI-4's Isendrecv
    handle: completes when BOTH complete; status is the receive's
    (isendrecv.c exposes exactly that)."""

    def __init__(self, rreq: rq.Request, sreq: rq.Request) -> None:
        super().__init__()
        self._rreq = rreq
        self._sreq = sreq

    @property
    def completed(self) -> bool:  # live view; no progress callback
        return self._rreq.completed and self._sreq.completed

    @completed.setter
    def completed(self, v: bool) -> None:
        pass  # base __init__ writes here; the property is derived

    @property
    def status(self) -> Status:
        return self._rreq.status

    @status.setter
    def status(self, st) -> None:
        pass

    def wait(self, timeout=None) -> Status:
        import time as _time

        t0 = _time.perf_counter()
        st = self._rreq.wait(timeout=timeout)
        rem = (None if timeout is None else
               max(0.0, timeout - (_time.perf_counter() - t0)))
        self._sreq.wait(timeout=rem)  # one budget for BOTH halves
        return st


def _Isendrecv(self, sendbuf, dest: int, recvbuf,
               source: int = ANY_SOURCE, sendtag: int = 0,
               recvtag: int = ANY_TAG) -> rq.Request:
    """MPI_Isendrecv (MPI-4, ompi/mpi/c/isendrecv.c): both halves
    post now; the returned request completes when both do."""
    rreq = _Irecv(self, recvbuf, source, recvtag)
    sreq = _Isend(self, sendbuf, dest, sendtag)
    return _PairRequest(rreq, sreq)


def _Isendrecv_replace(self, buf, dest: int, source: int = ANY_SOURCE,
                       sendtag: int = 0,
                       recvtag: int = ANY_TAG) -> rq.Request:
    """MPI_Isendrecv_replace (MPI-4): the send snapshot is taken NOW
    (the receive overwrites ``buf`` as it lands). Routed through the
    _Irecv/_Isend wrappers so revoked-comm checks and errhandler
    stamping apply like every other p2p entry."""
    arr, count, dt = _parse_buf(buf)
    tmp = np.array(arr, copy=True)
    rreq = _Irecv(self, (arr, count, dt), source, recvtag)
    sreq = _Isend(self, (tmp, count, dt), dest, sendtag)
    return _PairRequest(rreq, sreq)


# -- probe family --

def _Probe(self, source: int = ANY_SOURCE, tag: int = ANY_TAG) -> Status:
    return pml.current().probe(self, source, tag)


def _Iprobe(self, source: int = ANY_SOURCE,
            tag: int = ANY_TAG) -> Optional[Status]:
    return pml.current().iprobe(self, source, tag)


def _Mprobe(self, source: int = ANY_SOURCE, tag: int = ANY_TAG):
    return pml.current().mprobe(self, source, tag)


def _Improbe(self, source: int = ANY_SOURCE, tag: int = ANY_TAG):
    return pml.current().improbe(self, source, tag)


def _Mrecv(self, msg, buf) -> Status:
    arr, count, dt = _parse_buf(buf)
    return pml.current().mrecv(msg, arr, count, dt)


# -- persistent --

def _Send_init(self, buf, dest: int, tag: int = 0) -> _PersistentRequest:
    arr, count, dt = _parse_buf(buf)
    return _PersistentRequest(self, "send", (arr, count, dt, dest, tag))


def _Recv_init(self, buf, source: int = ANY_SOURCE,
               tag: int = ANY_TAG) -> _PersistentRequest:
    arr, count, dt = _parse_buf(buf)
    return _PersistentRequest(self, "recv", (arr, count, dt, source, tag))


# -- collectives (capitalized: buffers; lowercase: objects) --

def _is_dev(buf) -> bool:
    """True when buf is a device-resident array (the shared predicate
    accelerator.is_device_buffer — reference: check_addr on every
    collective entry, coll_accelerator_allreduce.c check_buf)."""
    if buf is IN_PLACE:
        return False
    from ompi_tpu import accelerator

    return accelerator.is_device_buffer(buf)


def _Pack(self, inbuf, outbuf, position: int = 0) -> int:
    """MPI_Pack: append inbuf's packed bytes into outbuf at position;
    returns the new position (reference: ompi/mpi/c/pack.c over the
    convertor — same engine here)."""
    from ompi_tpu.datatype.convertor import Convertor
    from ompi_tpu.datatype.datatype import BYTE

    arr, count, dt = _parse_buf(inbuf)
    data = Convertor(arr, dt or BYTE, count).pack()
    out = memoryview(outbuf).cast("B")
    if position + len(data) > len(out):
        raise errors.TruncateError(
            f"Pack: need {position + len(data)} bytes, outbuf has "
            f"{len(out)}")
    out[position:position + len(data)] = data
    return position + len(data)


def _Unpack(self, inbuf, position: int, outbuf) -> int:
    """MPI_Unpack: consume packed bytes from inbuf at position into
    outbuf; returns the new position."""
    from ompi_tpu.datatype.convertor import Convertor
    from ompi_tpu.datatype.datatype import BYTE

    arr, count, dt = _parse_buf(outbuf)
    conv = Convertor(arr, dt or BYTE, count)
    src = memoryview(inbuf).cast("B")
    need = conv.packed_size
    if position + need > len(src):
        raise errors.TruncateError(
            f"Unpack: need {need} bytes at position {position}, inbuf "
            f"has {len(src)}")
    conv.unpack(bytes(src[position:position + need]))
    return position + need


def _Pack_size(self, count: int, dtype) -> int:
    """MPI_Pack_size: an upper bound on Pack output bytes."""
    dt = dtype if isinstance(dtype, Datatype) else dtype_of(
        np.empty(0, dtype))
    return count * dt.size


def packed_displs(counts) -> list:
    """The MPI default displacement layout — counts packed end to
    end (one implementation for every v-variant's displs=None)."""
    counts = list(counts)
    if not counts:
        return []
    return np.concatenate(
        [[0], np.cumsum(counts[:-1], dtype=np.intp)]).tolist()


def _norm_cd(counts, displs):
    """Normalized (counts, displs) for a v-variant: plain ints,
    displs defaulting to the packed layout."""
    counts = [int(c) for c in counts]
    return counts, (packed_displs(counts) if displs is None
                    else [int(d) for d in displs])


def _require_packed_displs(counts, displs, what: str) -> None:
    """Device v-variants slice the send buffer as PACKED segments; a
    caller-supplied send-side displacement layout would silently move
    the wrong data, so it is rejected (recv-side displs are a host
    layout concept — device results come back packed by design)."""
    if displs is None:
        return
    packed = packed_displs(counts)
    if [int(d) for d in displs] != packed:
        raise ValueError(
            f"{what}: the device path requires packed send "
            f"displacements {packed}, got {list(displs)}; stage to "
            "host (np.asarray) for custom send layouts")


def _require_recvbuf(recvbuf, what: str):
    """Host-path collectives need a caller recvbuf; only device
    arrays legitimately omit it (they return a new array). Raising
    here beats the obscure TypeError _parse_buf(None) produces."""
    if recvbuf is None:
        raise TypeError(
            f"{what}: recvbuf required for host buffers (recvbuf="
            "None is the device-array form, which returns a new "
            "array)")
    return recvbuf


def _Barrier(self, device: bool = False) -> None:
    """device=True rendezvouses on the device plane (a compiled
    1-element psum over ICI) instead of the host transports."""
    self.check_revoked()
    self.check_failed()
    if device:
        return self.coll.barrier_dev(self)
    self.coll.barrier(self)


def _Bcast(self, buf, root: int = 0):
    self.check_revoked()
    self.check_failed()
    d = _parse_dev(buf)
    if d is not None:
        arr, count, dt = d
        if dt is None and count is None:
            return self.coll.bcast_dev(self, arr, root)
        # derived datatype: device pack -> collective -> scatter back
        # into the caller's template (gaps keep the template's
        # values). Non-roots only need a SHAPE operand — a zeros
        # template, not a wasted gather of data the bcast overwrites.
        from ompi_tpu.datatype import device as dtdev

        if self.rank == root:
            packed = dtdev.pack(arr, dt, count)
        else:
            packed = _dev_recv_plan(arr, count, dt)[0]
        out = self.coll.bcast_dev(self, packed, root)
        return dtdev.unpack(out, dt, count, arr)
    arr, count, dt = _parse_buf(buf)
    self.coll.bcast(self, arr, count, dt, root)


def _Reduce(self, sendbuf, recvbuf=None, op=op_mod.SUM, root: int = 0,
            deterministic=None):
    self.check_revoked()
    self.check_failed()
    if _is_dev(sendbuf):
        return self.coll.reduce_dev(self, sendbuf, op, root,
                                    deterministic=deterministic)
    sarr, count, dt = _parse_buf(sendbuf) if sendbuf is not IN_PLACE \
        else (IN_PLACE, None, None)
    rarr = None if recvbuf is None else _parse_buf(recvbuf)[0]
    if sarr is IN_PLACE:
        count, dt = _parse_buf(recvbuf)[1:]
    self.coll.reduce(self, sarr, rarr, count, dt, op, root)


def _Allreduce(self, sendbuf, recvbuf=None, op=op_mod.SUM,
               deterministic=None):
    """deterministic (device buffers only): None lets XLA schedule the
    reduction; 'ring'/'linear' fix the operand order (coll/xla) —
    'linear' is bit-identical to the host linear fold."""
    self.check_revoked()
    self.check_failed()
    d = _parse_dev(sendbuf)
    if d is not None:
        arr, count, dt = d
        out = self.coll.allreduce_dev(self, _dev_pack(arr, count, dt),
                                      op, deterministic=deterministic)
        if dt is None and count is None:
            return out
        from ompi_tpu.datatype import device as dtdev

        return dtdev.unpack(out, dt, count, arr)
    if sendbuf is IN_PLACE:
        rarr, count, dt = _parse_buf(recvbuf)
        self.coll.allreduce(self, IN_PLACE, rarr, count, dt, op)
    else:
        sarr, count, dt = _parse_buf(sendbuf)
        rarr = _parse_buf(recvbuf)[0]
        self.coll.allreduce(self, sarr, rarr, count, dt, op)


def _Allreduce_multi(self, bufs, op=op_mod.SUM, deterministic=None):
    """Fused (bucketed) allreduce over a list/pytree of buffers —
    the gradient-bucketing hot path. Device leaves coalesce into
    dtype-segregated flat buckets (target size: cvar
    coll_xla_bucket_bytes) and each bucket runs ONE compiled psum
    (coll/xla); 'linear' determinism stays bit-identical to the
    per-buffer loop. Host buffers (list/tuple form) loop per buffer.
    Always returns NEW buffers with the input structure (PJRT arrays
    are immutable; the host loop keeps the same contract)."""
    self.check_revoked()
    self.check_failed()
    if isinstance(bufs, (list, tuple)) and bufs \
            and not _is_dev(bufs[0]):
        outs = []
        for a in bufs:
            arr = np.ascontiguousarray(a)
            out = np.empty_like(arr)
            self.coll.allreduce(self, arr, out, out.size,
                                dtype_of(arr), op)
            outs.append(out)
        return type(bufs)(outs)
    return self.coll.allreduce_multi_dev(self, bufs, op,
                                         deterministic=deterministic)


def _Allreduce_multi_init(self, bufs, op=op_mod.SUM) -> rq.Request:
    """MPI-4-style persistent fused allreduce: plan + compile + bind
    at init, every Start()+Wait() is one cached-executable launch per
    bucket; req.array holds each cycle's result pytree. Device
    buffers only (host lists: use per-buffer Allreduce_init)."""
    self.check_revoked()
    self.check_failed()
    if isinstance(bufs, (list, tuple)) and bufs \
            and not _is_dev(bufs[0]):
        raise TypeError(
            "Allreduce_multi_init: device buffers only (host "
            "persistent form: use per-buffer Allreduce_init)")
    return self.coll.allreduce_multi_init_dev(self, bufs, op)


def _Pallreduce_init(self, bufs, op=op_mod.SUM,
                     deterministic=None) -> rq.Request:
    """MPI-4 partitioned fused allreduce (the part/ subsystem's
    device-path payoff): one partition per pytree leaf. Start() opens
    a cycle; Pready(i[, value]) hands over leaf i — optionally with
    this cycle's fresh gradient — and a dtype bucket's single
    compiled psum launches the moment its LAST member leaf is ready,
    so early buckets' communication overlaps production of later
    gradients (the DDP backward-hook pattern through a standard MPI
    surface); Wait() drains the tail into req.array. Shares bucket
    plans and compiled programs with Allreduce_multi ('linear' stays
    bit-identical). Device buffers only."""
    self.check_revoked()
    self.check_failed()
    if isinstance(bufs, (list, tuple)) and bufs \
            and not _is_dev(bufs[0]):
        raise TypeError(
            "Pallreduce_init: device buffers only (host partitioned "
            "transfers: use Psend_init/Precv_init)")
    return self.coll.pallreduce_init_dev(self, bufs, op,
                                         deterministic=deterministic)


def _Reduce_scatter_multi(self, bufs, op=op_mod.SUM,
                          deterministic=None):
    """Fused (bucketed) reduce_scatter over a list/pytree of buffers
    — the zero/ sharded-data-parallel gradient step. Leaves coalesce
    into the same dtype-segregated buckets as Allreduce_multi, each
    padded to a multiple of comm size so it lowers to ONE compiled
    reduce_scatter; returns a zero.ShardedState holding this rank's
    1-D shard per bucket ('linear' determinism stays bit-identical to
    the per-buffer allreduce fold). Host lists/tuples run the bucket
    cycle over the stacked host collectives."""
    self.check_revoked()
    self.check_failed()
    if isinstance(bufs, (list, tuple)) and bufs \
            and not _is_dev(bufs[0]):
        from ompi_tpu.zero import layout as _zl

        return _zl.host_reduce_scatter_multi(self, bufs, op)
    return self.coll.reduce_scatter_multi_dev(
        self, bufs, op, deterministic=deterministic)


def _Reduce_scatter_multi_init(self, bufs, op=op_mod.SUM,
                               deterministic=None) -> rq.Request:
    """Persistent form of Reduce_scatter_multi: plan + compile + bind
    at init, each Start()+Wait() is one cached launch per bucket;
    req.array holds the cycle's ShardedState. Device buffers only."""
    self.check_revoked()
    self.check_failed()
    if isinstance(bufs, (list, tuple)) and bufs \
            and not _is_dev(bufs[0]):
        raise TypeError(
            "Reduce_scatter_multi_init: device buffers only (host "
            "cycle: call Reduce_scatter_multi per step)")
    return self.coll.reduce_scatter_multi_init_dev(
        self, bufs, op, deterministic=deterministic)


def _Allgather_multi(self, state):
    """Rebuild the full pytree from a zero.ShardedState: ONE compiled
    all_gather per bucket, concat in rank order (= the pack order),
    pad dropped, leaf shapes restored. The parameter-refresh tail of
    the ZeRO cycle. Host (numpy) shards ride the object channel."""
    self.check_revoked()
    self.check_failed()
    shards = getattr(state, "shards", None)
    if shards and isinstance(shards[0], np.ndarray):
        from ompi_tpu.zero import layout as _zl

        return _zl.host_allgather_multi(self, state)
    return self.coll.allgather_multi_dev(self, state)


def _Allgather_multi_init(self, state) -> rq.Request:
    """Persistent form of Allgather_multi: plan + compile + bind the
    state's shards at init (jax arrays are immutable — the binding is
    per-init, like every persistent device collective); each
    Start()+Wait() is one cached launch per bucket, req.array holds
    the rebuilt pytree. ``req.rebind(new_state)`` swaps in a same-plan
    state's fresh shards with no re-planning (the zero-3 parameter
    stream's per-step refresh); ``req.discard()`` drops a completed
    cycle's gathered arrays (free-after-use). Device shards only."""
    self.check_revoked()
    self.check_failed()
    shards = getattr(state, "shards", None)
    if shards and isinstance(shards[0], np.ndarray):
        raise TypeError(
            "Allgather_multi_init: device shards only (host cycle: "
            "call Allgather_multi per step)")
    return self.coll.allgather_multi_init_dev(self, state)


def _Preduce_scatter_init(self, bufs, op=op_mod.SUM,
                          deterministic=None) -> rq.Request:
    """MPI-4 partitioned fused reduce_scatter — the overlapped form
    of the ZeRO gradient step: one partition per pytree leaf,
    Pready(i[, value]) hands leaf i over, and a bucket's single
    compiled reduce_scatter launches the moment its LAST member leaf
    is ready (zero_overlap_flushes counts buckets that beat the final
    push); Wait() drains the tail, req.array holds the ShardedState.
    Shares ZeroPlans and compiled programs with Reduce_scatter_multi
    ('linear' stays bit-identical). Device buffers only."""
    self.check_revoked()
    self.check_failed()
    if isinstance(bufs, (list, tuple)) and bufs \
            and not _is_dev(bufs[0]):
        raise TypeError(
            "Preduce_scatter_init: device buffers only (host "
            "partitioned transfers: use Psend_init/Precv_init)")
    return self.coll.preduce_scatter_init_dev(
        self, bufs, op, deterministic=deterministic)


def _Gather(self, sendbuf, recvbuf=None, root: int = 0):
    self.check_revoked()
    self.check_failed()
    if _is_dev(sendbuf):
        return self.coll.gather_dev(self, sendbuf, root)
    sarr, count, dt = _parse_buf(sendbuf)
    rarr = None if recvbuf is None else _parse_buf(recvbuf)[0]
    self.coll.gather(self, sarr, rarr, count, dt, root)


def _Gatherv(self, sendbuf, recvbuf, counts, displs=None,
             root: int = 0):
    self.check_revoked()
    self.check_failed()
    if _is_dev(sendbuf):
        # device path returns the packed (sum(counts), ...) array on
        # root (displs are a host-layout concept); recvbuf unused
        return self.coll.gatherv_dev(self, sendbuf, counts, root)
    sarr = _parse_buf(sendbuf)[0]
    rarr = None if recvbuf is None else _parse_buf(recvbuf)[0]
    if displs is None:
        displs = packed_displs(counts)
    self.coll.gatherv(self, sarr, rarr, counts, displs,
                      dtype_of(sarr), root)


def _Scatter(self, sendbuf, recvbuf=None, root: int = 0,
             device: bool = False):
    """``device=True`` lets non-roots (who pass no buffers) opt into the
    device path explicitly; the root is auto-detected from sendbuf."""
    self.check_revoked()
    self.check_failed()
    if _is_dev(sendbuf) or device:
        return self.coll.scatter_dev(self, sendbuf, root,
                                     like=recvbuf)
    rarr, count, dt = _parse_buf(recvbuf)
    sarr = None if sendbuf is None else _parse_buf(sendbuf)[0]
    self.coll.scatter(self, sarr, rarr, count, dt, root)


def _Scatterv(self, sendbuf, recvbuf, counts, displs=None,
              root: int = 0, device: bool = False):
    """Device path (root's sendbuf on device, or device=True): returns
    this rank's (counts[rank], ...) segment as a new device array;
    recvbuf serves as the non-root shape/dtype template (``like``)."""
    self.check_revoked()
    self.check_failed()
    if _is_dev(sendbuf) or device:
        _require_packed_displs(counts, displs, "Scatterv")
        return self.coll.scatterv_dev(self, sendbuf, counts, root,
                                      like=recvbuf)
    rarr = _parse_buf(recvbuf)[0]
    sarr = None if sendbuf is None else _parse_buf(sendbuf)[0]
    if displs is None:
        displs = packed_displs(counts)
    self.coll.scatterv(self, sarr, rarr, counts, displs,
                       dtype_of(rarr), root)


def _Allgather(self, sendbuf, recvbuf=None):
    self.check_revoked()
    self.check_failed()
    if _is_dev(sendbuf):
        return self.coll.allgather_dev(self, sendbuf)
    sarr, count, dt = _parse_buf(sendbuf)
    rarr = _parse_buf(recvbuf)[0]
    self.coll.allgather(self, sarr, rarr, count, dt)


def _Allgatherv(self, sendbuf, recvbuf, counts, displs=None):
    self.check_revoked()
    self.check_failed()
    if _is_dev(sendbuf):
        return self.coll.allgatherv_dev(self, sendbuf, counts)
    sarr = _parse_buf(sendbuf)[0]
    rarr = _parse_buf(recvbuf)[0]
    if displs is None:
        displs = packed_displs(counts)
    self.coll.allgatherv(self, sarr, rarr, counts, displs,
                         dtype_of(sarr))


def _Alltoall(self, sendbuf, recvbuf=None):
    self.check_revoked()
    self.check_failed()
    if _is_dev(sendbuf):
        return self.coll.alltoall_dev(self, sendbuf)
    sarr = _parse_buf(sendbuf)[0]
    rarr = _parse_buf(recvbuf)[0]
    count = np.asarray(sarr).size // self.size
    self.coll.alltoall(self, sarr, rarr, count, dtype_of(sarr))


def _Alltoallv(self, sendbuf, recvbuf, scounts, rcounts,
               sdispls=None, rdispls=None, max_count=None):
    """Device path: ``max_count`` (e.g. a fixed MoE expert capacity)
    makes the ragged exchange entirely host-free; without it one tiny
    host max-allreduce sizes the padded cells."""
    self.check_revoked()
    self.check_failed()
    if _is_dev(sendbuf):
        _require_packed_displs(scounts, sdispls, "Alltoallv")
        return self.coll.alltoallv_dev(self, sendbuf, scounts, rcounts,
                                       max_count=max_count)
    sarr = _parse_buf(sendbuf)[0]
    rarr = _parse_buf(recvbuf)[0]
    if sdispls is None:
        sdispls = packed_displs(scounts)
    if rdispls is None:
        rdispls = packed_displs(rcounts)
    self.coll.alltoallv(self, sarr, rarr, scounts, sdispls, rcounts,
                        rdispls, dtype_of(sarr))


def _Reduce_scatter_block(self, sendbuf, recvbuf=None, op=op_mod.SUM,
                          deterministic=None):
    self.check_revoked()
    self.check_failed()
    if _is_dev(sendbuf):
        return self.coll.reduce_scatter_block_dev(
            self, sendbuf, op, deterministic=deterministic)
    rarr, count, dt = _parse_buf(recvbuf)
    sarr = _parse_buf(sendbuf)[0]
    self.coll.reduce_scatter_block(self, sarr, rarr, count, dt, op)


def _Reduce_scatter(self, sendbuf, recvbuf, counts, op=op_mod.SUM,
                    deterministic=None):
    self.check_revoked()
    self.check_failed()
    if _is_dev(sendbuf):
        return self.coll.reduce_scatter_dev(
            self, sendbuf, counts, op, deterministic=deterministic)
    rarr = _parse_buf(recvbuf)[0]
    sarr = _parse_buf(sendbuf)[0]
    self.coll.reduce_scatter(self, sarr, rarr, counts,
                             dtype_of(rarr), op)


def _Scan(self, sendbuf, recvbuf=None, op=op_mod.SUM) -> None:
    self.check_revoked()
    self.check_failed()
    if _is_dev(sendbuf):
        return self.coll.scan_dev(self, sendbuf, op)
    if recvbuf is None:
        raise TypeError("Scan with a host sendbuf requires recvbuf "
                        "(recvbuf=None is the device-array form)")
    sarr, count, dt = _parse_buf(sendbuf)
    rarr = _parse_buf(recvbuf)[0]
    self.coll.scan(self, sarr, rarr, count, dt, op)


def _Exscan(self, sendbuf, recvbuf=None, op=op_mod.SUM) -> None:
    self.check_revoked()
    self.check_failed()
    if _is_dev(sendbuf):
        return self.coll.exscan_dev(self, sendbuf, op)
    if recvbuf is None:
        raise TypeError("Exscan with a host sendbuf requires recvbuf "
                        "(recvbuf=None is the device-array form)")
    sarr, count, dt = _parse_buf(sendbuf)
    rarr = _parse_buf(recvbuf)[0]
    self.coll.exscan(self, sarr, rarr, count, dt, op)


# -- nonblocking collectives (MPI-3 i-variants via coll/libnbc; device
# buffers dispatch async on the device plane and return a readiness-
# backed DeviceRequest whose .array is the result) --

def _Ibarrier(self, device: bool = False) -> rq.Request:
    if device:
        return self.coll.ibarrier_dev(self)
    return self.coll.ibarrier(self)


def _Ibcast(self, buf, root: int = 0) -> rq.Request:
    d = _parse_dev(buf)
    if d is not None:
        arr, count, dt = d
        if dt is None and count is None:
            return self.coll.ibcast_dev(self, arr, root)
        from ompi_tpu.datatype import device as dtdev

        packed = (dtdev.pack(arr, dt, count) if self.rank == root
                  else _dev_recv_plan(arr, count, dt)[0])
        req = self.coll.ibcast_dev(self, packed, root)
        # unpack is itself async device work: rebinding .array keeps
        # the request's readiness probe watching the FINAL result
        req.array = dtdev.unpack(req.array, dt, count, arr)
        return req
    arr, count, dt = _parse_buf(buf)
    return self.coll.ibcast(self, arr, count, dt, root)


def _Iallreduce(self, sendbuf, recvbuf=None, op=op_mod.SUM,
                deterministic=None) -> rq.Request:
    d = _parse_dev(sendbuf)
    if d is not None:
        arr, count, dt = d
        req = self.coll.iallreduce_dev(self, _dev_pack(arr, count, dt),
                                       op, deterministic=deterministic)
        if dt is not None or count is not None:
            from ompi_tpu.datatype import device as dtdev

            req.array = dtdev.unpack(req.array, dt, count, arr)
        return req
    _require_recvbuf(recvbuf, "Iallreduce")
    if sendbuf is IN_PLACE:
        rarr, count, dt = _parse_buf(recvbuf)
        return self.coll.iallreduce(self, IN_PLACE, rarr, count, dt, op)
    sarr, count, dt = _parse_buf(sendbuf)
    return self.coll.iallreduce(self, sarr, _parse_buf(recvbuf)[0],
                                count, dt, op)


def _Ireduce(self, sendbuf, recvbuf=None, op=op_mod.SUM,
             root: int = 0) -> rq.Request:
    if _is_dev(sendbuf):
        return self.coll.ireduce_dev(self, sendbuf, op, root)
    sarr, count, dt = _parse_buf(sendbuf)
    rarr = None if recvbuf is None else _parse_buf(recvbuf)[0]
    return self.coll.ireduce(self, sarr, rarr, count, dt, op, root)


def _Igather(self, sendbuf, recvbuf=None, root: int = 0) -> rq.Request:
    if _is_dev(sendbuf):
        return self.coll.igather_dev(self, sendbuf, root)
    sarr, count, dt = _parse_buf(sendbuf)
    rarr = None if recvbuf is None else _parse_buf(recvbuf)[0]
    return self.coll.igather(self, sarr, rarr, count, dt, root)


def _Iscatter(self, sendbuf, recvbuf=None, root: int = 0,
              device: bool = False) -> rq.Request:
    if _is_dev(sendbuf) or device:
        return self.coll.iscatter_dev(self, sendbuf, root,
                                      like=recvbuf)
    rarr, count, dt = _parse_buf(_require_recvbuf(recvbuf, "Iscatter"))
    sarr = None if sendbuf is None else _parse_buf(sendbuf)[0]
    return self.coll.iscatter(self, sarr, rarr, count, dt, root)


def _Iallgather(self, sendbuf, recvbuf=None) -> rq.Request:
    if _is_dev(sendbuf):
        return self.coll.iallgather_dev(self, sendbuf)
    sarr, count, dt = _parse_buf(sendbuf)
    rarr = _parse_buf(_require_recvbuf(recvbuf, "Iallgather"))[0]
    return self.coll.iallgather(self, sarr, rarr, count, dt)


def _Ialltoall(self, sendbuf, recvbuf=None) -> rq.Request:
    if _is_dev(sendbuf):
        return self.coll.ialltoall_dev(self, sendbuf)
    sarr = _parse_buf(sendbuf)[0]
    rarr = _parse_buf(_require_recvbuf(recvbuf, "Ialltoall"))[0]
    count = np.asarray(sarr).size // self.size
    return self.coll.ialltoall(self, sarr, rarr, count, dtype_of(sarr))


def _Igatherv(self, sendbuf, recvbuf, counts, displs=None,
              root: int = 0) -> rq.Request:
    if _is_dev(sendbuf):
        return self.coll.igatherv_dev(self, sendbuf, counts, root)
    sarr = _parse_buf(sendbuf)[0]
    rarr = None if recvbuf is None else _parse_buf(recvbuf)[0]
    if displs is None:
        displs = packed_displs(counts)
    return self.coll.igatherv(self, sarr, rarr, counts, displs,
                              dtype_of(sarr), root)


def _Iscatterv(self, sendbuf, recvbuf, counts, displs=None,
               root: int = 0, device: bool = False) -> rq.Request:
    if _is_dev(sendbuf) or device:
        _require_packed_displs(counts, displs, "Iscatterv")
        return self.coll.iscatterv_dev(self, sendbuf, counts, root,
                                       like=recvbuf)
    rarr = _parse_buf(recvbuf)[0]
    sarr = None if sendbuf is None else _parse_buf(sendbuf)[0]
    if displs is None:
        displs = packed_displs(counts)
    return self.coll.iscatterv(self, sarr, rarr, counts, displs,
                               dtype_of(rarr), root)


def _Iallgatherv(self, sendbuf, recvbuf, counts,
                 displs=None) -> rq.Request:
    if _is_dev(sendbuf):
        return self.coll.iallgatherv_dev(self, sendbuf, counts)
    sarr = IN_PLACE if sendbuf is IN_PLACE else _parse_buf(sendbuf)[0]
    rarr = _parse_buf(recvbuf)[0]
    if displs is None:
        displs = packed_displs(counts)
    return self.coll.iallgatherv(self, sarr, rarr, counts, displs,
                                 dtype_of(rarr))


def _Ialltoallv(self, sendbuf, recvbuf, scounts, rcounts,
                sdispls=None, rdispls=None,
                max_count=None) -> rq.Request:
    if _is_dev(sendbuf):
        _require_packed_displs(scounts, sdispls, "Ialltoallv")
        return self.coll.ialltoallv_dev(self, sendbuf, scounts,
                                        rcounts, max_count=max_count)
    sarr = _parse_buf(sendbuf)[0]
    rarr = _parse_buf(recvbuf)[0]
    if sdispls is None:
        sdispls = packed_displs(scounts)
    if rdispls is None:
        rdispls = packed_displs(rcounts)
    return self.coll.ialltoallv(self, sarr, rarr, scounts, sdispls,
                                rcounts, rdispls, dtype_of(sarr))


def _Iscan(self, sendbuf, recvbuf=None, op=op_mod.SUM) -> rq.Request:
    if _is_dev(sendbuf):
        return self.coll.iscan_dev(self, sendbuf, op)
    _require_recvbuf(recvbuf, "Iscan")
    rarr, rcount, rdt = _parse_buf(recvbuf)
    if sendbuf is IN_PLACE:
        return self.coll.iscan(self, IN_PLACE, rarr, rcount, rdt, op)
    sarr, count, dt = _parse_buf(sendbuf)
    return self.coll.iscan(self, sarr, rarr, count, dt, op)


def _Iexscan(self, sendbuf, recvbuf=None, op=op_mod.SUM) -> rq.Request:
    if _is_dev(sendbuf):
        return self.coll.iexscan_dev(self, sendbuf, op)
    _require_recvbuf(recvbuf, "Iexscan")
    rarr, rcount, rdt = _parse_buf(recvbuf)
    if sendbuf is IN_PLACE:
        return self.coll.iexscan(self, IN_PLACE, rarr, rcount, rdt, op)
    sarr, count, dt = _parse_buf(sendbuf)
    return self.coll.iexscan(self, sarr, rarr, count, dt, op)


def _Ireduce_scatter_block(self, sendbuf, recvbuf=None,
                           op=op_mod.SUM) -> rq.Request:
    if _is_dev(sendbuf):
        return self.coll.ireduce_scatter_block_dev(self, sendbuf, op)
    rarr, count, dt = _parse_buf(
        _require_recvbuf(recvbuf, "Ireduce_scatter_block"))
    return self.coll.ireduce_scatter_block(
        self, _parse_buf(sendbuf)[0], rarr, count, dt, op)


def _Ireduce_scatter(self, sendbuf, recvbuf, counts,
                     op=op_mod.SUM) -> rq.Request:
    if _is_dev(sendbuf):
        return self.coll.ireduce_scatter_dev(self, sendbuf, counts, op)
    rarr = _parse_buf(recvbuf)[0]
    return self.coll.ireduce_scatter(self, _parse_buf(sendbuf)[0],
                                     rarr, counts, dtype_of(rarr), op)


# -- MPI-4 persistent collectives (coll.h *_init slots via libnbc) -------

def _Barrier_init(self) -> rq.Request:
    return self.coll.barrier_init(self)


def _Bcast_init(self, buf, root: int = 0) -> rq.Request:
    if _is_dev(buf):
        return self.coll.bcast_init_dev(self, buf, root)
    arr, count, dt = _parse_buf(buf)
    return self.coll.bcast_init(self, arr, count, dt, root)


def _Allreduce_init(self, sendbuf, recvbuf=None,
                    op=op_mod.SUM) -> rq.Request:
    if _is_dev(sendbuf):
        # persistent device collective: operands bind now, every
        # start() re-dispatches the cached compiled program;
        # req.array holds each cycle's result
        return self.coll.allreduce_init_dev(self, sendbuf, op)
    sarr, count, dt = _parse_buf(sendbuf)
    return self.coll.allreduce_init(self, sarr, _parse_buf(recvbuf)[0],
                                    count, dt, op)


def _Reduce_init(self, sendbuf, recvbuf, op=op_mod.SUM,
                 root: int = 0) -> rq.Request:
    sarr, count, dt = _parse_buf(sendbuf)
    rarr = None if recvbuf is None else _parse_buf(recvbuf)[0]
    return self.coll.reduce_init(self, sarr, rarr, count, dt, op, root)


def _Gather_init(self, sendbuf, recvbuf, root: int = 0) -> rq.Request:
    sarr, count, dt = _parse_buf(sendbuf)
    rarr = None if recvbuf is None else _parse_buf(recvbuf)[0]
    return self.coll.gather_init(self, sarr, rarr, count, dt, root)


def _Scatter_init(self, sendbuf, recvbuf, root: int = 0) -> rq.Request:
    rarr, count, dt = _parse_buf(recvbuf)
    sarr = None if sendbuf is None else _parse_buf(sendbuf)[0]
    return self.coll.scatter_init(self, sarr, rarr, count, dt, root)


def _Allgather_init(self, sendbuf, recvbuf=None) -> rq.Request:
    if _is_dev(sendbuf):
        return self.coll.allgather_init_dev(self, sendbuf)
    sarr, count, dt = _parse_buf(sendbuf)
    rarr = _parse_buf(_require_recvbuf(recvbuf, "Allgather_init"))[0]
    return self.coll.allgather_init(self, sarr, rarr, count, dt)


def _Reduce_scatter_block_init(self, sendbuf, recvbuf=None,
                               op=op_mod.SUM) -> rq.Request:
    if _is_dev(sendbuf):
        return self.coll.reduce_scatter_block_init_dev(self, sendbuf,
                                                       op)
    sarr = _parse_buf(sendbuf)[0]
    rarr, count, dt = _parse_buf(
        _require_recvbuf(recvbuf, "Reduce_scatter_block_init"))
    return self.coll.reduce_scatter_block_init(self, sarr, rarr,
                                               count, dt, op)


def _Alltoall_init(self, sendbuf, recvbuf=None) -> rq.Request:
    if _is_dev(sendbuf):
        return self.coll.alltoall_init_dev(self, sendbuf)
    sarr = _parse_buf(sendbuf)[0]
    rarr = _parse_buf(_require_recvbuf(recvbuf, "Alltoall_init"))[0]
    count = np.asarray(sarr).size // self.size
    return self.coll.alltoall_init(self, sarr, rarr, count,
                                   dtype_of(sarr))


def _barrier(self) -> None:
    _Barrier(self)


def _bcast(self, obj=None, root: int = 0):
    self.check_revoked()
    self.check_failed()
    return self.coll.bcast_obj(self, obj, root)


def _gather(self, obj, root: int = 0):
    return self.coll.gather_obj(self, obj, root)


def _scatter(self, objs=None, root: int = 0):
    return self.coll.scatter_obj(self, objs, root)


def _allgather(self, obj):
    return self.coll.allgather_obj(self, obj)


def _alltoall(self, objs):
    return self.coll.alltoall_obj(self, objs)


def _allreduce(self, obj, op=None):
    fn = op if callable(op) and not isinstance(op, op_mod.Op) else \
        (op.np_fn if isinstance(op, op_mod.Op) else (lambda a, b: a + b))
    return self.coll.allreduce_obj(self, obj, fn)


def _reduce(self, obj, op=None, root: int = 0):
    vals = self.coll.gather_obj(self, obj, root)
    if vals is None:
        return None
    fn = op if callable(op) and not isinstance(op, op_mod.Op) else \
        (op.np_fn if isinstance(op, op_mod.Op) else (lambda a, b: a + b))
    acc = vals[0]
    for v in vals[1:]:
        acc = fn(acc, v)
    return acc


# -- errhandler + info planes (ompi/errhandler, ompi/info) ---------------

def _Set_errhandler(self, eh) -> None:
    """MPI_Comm_set_errhandler: a string mode (mpi.ERRORS_RETURN /
    ERRORS_ARE_FATAL) or an errors.Errhandler callback
    (Comm_create_errhandler). Inherited by dup/split."""
    self.errhandler = eh


def _Get_errhandler(self):
    return self.errhandler


def _Set_info(self, info) -> None:
    """MPI_Comm_set_info; a mpi_memory_alloc_kinds request is
    answered with the granted subset (info_memkind.c)."""
    from ompi_tpu.info import apply_memkinds, as_info

    self.info = apply_memkinds(as_info(info))


def _Get_info(self):
    from ompi_tpu.info import as_info

    return as_info(self.info)


def _api_entry(name: str, fn, errhandled: bool):
    """What the API table attaches for a binding.

    Every binding opens the one span source's ``ompi:api.<name>``
    (trace/recorder.api_span: while a jax.profiler session or the ring
    is up — one guard otherwise), which gives the call the sequence
    number every span beneath it carries.

    The ``_ERRHANDLED`` bindings also route MPIErrors escaping them
    through the comm's errhandler (the reference's
    OMPI_ERRHANDLER_INVOKE at every binding's error exit, e.g.
    allreduce.c). String modes re-raise; a user-callback handler that
    returns makes the operation recover (the call returns None)."""
    def traced(self, *a, **kw):
        if not _trace.active():
            return fn(self, *a, **kw)
        with _trace.api_span(name):
            return fn(self, *a, **kw)

    def handled(self, *a, **kw):
        try:
            if not _trace.active():
                return fn(self, *a, **kw)
            with _trace.api_span(name):
                return fn(self, *a, **kw)
        except errors.MPIError as exc:
            errors.dispatch(self, exc)  # raises unless a callback
            return None                 # handled it

    entry = handled if errhandled else traced
    entry.__name__ = fn.__name__
    entry.__doc__ = fn.__doc__
    return entry


_pending_bsends: List[Tuple[rq.Request, int]] = []


def _flush_bsends() -> None:
    for r, _ in list(_pending_bsends):
        r.wait()
    _pending_bsends.clear()


#: capitalized buffer ops whose errors route through the comm's
#: errhandler (the OMPI_ERRHANDLER_INVOKE set). i-variants surface
#: errors at wait: Isend/Irecv stamp ``.comm`` on their requests and
#: Request.wait dispatches on it (the reference likewise invokes on
#: the request's comm at completion).
_ERRHANDLED = (
    "Send", "Recv", "Ssend", "Rsend", "Bsend", "Sendrecv",
    "Sendrecv_replace", "Mrecv", "Probe", "Barrier", "Bcast",
    "Reduce", "Allreduce", "Gather", "Gatherv", "Scatter", "Scatterv",
    "Allgather", "Allgatherv", "Alltoall", "Alltoallv",
    "Reduce_scatter", "Reduce_scatter_block", "Scan", "Exscan",
    "Allreduce_multi", "Reduce_scatter_multi", "Allgather_multi",
)

_API = {
    "send": _send, "isend": _isend, "recv": _recv, "irecv": _irecv,
    "sendrecv": _sendrecv,
    "Send": _Send, "Isend": _Isend, "Ssend": _Ssend, "Issend": _Issend,
    "Rsend": _Rsend, "Bsend": _Bsend, "Recv": _Recv, "Irecv": _Irecv,
    "Sendrecv": _Sendrecv, "Sendrecv_replace": _Sendrecv_replace,
    "Isendrecv": _Isendrecv, "Isendrecv_replace": _Isendrecv_replace,
    "Probe": _Probe, "Iprobe": _Iprobe, "Mprobe": _Mprobe,
    "Improbe": _Improbe, "Mrecv": _Mrecv,
    "Send_init": _Send_init, "Recv_init": _Recv_init,
    "Barrier": _Barrier, "barrier": _barrier,
    "Pack": _Pack, "Unpack": _Unpack, "Pack_size": _Pack_size,
    "Bcast": _Bcast, "bcast": _bcast,
    "Reduce": _Reduce, "reduce": _reduce,
    "Allreduce": _Allreduce, "allreduce": _allreduce,
    "Allreduce_multi": _Allreduce_multi,
    "Allreduce_multi_init": _Allreduce_multi_init,
    "Pallreduce_init": _Pallreduce_init,
    "Reduce_scatter_multi": _Reduce_scatter_multi,
    "Reduce_scatter_multi_init": _Reduce_scatter_multi_init,
    "Allgather_multi": _Allgather_multi,
    "Allgather_multi_init": _Allgather_multi_init,
    "Preduce_scatter_init": _Preduce_scatter_init,
    "Gather": _Gather, "gather": _gather,
    "Gatherv": _Gatherv,
    "Scatter": _Scatter, "scatter": _scatter,
    "Scatterv": _Scatterv,
    "Allgather": _Allgather, "allgather": _allgather,
    "Allgatherv": _Allgatherv,
    "Alltoall": _Alltoall, "alltoall": _alltoall,
    "Alltoallv": _Alltoallv,
    "Reduce_scatter": _Reduce_scatter,
    "Reduce_scatter_block": _Reduce_scatter_block,
    "Scan": _Scan, "Exscan": _Exscan,
    "Set_errhandler": _Set_errhandler,
    "Get_errhandler": _Get_errhandler,
    "Set_info": _Set_info, "Get_info": _Get_info,
    "Ibarrier": _Ibarrier, "Ibcast": _Ibcast,
    "Iallreduce": _Iallreduce, "Ireduce": _Ireduce,
    "Igather": _Igather, "Iscatter": _Iscatter,
    "Iallgather": _Iallgather, "Ialltoall": _Ialltoall,
    "Igatherv": _Igatherv, "Iscatterv": _Iscatterv,
    "Iallgatherv": _Iallgatherv, "Ialltoallv": _Ialltoallv,
    "Iscan": _Iscan, "Iexscan": _Iexscan,
    "Ireduce_scatter": _Ireduce_scatter,
    "Ireduce_scatter_block": _Ireduce_scatter_block,
    "Barrier_init": _Barrier_init, "Bcast_init": _Bcast_init,
    "Allreduce_init": _Allreduce_init, "Reduce_init": _Reduce_init,
    "Gather_init": _Gather_init, "Scatter_init": _Scatter_init,
    "Allgather_init": _Allgather_init, "Alltoall_init": _Alltoall_init,
    "Reduce_scatter_block_init": _Reduce_scatter_block_init,
}

for _name, _fn in _API.items():
    setattr(Communicator, _name,
            _api_entry(_name, _fn, _name in _ERRHANDLED))

# topology API (Create_cart/Cart_sub/Neighbor_*) attaches its own
# Communicator methods at import (ompi/mca/topo equivalent)
from ompi_tpu import topo as _topo  # noqa: E402,F401

# partitioned communication subsystem (MPI-4 Psend_init/Precv_init +
# Pallreduce_init — ompi/mca/part equivalent)
from ompi_tpu import part as _part  # noqa: E402,F401

# intercommunicators + dynamic processes (ompi/communicator + dpm)
from ompi_tpu.comm.intercomm import (  # noqa: E402,F401
    ROOT, Intercommunicator, comm_accept as Comm_accept,
    comm_connect as Comm_connect, intercomm_create as Intercomm_create,
    open_port as Open_port,
)

# MPI-IO (ompio equivalent: ompi/mca/io + fs/fbtl/fcoll/sharedfp)
from ompi_tpu.io import (  # noqa: E402,F401
    File, File_delete, File_open, MODE_APPEND, MODE_CREATE,
    MODE_DELETE_ON_CLOSE, MODE_EXCL, MODE_RDONLY, MODE_RDWR,
    MODE_SEQUENTIAL, MODE_WRONLY, SEEK_CUR, SEEK_END, SEEK_SET,
)

# dynamic processes (ompi/dpm: PMIx_Spawn equivalent)
from ompi_tpu.dpm import (  # noqa: E402,F401
    appnum as Appnum, comm_spawn as Comm_spawn,
    comm_spawn_multiple as Comm_spawn_multiple,
    get_parent as Comm_get_parent,
)

# MPI_Pack family incl. the canonical external32 representation
from ompi_tpu.datatype.convertor import (  # noqa: E402,F401
    pack as Pack, pack_external as Pack_external, unpack as Unpack,
    unpack_external as Unpack_external,
)

# MPI_Info objects (ompi/info/info.c) + memkind plane (info_memkind.c)
from ompi_tpu.info import (  # noqa: E402,F401
    Info, MEMORY_ALLOC_KINDS, env_info as Info_env,
)

# errhandler factories (ompi/errhandler/errhandler.h:401) — one
# factory serves all three object classes, as in the reference
from ompi_tpu.errors import (  # noqa: E402,F401
    ERRORS_ABORT, ERRORS_ARE_FATAL, ERRORS_RETURN, Errhandler,
    add_error_class as Add_error_class,
    add_error_code as Add_error_code,
    add_error_string as Add_error_string,
    error_class as Error_class,
    error_string as Error_string,
    create_errhandler as Comm_create_errhandler,
    create_errhandler as Win_create_errhandler,
    create_errhandler as File_create_errhandler,
)

# attribute/keyval caching (ompi/attribute/attribute.c; predefined
# attrs attribute_predefined.c:119-195). Objects expose
# Set_attr/Get_attr/Delete_attr; keyvals are created per object class.
from ompi_tpu import attr as _attr_mod  # noqa: E402
from ompi_tpu.attr import (  # noqa: E402,F401
    APPNUM, HOST, IO, KEYVAL_INVALID, LASTUSEDCODE, NO_COPY, TAG_UB,
    UNIVERSE_SIZE, WIN_BASE, WIN_CREATE_FLAVOR, WIN_DISP_UNIT,
    WIN_MODEL, WIN_SIZE, WTIME_IS_GLOBAL, dup_fn, null_copy_fn,
)


def Comm_create_keyval(copy_fn=None, delete_fn=None, extra_state=None):
    """MPI_Comm_create_keyval: copy_fn(obj, keyval, extra_state, val)
    -> new val (return mpi.NO_COPY to drop the attr on dup; copy_fn
    None never propagates); delete_fn(obj, keyval, val, extra_state)
    fires on delete/overwrite/free."""
    return _attr_mod.create_keyval("comm", copy_fn, delete_fn,
                                   extra_state)


def Win_create_keyval(copy_fn=None, delete_fn=None, extra_state=None):
    return _attr_mod.create_keyval("win", copy_fn, delete_fn,
                                   extra_state)


def Type_create_keyval(copy_fn=None, delete_fn=None, extra_state=None):
    return _attr_mod.create_keyval("type", copy_fn, delete_fn,
                                   extra_state)


def Comm_free_keyval(keyval: int) -> int:
    return _attr_mod.free_keyval(keyval)


Win_free_keyval = Comm_free_keyval
Type_free_keyval = Comm_free_keyval


# ---------------------------------------------------------------------------
# module-level state: COMM_WORLD / COMM_SELF / init / finalize
# ---------------------------------------------------------------------------

def Init():
    from ompi_tpu.runtime import state

    return state.init()


def Request_get_status(request) -> Tuple[bool, Status]:
    """MPI_Request_get_status (ompi/mpi/c/request_get_status.c):
    (flag, status) for a request. The C binding exists because
    MPI_Test deallocates the handle; handles here are objects that
    test() never frees, so this is the same operation with the
    status returned alongside."""
    return request.test(), request.retrieve_status()


def Grequest_start(query_fn=None, free_fn=None, cancel_fn=None):
    """MPI_Grequest_start: returns a request the application completes
    with req.complete() (MPI_Grequest_complete). Works with
    wait/test/wait_all like any other request."""
    return rq.GeneralizedRequest(query_fn, free_fn, cancel_fn)


def Session_init(info=None):
    """MPI-4 MPI_Session_init: an instance handle with NO world model
    (reference: ompi/mpi/c/session_init.c over ompi/instance). Query
    psets, derive groups, build comms via Comm_create_from_group —
    see runtime.state.Session."""
    from ompi_tpu.runtime import state

    return state.Session(info)


def Group_from_session_pset(session, pset_name: str):
    return session.group_from_pset(pset_name)


def Comm_create_from_group(group, tag: str = "org.ompi_tpu.default"):
    from ompi_tpu.comm import comm_create_from_group

    return comm_create_from_group(group, tag)


def Abort(comm=None, errorcode: int = 1) -> None:
    """MPI_Abort: bring the job down through the runtime — the store
    broadcasts the abort and the launcher kills every rank (the
    reference routes through the PRRTE daemons the same way)."""
    from ompi_tpu.runtime import state

    state.abort(errorcode,
                f"MPI_Abort on {getattr(comm, 'name', 'the job')}")


def Finalize() -> None:
    from ompi_tpu.runtime import state

    _flush_bsends()
    state.finalize()


def Is_initialized() -> bool:
    from ompi_tpu.runtime import state

    return state.is_initialized()


def Get_processor_name() -> str:
    from ompi_tpu.runtime import rte

    return rte.hostname()


def Wtime() -> float:
    import time

    return time.perf_counter()


def Wtick() -> float:
    """MPI_Wtick: resolution of Wtime."""
    import time

    return time.get_clock_info("perf_counter").resolution


def Get_version():
    """MPI_Get_version: the standard level this framework targets
    (3.1 + the MPI-4 subset: sessions, partitioned p2p, big-count,
    persistent collectives — mirroring the reference fork)."""
    return (3, 1)


def Get_library_version() -> str:
    return ("ompi_tpu: TPU-native MPI-class framework "
            "(Open MPI big-count fork parity build)")


def __getattr__(name: str):
    if name == "COMM_WORLD":
        from ompi_tpu.runtime import state

        return state.world()
    if name == "COMM_SELF":
        from ompi_tpu.runtime import state

        return state.comm_self()
    raise AttributeError(name)


pvar.record("init_import_ns", _time.monotonic_ns() - _T_IMPORT)
