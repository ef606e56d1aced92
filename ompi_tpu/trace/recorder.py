"""Per-process bounded ring-buffer span recorder.

Reference tradition: Score-P/OTF2 region records and the Chrome
trace-event recorder — bounded memory, drop accounting, monotonic
timestamps. Here the recorder is layered on the repo's existing MPI_T
planes instead of a sidecar: drops surface as the ``trace_dropped``
pvar, and the log2 latency histogram (:func:`hist`) is plain pvar
counters readable through ``pvar.snapshot()`` / ``mpit``.

ONE span source, two sinks. :func:`span` is the call every
instrumented site on the device path and in ``mpi.Init()`` makes:

- while a ``jax.profiler`` session is live it opens a
  ``jax.profiler.TraceAnnotation`` named ``ompi:<subsys>.<name>``, so
  the span lands on ``/host:CPU`` of the same ``.xplane.pb`` as the
  chip's ``XLA Ops``/``XLA Modules`` lines, on the profiler's clock
  (nothing to switch on: any profiler session carries the program's
  spans);
- while ``RECORDER`` is up (``trace_enable`` / ``OMPI_TPU_TRACE``, the
  operator's path to a Perfetto JSON) it records into the ring;
- otherwise it hands back one shared no-op and constructs nothing.

:func:`instant` is the same call for a marker with no duration.
Spans of one API call carry the same ``call`` sequence number
(:func:`api_span` opens the call; every span inside it inherits it).
:func:`closed` feeds both sinks a span that someone else timed and
that has already ended (jax's compile events).

Hot-path contract (regression-tested): while both sinks are down — the
default — an instrumented site pays :func:`active` (one attribute
load, one branch, one ``TraceAnnotation.is_enabled()`` read) and
constructs nothing. Older host-plane sites still guard on
``recorder.RECORDER is None`` and feed the ring alone.

Clocks: spans carry ``time.monotonic_ns`` timestamps. At enable each
rank samples ``wall - monotonic`` (``clock_offset_ns``);
:func:`sync_clock` exchanges these through the runtime store (modex)
so every rank exports in rank 0's timebase (``clock_base_ns``) and
merged timelines line up without wall-clock-quality cross-host sync.
"""

from __future__ import annotations

import contextlib
import itertools
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional

from ompi_tpu.core import cvar, pvar
from ompi_tpu.telemetry import clock as _clock

_enable_var = cvar.register(
    "trace_enable", False, bool,
    help="Enable the span recorder at instance init (equivalently: "
         "any truthy OMPI_TPU_TRACE env value).", level=5)
_cap_var = cvar.register(
    "trace_buffer_spans", 65536, int,
    help="Span ring-buffer capacity; overflow overwrites the oldest "
         "span and counts in the trace_dropped pvar.", level=5)

#: THE disabled guard. Instrumented sites do
#: ``if recorder.RECORDER is not None: ...`` — module attribute load
#: plus one branch, nothing constructed on the None path.
RECORDER: Optional["Recorder"] = None


def now() -> int:
    return time.monotonic_ns()


class Span:
    """One closed region: [t0, t1) in monotonic ns."""

    __slots__ = ("name", "subsys", "t0", "t1", "args")

    def __init__(self, name: str, subsys: str, t0: int, t1: int,
                 args: Optional[Dict[str, Any]] = None) -> None:
        self.name = name
        self.subsys = subsys
        self.t0 = t0
        self.t1 = t1
        self.args = args

    def __repr__(self) -> str:
        return (f"Span({self.name}, {self.subsys}, "
                f"dur={self.t1 - self.t0}ns, {self.args})")


class Recorder:
    """Thread-safe bounded ring of spans (oldest overwritten)."""

    def __init__(self, capacity: Optional[int] = None,
                 rank: int = 0) -> None:
        cap = int(capacity if capacity is not None else _cap_var.get())
        self.capacity = max(1, cap)
        self._buf: List[Optional[Span]] = [None] * self.capacity
        self._head = 0
        self._n = 0
        self._lock = threading.Lock()
        self.rank = rank
        # bracketed wall-minus-monotonic at enable (telemetry/clock);
        # sync_clock rebases exports onto rank 0's offset
        self.clock_offset_ns, self.clock_err_ns = \
            _clock.sample_offset()
        self.clock_base_ns = self.clock_offset_ns
        self.clock_base_err_ns = self.clock_err_ns

    def record(self, name: str, subsys: str, t0: int, t1: int,
               args: Optional[Dict[str, Any]] = None) -> Span:
        sp = Span(name, subsys, t0, t1, args)
        with self._lock:
            if self._n == self.capacity:
                pvar.record("trace_dropped")
            else:
                self._n += 1
            self._buf[self._head] = sp
            self._head = (self._head + 1) % self.capacity
        return sp

    def instant(self, name: str, subsys: str,
                args: Optional[Dict[str, Any]] = None) -> Span:
        """Zero-duration marker (renders as a sliver in Perfetto)."""
        t = now()
        return self.record(name, subsys, t, t, args)

    def spans(self) -> List[Span]:
        """Chronological (completion-order) snapshot."""
        with self._lock:
            if self._n < self.capacity:
                out = self._buf[:self._n]
            else:
                out = self._buf[self._head:] + self._buf[:self._head]
            return list(out)

    def clear(self) -> None:
        with self._lock:
            self._buf = [None] * self.capacity
            self._head = 0
            self._n = 0


# -- the span source -----------------------------------------------------

#: prefix of every program span in a profiler trace
PREFIX = "ompi:"

_annotation = None  # jax.profiler.TraceAnnotation, once jax is loaded
_calls = itertools.count(1)
_call_of: Dict[int, int] = {}  # thread id -> the API call it is in
_thread_id = threading.get_ident


def _profiler_live() -> bool:
    """Is a jax.profiler session collecting? Until jax is imported
    none can be; from then on this name IS
    ``TraceAnnotation.is_enabled`` (a static C++ read)."""
    global _annotation, _profiler_live
    if "jax" not in sys.modules:
        return False
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:  # jax is half way through its own import
        return False
    _annotation = TraceAnnotation
    _profiler_live = TraceAnnotation.is_enabled
    return _profiler_live()


def active() -> bool:
    """THE guard of a site on the one span source: is a sink up?"""
    return RECORDER is not None or _profiler_live()


class _Off:
    """What :func:`span` hands back while no sink is up."""

    __slots__ = ()
    args = None  # where an open span has its arguments

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        pass


#: the one no-op span (what sites pass on when they skipped `span()`)
OFF = _Off()


class _Span:
    """One open region, fed to whichever sinks were up when it
    opened. Kept lean: with a profiler session live this runs on the
    path it measures (PERF.md gives the cost per span)."""

    __slots__ = ("name", "subsys", "args", "t0", "_ann", "_outer")

    def __init__(self, name: str, subsys: str, args: Dict[str, Any],
                 live: bool, opens_call: bool) -> None:
        self.name = name
        self.subsys = subsys
        self.args = args
        # False: no profiler session; True until __enter__ puts the
        # open TraceAnnotation here
        self._ann = live
        # the call this thread was in before an api span opened its
        # own (0: none); None for a span that opens no call
        self._outer = 0 if opens_call else None

    def __enter__(self):
        args = self.args
        if self._outer is None:
            call = _call_of.get(_thread_id())
        else:
            me = _thread_id()
            self._outer = _call_of.get(me, 0)
            call = _call_of[me] = next(_calls)
        if call is not None:
            args["call"] = call
        if self._ann:
            ann = self._ann = _annotation(
                PREFIX + self.subsys + "." + self.name, **args)
            ann.__enter__()
        self.t0 = now()
        return self

    def set(self, **args) -> None:
        """Arguments learned while the span is open."""
        self.args.update(args)
        if self._ann:
            self._ann.set_metadata(**args)

    def __exit__(self, etype, exc, tb):
        t1 = now()
        if etype is not None:
            self.set(error=etype.__name__)
        if self._ann:
            self._ann.__exit__(etype, exc, tb)
        rec = RECORDER
        if rec is not None:
            rec.record(self.name, self.subsys, self.t0, t1,
                       self.args or None)
        if self._outer is not None:
            if self._outer:
                _call_of[_thread_id()] = self._outer
            else:
                _call_of.pop(_thread_id(), None)
        return False


def span(name: str, subsys: str, **args):
    """``with span("launch", "coll_xla", program=p) as sp: ...`` —
    the one call (module docstring). ``sp.set(k=v)`` adds arguments
    learned inside. Sites whose arguments cost something to compute
    branch on :func:`active` first."""
    return _open(name, subsys, args, False)


def _open(name: str, subsys: str, args: Dict[str, Any], opens_call: bool):
    live = _profiler_live()
    if RECORDER is None and not live:
        return OFF
    return _Span(name, subsys, args, live, opens_call)


@contextlib.contextmanager
def timed(name: str, subsys: str, counter: str):
    """A span whose nanoseconds ALSO go into pvar ``counter``, sink or
    no sink: for regions that run once per job, so two clock reads
    cost nothing — the phases of ``mpi.Init()``, which end before a
    profiler session can exist."""
    t0 = now()
    try:
        with span(name, subsys):
            yield
    finally:
        pvar.record(counter, now() - t0)


def instant(name: str, subsys: str, **args) -> None:
    """A marker with no duration, on both sinks: the ring gets a span
    whose start is its end, a live profiler session
    ``ompi:<subsys>.<name>`` opened and closed at once. Like every
    span it carries the ``call`` of the API call it happened in, if
    any. Sites branch on :func:`active` first."""
    rec = RECORDER
    live = _profiler_live()
    if rec is None and not live:
        return
    call = _call_of.get(_thread_id())
    if call is not None:
        args["call"] = call
    if live:
        with _annotation(PREFIX + subsys + "." + name, **args):
            pass
    if rec is not None:
        rec.instant(name, subsys, args or None)


def api_span(name: str):
    """The span of one MPI API call: opens a new ``call`` sequence
    number that every span inside it carries."""
    return _open(name, "api", {}, True)


def closed(name: str, subsys: str, t0_wall_ns: int, t1_wall_ns: int,
           **args) -> None:
    """A span that has ALREADY ended, timed by someone else on the
    wall clock (jax's compile events, ``prof/compile.py``). The ring
    gets it moved onto its monotonic clock by the offset sampled at
    enable; a live profiler session gets ``ompi:<subsys>.<name>``
    opened and closed NOW, at the span's end (a ``TraceAnnotation``
    cannot be backdated), carrying ``dur_ns``. Like every span it
    carries the ``call`` of the API call it happened in, if any."""
    rec = RECORDER
    live = _profiler_live()
    if rec is None and not live:
        return
    call = _call_of.get(_thread_id())
    if call is not None:
        args["call"] = call
    if rec is not None:
        off = rec.clock_offset_ns
        rec.record(name, subsys, t0_wall_ns - off, t1_wall_ns - off,
                   args or None)
    if live:
        with _annotation(PREFIX + subsys + "." + name,
                         dur_ns=t1_wall_ns - t0_wall_ns, **args):
            pass


# -- log2 latency histogram (pvar-plane export) --------------------------

HIST_PREFIX = "trace_hist_"


def hist(op: str, nbytes: int, dur_ns: int) -> None:
    """One histogram sample: counter ``trace_hist_<op>_sz<s>_lat<l>``
    with s = bit_length(nbytes) and l = bit_length(dur_ns) — log2
    bins per (op, size-bin), readable via ``pvar.snapshot()`` /
    ``mpit`` sessions, decoded by ``trace.export.histograms``.
    Callers guard on ``RECORDER is not None``; this records
    unconditionally."""
    pvar.record("%s%s_sz%d_lat%d" % (
        HIST_PREFIX, op, int(nbytes).bit_length(),
        max(0, int(dur_ns)).bit_length()))


# -- enable / disable ----------------------------------------------------

def requested() -> bool:
    """cvar trace_enable (incl. OMPI_TPU_TRACE_ENABLE env) or the
    short-form OMPI_TPU_TRACE env knob."""
    if _enable_var.get():
        return True
    raw = os.environ.get("OMPI_TPU_TRACE", "").strip().lower()
    return raw not in ("", "0", "false", "no", "off")


def enable(capacity: Optional[int] = None,
           rank: Optional[int] = None) -> Recorder:
    """Turn the recorder on (idempotent). The MPI API's entry/exit
    spans (subsystem "api") come from the API table's own wrapper
    (``mpi._api_entry`` -> :func:`api_span`), like every other span."""
    global RECORDER
    if RECORDER is None:
        RECORDER = Recorder(capacity,
                            rank=0 if rank is None else rank)
    elif rank is not None:
        RECORDER.rank = rank
    return RECORDER


def disable() -> Optional[Recorder]:
    """Turn the recorder off; returns it (spans stay exportable)."""
    global RECORDER
    rec, RECORDER = RECORDER, None
    return rec


def sync_clock() -> None:
    """Exchange wall-vs-monotonic offsets through the runtime store
    so every rank exports in rank 0's monotonic timebase. All ranks
    must have tracing enabled (the env/cvar knobs are job-uniform by
    construction) — the modex read blocks until rank 0 publishes.
    The exchange itself is telemetry/clock.py's (shared with the
    skew plane's "skew_clock" sync)."""
    rec = RECORDER
    if rec is None:
        return
    from ompi_tpu.runtime import rte

    rec.rank = rte.rank
    rec.clock_base_ns, rec.clock_base_err_ns = _clock.sync_via_store(
        "trace_clock", rec.clock_offset_ns, rec.clock_err_ns)
