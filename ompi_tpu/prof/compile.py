"""The compile ledger: what every program the job compiled cost, by
phase, from jax's own events.

jax reports through ``jax.monitoring`` — only while it traces, lowers
or compiles: a warm launch calls no listener — each phase of every
jitted function, with the function's name and a wall-clock pair:
``jaxpr_trace_duration`` (the function's Python), ``jaxpr_to_mlir_
module_duration`` (lowering to StableHLO) and ``backend_compile_
duration`` (XLA's compile, or the persistent cache's answer); and,
nameless but INSIDE the backend event on the same thread, the cache's
request, its hit and the retrieval's time. :func:`register` hooks them
(``prof.wire_compile_cache``: every job, always on, nothing to
configure). The rules of the account:

- a program is jax's ``fun_name`` with ``jit(...)`` taken off;
- of nested trace events on one thread only the OUTERMOST counts: a
  jitted function traced inside another's trace reports an event of
  its own, and its time is the outer's already;
- a backend event with a cache hit inside it is ``cache_load`` for
  the retrieval's time and ``backend`` for the rest; on a miss it is
  all ``backend`` (XLA's compile and the write of the entry);
- a program is the job's OWN when its name begins ``ompi_`` (the
  train step, ``coll/xla.program_name``, the set-up probes of
  ``models/transformer.py``); every other one — jax's eager helpers,
  a user's own functions — is ``foreign`` and counted apart.

Counters (pvars), own programs: ``compile_{trace,lower,backend,
cache_load}_ns``, ``compile_programs`` (those that reached the
backend), ``compile_cache_requests``, ``compile_cache_hits``; foreign:
``compile_foreign_ns``, ``compile_foreign_programs``; all programs:
``prof_compile_cache_{hits,misses}``. :func:`table` holds the same per
program, and each phase is span ``compile.<phase>`` on the one span
source (``trace/recorder.closed``) with ``program`` and, for the
backend's two, ``cache``.
"""

from __future__ import annotations

import threading
from typing import Dict, List

from ompi_tpu.core import pvar
from ompi_tpu.trace import recorder as _trace

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_PHASE_OF = {
    _TRACE: "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_HIT = "/jax/compilation_cache/cache_hits"
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"

PHASES = ("trace", "lower", "backend", "cache_load")
#: names the table keeps; the programs after them share one row
MAX_PROGRAMS = 256
OTHER = "other"


def program_of(fun_name: str) -> str:
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


def own(program: str) -> bool:
    return program.startswith("ompi_")


class Ledger:
    """The four listeners and the table they fill. One per process
    (:data:`LEDGER`); a test feeds its own."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._rows: Dict[str, dict] = {}  # first-seen order
        # per thread: traces open on it, and what the cache answered
        # inside the backend event it is in
        self._here = threading.local()

    # -- jax.monitoring listeners ---------------------------------------
    def on_scalar(self, event: str, value: float, **kw) -> None:
        """jax reports a phase's START as a scalar."""
        if event == _TRACE:
            self._here.depth = getattr(self._here, "depth", 0) + 1

    def on_event(self, event: str, **kw) -> None:
        if event == _REQUEST:
            self._here.cache, self._here.load_ns = "miss", 0
        elif event == _HIT:
            self._here.cache = "hit"

    def on_duration(self, event: str, secs: float, **kw) -> None:
        if event == _RETRIEVAL:
            self._here.load_ns = int(secs * 1e9)

    def on_span(self, event: str, start: float, end: float,
                fun_name: str = "?", **kw) -> None:
        phase = _PHASE_OF.get(event)
        if phase is None:
            return
        here = self._here
        if phase == "trace":
            here.depth = depth = max(getattr(here, "depth", 1) - 1, 0)
            if depth:  # inside another function's trace
                return
        program = program_of(fun_name)
        t0, t1 = int(start * 1e9), int(end * 1e9)
        if phase != "backend":
            self._add(program, phase, t0, t1)
            return
        cache = getattr(here, "cache", None)  # None: no request made
        load = min(getattr(here, "load_ns", 0), t1 - t0)
        here.cache, here.load_ns = None, 0
        hit = cache == "hit"
        if cache:
            pvar.record("prof_compile_cache_hits" if hit
                        else "prof_compile_cache_misses")
        if own(program):
            pvar.record("compile_programs")
            if cache:
                pvar.record("compile_cache_requests")
            if hit:
                pvar.record("compile_cache_hits")
        else:
            pvar.record("compile_foreign_programs")
        args = {"cache": cache} if cache else {}
        with self._lock:
            row = self._row(program)
            row["hits"] += hit
            row["misses"] += cache == "miss"
        self._add(program, "backend", t0, t1 - load, **args)
        if hit:
            self._add(program, "cache_load", t1 - load, t1, **args)

    # -- the account -----------------------------------------------------
    def _row(self, program: str) -> dict:
        row = self._rows.get(program)
        if row is None:
            if len(self._rows) >= MAX_PROGRAMS and program != OTHER:
                return self._row(OTHER)
            row = self._rows[program] = {
                "program": program, "own": own(program),
                "ns": dict.fromkeys(PHASES, 0),
                "runs": dict.fromkeys(PHASES, 0),
                "hits": 0, "misses": 0}
        return row

    def _add(self, program: str, phase: str, t0: int, t1: int,
             **args) -> None:
        with self._lock:
            row = self._row(program)
            row["ns"][phase] += t1 - t0
            row["runs"][phase] += 1
        pvar.record("compile_%s_ns" % phase if own(program)
                    else "compile_foreign_ns", t1 - t0)
        _trace.closed(phase, "compile", t0, t1, program=program, **args)

    def table(self) -> List[dict]:
        """One row per program in first-seen order: ``program``,
        ``own``, ``ns`` and ``runs`` by phase, cache ``hits`` and
        ``misses``; past :data:`MAX_PROGRAMS` names, row ``other``."""
        with self._lock:
            return [dict(r, ns=dict(r["ns"]), runs=dict(r["runs"]))
                    for r in self._rows.values()]


LEDGER = Ledger()


def table() -> List[dict]:
    return LEDGER.table()


def register(monitoring) -> None:
    """Hook :data:`LEDGER` to ``jax.monitoring`` (handed in: this
    module never imports jax). Call once."""
    monitoring.register_event_listener(LEDGER.on_event)
    monitoring.register_event_duration_secs_listener(LEDGER.on_duration)
    monitoring.register_event_time_span_listener(LEDGER.on_span)
    monitoring.register_scalar_listener(LEDGER.on_scalar)
