"""ompi_tpu.prof — wall-clock attribution profiler.

Sixth observability component (after events, monitoring, profile,
trace, telemetry): answers "where did the wall go" for the ingest
plane. Three sub-planes, all riding the existing substrate:

- the **phase ledger** (:mod:`ompi_tpu.prof.ledger`): ``staging`` /
  ``compile`` / ``train`` / ``teardown`` phases as nestable spans +
  ``prof_phase_*_ns`` pvars;
- **transfer instrumentation**: h2d/d2h copy spans with bytes,
  bandwidth gauges and log2 size/latency histograms, emitted by the
  accelerator and ``_Ctx.to_global`` staging sites;
- the **compile ledger** (:mod:`ompi_tpu.prof.compile`), ALWAYS ON:
  every program the job compiles — the train step, coll/xla's
  collectives, the set-up probes, and apart from them whatever else
  jax compiles — split into trace, lowering, XLA compile and load
  from the persistent cache by jax's own events: pvars
  ``compile_{trace,lower,backend,cache_load}_ns``,
  ``compile_programs``, ``compile_cache_{requests,hits}``,
  ``compile_foreign_{ns,programs}``; :func:`compile_table` per
  program; spans ``compile.<phase>`` for ``python -m ompi_tpu.prof
  report``'s ``compile`` section. :func:`wire_compile_cache` places
  jax's persistent compilation cache and hooks the ledger
  (``prof_compile_cache_{hits,misses}`` count every program's
  requests).

The phase ledger and the transfer spans wait for ``--mca prof_enable
1`` (or ``OMPI_TPU_PROF=1``); off by default at the usual one-branch
cost per instrumented site. The compile ledger's listeners run only
when jax traces, lowers or compiles.
"""

from __future__ import annotations

import os
import sys

from ompi_tpu import errors
from ompi_tpu.core import cvar
from ompi_tpu.prof import compile as _compile
from ompi_tpu.prof.compile import table as compile_table  # noqa: F401
from ompi_tpu.prof.ledger import (  # noqa: F401  (public re-exports)
    PROFILER, Profiler, current_phase, disable, enable,
    overlap_seconds, phase, phase_seconds, requested,
)

_cache_dir_var = cvar.register(
    "compile_cache_dir", "", str,
    help="Directory for jax's persistent XLA compilation cache, used "
         "when the environment does not place it "
         "(JAX_COMPILATION_CACHE_DIR wins); empty [default] = "
         "<checkout>/.jax_cache. prof_compile_cache_{hits,misses} "
         "let repeat jobs prove the cold compile was skipped.",
    level=4)
_cache_min_var = cvar.register(
    "compile_cache_min_secs", -1.0, float,
    help="Override jax_persistent_cache_min_compile_time_secs "
         "(negative: leave jax's default, which skips persisting "
         "sub-second compiles — lower it to cache tiny CPU programs).",
    level=7)

_CACHE_WIRED = False

#: where the cache goes when neither the environment nor the cvar
#: places it: a FIXED path beside the package (the path is part of
#: jax's cache key — a directory that moves never hits)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def wire_compile_cache() -> str:
    """Place jax's persistent compilation cache and hook the compile
    ledger (prof/compile.py); returns the directory. Called from
    runtime init, before anything compiles, so every rank of every
    job shares it.

    Placement: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets
    it — jax reads that itself, so nothing is configured in code and
    whoever runs the job owns the location; else the
    ``compile_cache_dir`` cvar; else :data:`DEFAULT_CACHE_DIR`. A
    directory that cannot be created raises ``MPIError``.

    Never imports jax (2 s a rank that host-only jobs must not pay):
    while jax is not loaded the placement is left in the environment
    for its import to read, and the accounting waits for the next
    call — the accelerator component repeats it on its lazy init."""
    global _CACHE_WIRED
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    d = (from_env or str(_cache_dir_var.get() or "").strip()
         or DEFAULT_CACHE_DIR)
    try:
        os.makedirs(d, exist_ok=True)
    except OSError as exc:
        raise errors.MPIError(
            errors.ERR_OTHER,
            f"persistent compile cache: cannot create {d!r} ({exc}); "
            "set JAX_COMPILATION_CACHE_DIR or --mca compile_cache_dir "
            "to a writable directory") from exc
    jax = sys.modules.get("jax")
    if jax is None:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = d
        return d
    if not from_env:
        jax.config.update("jax_compilation_cache_dir", d)
    if not _CACHE_WIRED:
        min_secs = float(_cache_min_var.get())
        if min_secs >= 0:
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", min_secs)
        _compile.register(jax.monitoring)
        _CACHE_WIRED = True
    return d
