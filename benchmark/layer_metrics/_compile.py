"""What the PROGRAM says of its own compiles: the always-on compile
ledger of `ompi_tpu/prof/compile.py`, fed by jax's own events. The
five `compile_*` readers beside this file take their numbers from here.
The job's OWN programs are those named `ompi_*` (the train step, the
collectives, the set-up probes); every other one — the weights' and the
plain reference's, jax's eager helpers — is `foreign`, printed and in
no metric. A program that keeps no ledger (a parent commit) makes every
reader return None, and none raises."""

from __future__ import annotations

from typing import List, Optional

from benchmark.common import say
from benchmark.layer_metrics import _program


def seconds(name: str) -> Optional[float]:
    """Counter `name` in seconds. In a program that keeps the ledger
    (some program of its own reached the backend) a phase that took no
    time reads 0.0, not None: a cold run loads nothing from the cache,
    a warm one compiles nothing."""
    if _program.counter("compile_programs") is None:
        return None
    return (_program.counter(name) or 0) / 1e9


def table() -> Optional[List[dict]]:
    """The program's per-program rows, None where it keeps none."""
    try:
        from ompi_tpu import prof
    except ImportError:
        return None
    rows = getattr(prof, "compile_table", None)
    return None if rows is None else rows()


def say_table(rows: List[dict]) -> None:
    """One information line per program of the job's own, one for all
    the others."""
    for r in rows:
        if r["own"]:
            say(f"program: compile {r['program']}: " + ", ".join(
                f"{ph} {ns / 1e9:.3f} s x{r['runs'][ph]}"
                for ph, ns in r["ns"].items())
                + f"; cache {r['hits']} hit(s) {r['misses']} miss(es) "
                "(information)")
    foreign = sorted((r for r in rows if not r["own"]),
                     key=lambda r: -sum(r["ns"].values()))
    if foreign:
        say(f"program: compile foreign: {len(foreign)} program(s), "
            f"{sum(sum(r['ns'].values()) for r in foreign) / 1e9:.3f} s "
            "in no metric; most: " + ", ".join(
                f"{r['program']} {sum(r['ns'].values()) / 1e9:.3f} s"
                for r in foreign[:6]) + " (information)")
