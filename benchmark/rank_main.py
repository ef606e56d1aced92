"""One rank of a benchmark run (started by run.py under the program's
launcher; never run by hand).

It brings the program up the way a job does (`mpi.Init()`), refuses any
platform or chip count but the cell's, hands the cell to its runner
(found by the name in the workload file), reduces the trace, asks each
per-layer metric's reader (found by the metric's name in
BENCHMARK.json) for its number, and prints the result for run.py.
"""

from __future__ import annotations

import argparse
import contextlib
import glob
import importlib
import json
import os
import sys
import time

T_RANK_START = time.time()

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import compare, manifest as mf, trace_reduce  # noqa: E402
from benchmark.common import (RESULT_TAG, device_facts, peaks,  # noqa: E402
                              say)


class Tracer:
    """jax.profiler around part of the window, on the rank that asks.
    `window(name)` marks a stretch the reduction measures busy and idle
    time over; `span(name)` says what the host is doing, so that idle
    gaps can be named. Both are no-ops while no trace is being taken."""

    def __init__(self, out_dir: str, on: bool) -> None:
        self.dir = os.path.join(out_dir, "trace")
        self.on = on
        self.taken = False
        self._live = False

    def start(self) -> None:
        import jax

        if self.on and not self.taken:
            # the Python tracer would add an event per Python call to
            # the very host path that is being measured
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=options)
            self._live = True

    def stop(self) -> None:
        import jax

        if self._live:
            jax.profiler.stop_trace()
            self._live = False
        self.taken = True

    def window(self, name: str):
        return self._annotate(trace_reduce.WINDOW + name)

    def span(self, name: str):
        return self._annotate(trace_reduce.SPAN + name)

    def _annotate(self, name: str):
        import jax

        if not self._live:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(name)

    def file(self):
        found = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None


class Run:
    """What a runner is handed."""

    def __init__(self, ns, comm) -> None:
        self.manifest = mf.load()
        self.rehearsal = bool(ns.rehearsal)
        (self.cell, self.workload, self.traffic, self.config,
         self.limits) = mf.cell_inputs(self.manifest, ns.workload,
                                       self.rehearsal)
        self.seed, self.seconds = ns.seed, ns.seconds
        self.trace = bool(ns.trace)
        self.comm = comm
        self.out_dir = ns.out
        self.tracer = Tracer(ns.out, self.trace and comm.rank == 0)
        self.t_parent = ns.t0
        self.setup_s = None
        self.peaks = None

    def window_opens(self) -> None:
        """Set-up ends here: parent process start -> first iteration
        of the timed window."""
        self.setup_s = time.time() - self.t_parent


def layer_metrics(ctx, out: dict, reduced) -> dict:
    """Ask each per-layer metric's reader; one that finds nothing to
    read returns None and the metric is left out of the line."""
    values = {}
    for name, entry in mf.metrics_for(ctx.manifest, ctx.cell["name"],
                                      1).items():
        reader = importlib.import_module(
            "benchmark.layer_metrics." + mf.reader_name(name))
        got = reader.read({
            "spans": out["spans"], "counters": out["counters"],
            "facts": out["facts"], "trace": reduced,
            "peaks": ctx.peaks, "ranks": ctx.comm.size})
        if got is not None:
            values[name] = {"value": got, "unit": entry["unit"]}
    return values


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rehearsal", type=int, default=0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--t0", type=float, required=True)
    ns = ap.parse_args()

    from ompi_tpu import mpi

    comm = mpi.Init()
    init_s = time.time() - T_RANK_START
    ctx = Run(ns, comm)
    platform = "cpu" if ctx.rehearsal else "tpu"
    device = device_facts(platform, ctx.cell["chips"]
                          if not ctx.rehearsal else comm.size)
    if comm.size != ctx.workload["ranks"]:
        raise RuntimeError(f"{comm.size} ranks, the cell wants "
                           f"{ctx.workload['ranks']}")
    if not ctx.rehearsal:
        ctx.peaks = peaks(device["kind"])
    say(f"rank {comm.rank}: mpi.Init() returned {init_s:.2f}s after rank "
        f"start; {device}")

    runner = importlib.import_module(
        "benchmark.runners." + ctx.workload["runner"])
    out = runner.run(ctx)
    if ctx.tracer._live:
        ctx.tracer.stop()
    # every rank's seconds from its own start to mpi.Init()'s return
    inits = comm.gather(init_s, root=0)
    if comm.rank == 0:
        out["spans"]["init_s"] = max(inits)
        report(ctx, out, device)
    mpi.Finalize()
    return 0


def report(ctx, out: dict, device: dict) -> None:
    correct = compare.verdict(out["checks"], say)
    say(f"spans {json.dumps(out['spans'])}")
    say(f"counters {json.dumps(out['counters'])}")
    device = dict(device, memory_peak_bytes=int(out["memory_peak_bytes"]))
    result = {"correct": bool(correct), "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": {}, "device": device}
    end_to_end = dict(out["end_to_end"], setup_s=ctx.setup_s)
    if ctx.rehearsal:
        # a CPU run proves counts, never a time or a rate: the numbers
        # go on this line under no metric's name
        say(f"REHEARSAL counts: attempted {out['attempted']} failed "
            f"{out['failed']} facts {json.dumps(out['facts'])}")
        if ctx.trace:  # the reduction's path, on a trace with no chip
            reduced = trace_reduce.reduce_file(ctx.tracer.file())
            say(f"REHEARSAL trace windows {sorted(reduced['windows'])}")
    elif ctx.trace:
        path = ctx.tracer.file()
        if path is None:
            raise RuntimeError("--trace 1 but no .xplane.pb was written")
        reduced = trace_reduce.reduce_file(path)
        say(f"trace {path}: windows "
            f"{ {k: round(v['window_s'], 4) for k, v in reduced['windows'].items()} }")
        result["metrics"] = layer_metrics(ctx, out, reduced)
        result["device"].update(
            busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result["breakdown"] = reduced["breakdown"]
        with open(os.path.join(ctx.out_dir, "trace_reduced.json"),
                  "w") as f:
            json.dump(reduced, f, indent=1)
    else:
        for name, entry in mf.metrics_for(
                ctx.manifest, ctx.cell["name"], 0).items():
            result["metrics"][name] = {"value": end_to_end[name],
                                       "unit": entry["unit"]}
    say(f"result printed {time.time() - ctx.t_parent:.1f}s after the "
        "parent started")
    say(RESULT_TAG + json.dumps(result))


if __name__ == "__main__":
    sys.exit(main())
