"""The benchmark's one command.

    python benchmark/run.py --workload CELL --seed N --seconds S --trace 0|1

It knows no cell, configuration or metric by name: the cell's entry in
BENCHMARK.json names its configuration, `workloads/CELL.json` names its
runner and traffic, and each per-layer metric names its reader. This
process never imports jax (a parent that touched jax would hold the
chip): it starts the program's launcher with one rank per chip, echoes
what the ranks print, and prints rank 0's result as its own last line.

`--rehearsal 1` is the CPU rehearsal: the same path at the toy widths
of `configs/<config>.rehearsal.json` with `device_plane_platform cpu`;
it prints counts and a result line whose device is the CPU and whose
`metrics` are empty — a CPU run proves results and counts, never a
time or a rate.

Exit code: 0 with a result line (whatever `correct` says); non-zero and
no result where no run could be made — no accelerator, fewer chips than
the cell asks for, or a checkout without the program.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time

T0 = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import manifest as mf  # noqa: E402
from benchmark.common import RESULT_TAG  # noqa: E402

#: a run has 360 s, a checkout's first run of a cell (it compiles) 1200
DEADLINE_S = 1100


def child_env() -> dict:
    env = dict(os.environ)
    # the cell, not the calling shell, names the platform
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    # persist every compiled program, also those that took under a
    # second, so that a checkout's second run compiles nothing
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    # libtpu would log under /tmp; nothing is written outside the
    # checkout, HOME, XDG_CACHE_HOME and TMPDIR
    env.setdefault("TPU_LOG_DIR", "disabled")
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    return env


def launcher_argv(ns, workload: dict, out_dir: str) -> list:
    platform = "cpu" if ns.rehearsal else "tpu"
    return [sys.executable, "-m", "ompi_tpu.runtime.launcher",
            "-n", str(workload["ranks"]),
            "--timeout", str(DEADLINE_S - 20),
            "--mca", "device_plane", "on",
            "--mca", "device_plane_platform", platform,
            os.path.join(HERE, "rank_main.py"),
            "--workload", ns.workload, "--seed", str(ns.seed),
            "--seconds", str(ns.seconds), "--trace", str(ns.trace),
            "--rehearsal", str(ns.rehearsal), "--out", out_dir,
            "--t0", repr(T0)]


def run_ranks(argv: list, env: dict):
    """Run the job to the end of every process it started. Returns
    (exit code, result or None); everything else the ranks print is
    echoed as it comes."""
    proc = subprocess.Popen(
        argv, env=env, cwd=ROOT, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, start_new_session=True)
    killer = threading.Timer(DEADLINE_S, _kill_group, (proc,))
    killer.daemon = True
    killer.start()
    result = None
    try:
        for line in proc.stdout:
            if line.startswith(RESULT_TAG):
                result = json.loads(line[len(RESULT_TAG):])
            else:
                sys.stdout.write(line)
                sys.stdout.flush()
        rc = proc.wait()
    finally:
        killer.cancel()
        _kill_group(proc)  # no straggler keeps a chip
        proc.wait()
    return rc, result


def _kill_group(proc) -> None:
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", type=int, choices=(0, 1), default=0)
    ns = ap.parse_args(argv)

    if not os.path.isfile(os.path.join(ROOT, "ompi_tpu", "runtime",
                                       "launcher.py")):
        print("no program to measure here: this checkout lacks "
              "ompi_tpu/ (BENCHMARK.json and benchmark/ alone are not "
              "a run)", file=sys.stderr)
        return 2
    manifest = mf.load()
    cell = mf.cell(manifest, ns.workload)
    workload = mf.workload_file(ns.workload)
    out_dir = os.path.join(ROOT, "chiprun_out", "benchmark",
                           ("rehearsal-" if ns.rehearsal else "")
                           + cell["name"])
    os.makedirs(out_dir, exist_ok=True)
    print(f"cell {cell['name']}: configuration {cell['config']}, "
          f"{cell['chips']} chip(s), {workload['ranks']} rank(s), runner "
          f"{workload['runner']}, seed {ns.seed}, {ns.seconds} s, trace "
          f"{ns.trace}{', CPU REHEARSAL' if ns.rehearsal else ''}",
          flush=True)
    rc, result = run_ranks(launcher_argv(ns, workload, out_dir),
                           child_env())
    if rc != 0 or result is None:
        print(f"no result: the job exited {rc}"
              + ("" if result is None else " after printing one"),
              file=sys.stderr)
        return rc or 1
    print(f"job over {time.time() - T0:.1f}s after the parent started",
          flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
