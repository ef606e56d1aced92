"""Multi-rank test harness.

Reference analog: the test strategy of SURVEY.md §4 — no mock network;
N real processes on localhost over self+sm+tcp stand in for a cluster
(the mpi4py-suite-under-mpiexec pattern of the reference CI).

Pooling (r2 VERDICT weak #7): most bodies run in PERSISTENT rank
pools keyed by (n, mca) — one process group executes many test bodies
(the reference CI batches its mpi4py suite under one mpiexec the same
way), cutting per-test process-spawn/import cost. Bodies that need
process isolation (FT/SIGKILL injection, custom preludes, sys/process
state mutation) run isolated, auto-detected or via isolate=True. A
body failure poisons its pool (peers may be desynchronized mid-
collective), so pools are only ever reused across clean runs.
"""

from __future__ import annotations

import atexit
import os
import subprocess
import sys
import tempfile
import textwrap
import time
import uuid
from typing import Dict, List, Optional, Tuple

from ompi_tpu.runtime import kvstore, launcher

_POOL_CAP = 4  # live pools (LRU evicted); each is n live processes


class _Pool:
    """One persistent n-rank job executing bodies via pool_worker."""

    def __init__(self, n: int, mca: Dict[str, str]) -> None:
        self.n = n
        self.store = kvstore.Store().start()
        self.jobid = uuid.uuid4().hex[:12]
        self.store.seed_counter(f"ww:{self.jobid}", n)
        self.client = kvstore.Client(self.store.addr)
        worker = os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "pool_worker.py")
        self.procs: List[subprocess.Popen] = []
        for r in range(n):
            env = launcher.build_env(r, n, self.store.addr, self.jobid,
                                     mca)
            self.procs.append(subprocess.Popen(
                [sys.executable, worker], env=env))
        self.i = 0
        self.alive = True

    def run(self, body: str, timeout: float) -> Tuple[bool, list]:
        """(ok, errors). Not ok => the pool is poisoned and killed."""
        idx = self.i
        self.i += 1
        self.client.put(f"pool:{self.jobid}:task:{idx}", body)
        deadline = time.monotonic() + timeout
        results: Dict[int, tuple] = {}
        grace_started = None
        while len(results) < self.n:
            for r in range(self.n):
                if r in results:
                    continue
                res = self.client.get(
                    f"pool:{self.jobid}:res:{idx}:{r}", wait=False)
                if res is not None:
                    results[r] = res
            if len(results) < self.n:
                if any(p.poll() is not None for p in self.procs):
                    results["dead"] = ("err", "pool rank died")
                    break
                now = time.monotonic()
                if any(r[0] == "err" for r in results.values()):
                    # one rank failed: give the others a short grace
                    # to fail/finish too, then declare the pool toast
                    if grace_started is None:
                        grace_started = now
                    elif now - grace_started > 5.0:
                        break
                if now > deadline:
                    results["timeout"] = ("err",
                                          f"pool body timeout {timeout}s")
                    break
                time.sleep(0.005)
        errors = [f"rank {r}: {msg}" for r, (st, msg) in
                  sorted(results.items(), key=str) if st == "err"]
        missing = [r for r in range(self.n) if r not in results]
        if missing:
            errors.append(f"no result from ranks {missing}")
        ok = not errors
        if not ok:
            self.kill()
        return ok, errors

    def shutdown(self) -> None:
        if not self.alive:
            return
        try:
            self.client.put(f"pool:{self.jobid}:task:{self.i}",
                            "__POOL_SHUTDOWN__")
            for p in self.procs:
                p.wait(timeout=10)
        except Exception:  # noqa: BLE001 — fall through to kill
            pass
        self.kill()

    def kill(self) -> None:
        self.alive = False
        launcher.reap(self.procs)
        launcher.cleanup_shm(self.jobid)
        self.store.stop()


_pools: Dict[tuple, _Pool] = {}


def _pool_for(n: int, mca: Dict[str, str]) -> _Pool:
    key = (n, tuple(sorted(mca.items())))
    pool = _pools.get(key)
    if pool is not None and not pool.alive:
        _pools.pop(key, None)
        pool = None
    if pool is None:
        while len([p for p in _pools.values() if p.alive]) >= _POOL_CAP:
            # LRU: dicts preserve insertion order; evict the oldest
            old_key = next(iter(_pools))
            _pools.pop(old_key).shutdown()
        pool = _pools[key] = _Pool(n, mca)
    else:  # refresh LRU position
        _pools.pop(key)
        _pools[key] = pool
    return pool


@atexit.register
def _shutdown_pools() -> None:
    for pool in list(_pools.values()):
        pool.shutdown()
    _pools.clear()


def _must_isolate(body: str, mca: Dict[str, str]) -> bool:
    """Bodies that mutate process-wide state or kill ranks cannot
    share a pool."""
    if mca.get("ft", "0") not in ("0", "false", ""):
        return True
    needles = ("os.kill", "SIGKILL", "SIGTERM", "os._exit",
               "mpi.Finalize", "Comm_spawn", "spawn(")
    return any(s in body for s in needles)

_PRELUDE = """
# NOTE: no jax import or platform pinning here — the launcher already
# pins rank processes to JAX_PLATFORMS=cpu (launcher.build_env; the
# device_plane_platform cvar defaults to cpu), and importing jax costs
# ~2s per rank per test; bodies that need jax import it themselves.
import numpy as np
from ompi_tpu import mpi
comm = mpi.Init()
rank, size = comm.rank, comm.size
"""

_EPILOGUE = """
mpi.Finalize()
"""


def _run_script(launch_fn, body: str, prelude: bool) -> None:
    src = (_PRELUDE if prelude else "") + textwrap.dedent(body) \
        + (_EPILOGUE if prelude else "")
    with tempfile.NamedTemporaryFile(
            "w", suffix=".py", delete=False) as fh:
        fh.write(src)
        path = fh.name
    try:
        rc = launch_fn([sys.executable, path])
        assert rc == 0, f"ranks exited with {rc}\n--- script ---\n{src}"
    finally:
        os.unlink(path)


def run_ranks(body: str, n: int, mca: Optional[Dict[str, str]] = None,
              timeout: float = 120, prelude: bool = True,
              isolate: bool = False) -> None:
    """Run `body` (indented python) in n ranks; assert all succeed.

    Default: pooled execution in a persistent (n, mca) rank pool.
    isolate=True (or auto-detected process-state mutation / no
    prelude) spawns a fresh process group, exactly as before."""
    mca = dict(mca or {})
    src = textwrap.dedent(body)
    if prelude and not isolate and not _must_isolate(src, mca):
        ok, errors = _pool_for(n, mca).run(src, timeout)
        assert ok, ("pooled ranks failed:\n" + "\n".join(errors)
                    + f"\n--- body ---\n{src}")
        return
    _run_script(
        lambda argv: launcher.launch(argv, n, mca=mca, timeout=timeout),
        body, prelude)


def run_hosts(body: str, hosts, mca: Optional[Dict[str, str]] = None,
              timeout: float = 180, prelude: bool = True) -> None:
    """Run `body` across launcher.HostSpec's via local daemons (the
    fake-multi-host lane: per-host hostnames + loopback addresses)."""
    _run_script(
        lambda argv: launcher.launch_hosts(argv, hosts, mca=mca,
                                           timeout=timeout,
                                           agent="local"),
        body, prelude)
