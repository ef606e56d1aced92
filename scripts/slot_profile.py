"""The blocking slot's host microseconds, once, by function (ROADMAP
S7.4): rank 0's small pass of the OSU cell under cProfile.

    python -m ompi_tpu.runtime.launcher -n 4 --mca device_plane on \
        --mca device_plane_platform tpu scripts/slot_profile.py \
        [--calls 300] [--bytes 1024] [--out chiprun_out/slot_profile.txt]

Every rank makes the same warm-up and the same `--calls` blocking
`comm.Allreduce(x)` + `block_until_ready`; rank 0 makes its timed
calls under cProfile and prints, per function of `ompi_tpu/` (and the
costliest builtins beneath them), calls per collective, OWN
microseconds per collective and cumulative ones. cProfile charges
every Python call about a microsecond of its own, so own times read
high by about the number of calls a function makes; the same loop is
timed unprofiled first, and both medians are printed. A builder's
tool: nothing here is permanent instrumentation.
"""

import argparse
import cProfile
import io
import pstats
import statistics
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=300)
    ap.add_argument("--bytes", type=int, default=1024)
    ap.add_argument("--out", default=None)
    ns = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from ompi_tpu import mpi

    comm = mpi.Init()
    x = jax.device_put(jnp.full((ns.bytes // 4,), float(comm.rank + 1),
                                jnp.float32))
    jax.block_until_ready(x)

    def one():
        t = time.perf_counter()
        jax.block_until_ready(comm.Allreduce(x))
        return time.perf_counter() - t

    for _ in range(50):
        one()
    comm.Barrier()
    plain = [one() for _ in range(ns.calls)]
    comm.Barrier()
    prof = cProfile.Profile()
    if comm.rank == 0:
        prof.enable()
    profiled = [one() for _ in range(ns.calls)]
    if comm.rank == 0:
        prof.disable()
    comm.Barrier()
    if comm.rank == 0:
        text = report(prof, ns.calls, plain, profiled)
        print(text, flush=True)
        if ns.out:
            with open(ns.out, "w") as f:
                f.write(text + "\n")
    mpi.Finalize()
    return 0


def report(prof, calls: int, plain, profiled) -> str:
    stats = pstats.Stats(prof, stream=io.StringIO()).stats
    rows = []
    for (path, line, fn), (_cc, n, own, cum, _callers) in stats.items():
        where = path[path.index("ompi_tpu/"):] if "ompi_tpu/" in path \
            else path.rsplit("/", 1)[-1]
        rows.append((own / calls * 1e6, cum / calls * 1e6, n / calls,
                     f"{where}:{line}" if line else where, fn))
    rows.sort(reverse=True)
    total = sum(r[0] for r in rows)
    out = [f"slot profile: {calls} blocking Allreduce calls on rank 0; "
           f"median iteration unprofiled "
           f"{statistics.median(plain) * 1e6:.1f} us, under cProfile "
           f"{statistics.median(profiled) * 1e6:.1f} us; own time of all "
           f"functions {total:.1f} us a call",
           f"{'own us':>9} {'cum us':>9} {'calls':>6}  function"]
    for own, cum, n, where, fn in rows[:45]:
        out.append(f"{own:9.2f} {cum:9.2f} {n:6.2f}  {fn}  ({where})")
    mine = sum(r[0] for r in rows if r[3].startswith("ompi_tpu/"))
    out.append(f"own time of ompi_tpu/ functions together: {mine:.1f} us "
               "a call")
    return "\n".join(out)


if __name__ == "__main__":
    raise SystemExit(main())
