"""Runner `osu_collective`: an OSU-style sweep of one blocking
collective on device-resident buffers, one rank per chip.

As osu_allreduce does: per message size, a barrier, warm-up
iterations, then a timed loop; latency per size. The collective is
data: the configuration names it (`collective`) and
`benchmark/collectives/<name>.py` holds its call, its bus-byte rule,
its seeded inputs and its comparison with the plain reference, so a
Bcast or an Alltoall cell adds files and edits none. The window is cut
into one slice per size in the file's order, each with the share of
the window the file gives it (`slice_shares`: the sizes that feed an
end-to-end metric get most of it). An iteration's time is, per
iteration, the MAXIMUM over the ranks of the host clock around the
blocking call and `block_until_ready` of its result: what a
bulk-synchronous caller waits for.

`coll_busbw` is a rate, so it is taken over ALL the work and time of
the large size's slice: bus bytes x iterations / the sum of those
iterations' times. An iteration that stalls moves it. The median-based
figure goes on an earlier line.

The ranks must make the same number of calls, so none of them stops on
its own clock: every `block` iterations rank 0 looks at the time and
broadcasts over the host plane whether to go on (between two timed
iterations, never inside one). `block` is sized from the warm-up so
that a block lasts about `block_seconds`.
"""

from __future__ import annotations

import importlib
import json
import os
import time

import numpy as np

from benchmark.common import compile_requests, memory_stats, say

#: parts a size's samples are cut into, in time order, to tell the
#: scatter of sampling (parts of one run differ) from that of the
#: launch (runs differ, their parts agree)
PARTS = 4


def _timed(call, x):
    import jax

    t = time.perf_counter()
    y = call(x)
    jax.block_until_ready(y)
    return time.perf_counter() - t, y


def _slice(comm, call, x, seconds: float, block: int):
    """One size's share of the window: a barrier, then blocks of timed
    iterations until rank 0 says stop. Returns this rank's samples."""
    samples, y = [], None
    comm.Barrier()
    t_end = time.perf_counter() + seconds
    go = True
    while go:
        for _ in range(block):
            dt, y = _timed(call, x)
            samples.append(dt)
        go = comm.bcast(time.perf_counter() < t_end
                        if comm.rank == 0 else None, root=0)
    return samples, y


def _traced_pass(ctx, call, x, name: str, iters: int):
    """A fixed number of iterations under the profiler on rank 0."""
    import jax

    tr = ctx.tracer
    ctx.comm.Barrier()
    with tr.window(name):
        for _ in range(iters):
            with tr.span("collective call"):
                y = call(x)
            with tr.span("wait for result"):
                jax.block_until_ready(y)


def run(ctx) -> dict:
    import jax

    comm, traffic = ctx.comm, ctx.traffic
    rank, n = comm.rank, comm.size
    name = ctx.config["collective"]
    coll = importlib.import_module("benchmark.collectives." + name)
    call = lambda x: coll.call(comm, x)  # noqa: E731
    sizes = list(traffic["sizes_bytes"])
    shares = traffic["slice_shares"]
    if len(shares) != len(sizes):
        raise ValueError(f"{len(sizes)} sizes, {len(shares)} slice_shares")
    small, large = traffic["small_bytes"], traffic["large_bytes"]
    dtype = ctx.config["dtype"]
    itemsize = np.dtype(dtype).itemsize
    spans, counters = {}, {}

    from ompi_tpu.core import pvar

    pv = pvar.session()

    # -- set-up: seeded inputs on the device, every size warmed ------
    t = time.perf_counter()
    xs = {s: coll.rank_input(ctx.seed, i, rank, s // itemsize, dtype)
          for i, s in enumerate(sizes)}
    jax.block_until_ready(list(xs.values()))
    spans["buffers_s"] = time.perf_counter() - t

    requests = compile_requests()
    t = time.perf_counter()
    blocks = {}
    for s in sizes:
        comm.Barrier()
        _timed(call, xs[s])  # compiles
        est = min(_timed(call, xs[s])[0] for _ in range(traffic["warmup"]))
        est = comm.bcast(est, root=0)
        blocks[s] = max(1, min(traffic["block_max"],
                               int(traffic["block_seconds"] / est)))
    spans["compile_s"] = time.perf_counter() - t
    counters["compile_requests_setup"] = requests[0]
    if rank == 0:
        say(f"{name} {dtype} on {n} ranks; sizes {sizes}; iterations "
            f"per block {blocks}")

    # -- the window ---------------------------------------------------
    window_requests = requests[0]
    samples, results = {}, {}
    t0 = time.perf_counter()
    ctx.window_opens()
    for s, share in zip(sizes, shares):
        samples[s], results[s] = _slice(
            comm, call, xs[s], ctx.seconds * share / sum(shares), blocks[s])
    window_s = time.perf_counter() - t0
    counters["compiles_in_window"] = requests[0] - window_requests
    stats = memory_stats()

    if ctx.trace:
        tr = ctx.tracer
        results.pop(large, None)  # a GiB freed for the traced pass;
        # the answer that is checked is made again below
        tr.start()
        _traced_pass(ctx, call, xs[small], "small", traffic["trace_small"])
        _traced_pass(ctx, call, xs[large], "large", traffic["trace_large"])
        comm.Barrier()
        tr.stop()
        results[large] = call(xs[large])

    # -- after the window: counters, samples to rank 0 ---------------
    t = time.perf_counter()
    counters["coll_xla_launches"] = int(pv.read("coll_xla_launches"))
    counters["coll_accelerator_staged"] = int(
        pv.read("coll_accelerator_staged"))
    counters["pallas_fallthrough"] = int(pv.read("pallas_fallthrough"))
    mine = {s: np.asarray(v, np.float64) for s, v in samples.items()}
    everyone = comm.gather(mine, root=0)
    peaks_mem = comm.gather(stats.get("peak_bytes_in_use", 0), root=0)

    spans["gather_s"] = time.perf_counter() - t

    # -- correctness: every size's result against the plain reference
    t = time.perf_counter()
    checks = coll.checks(comm, xs, results, ctx.seed, sizes, dtype,
                         traffic, ctx.limits)
    staged = sum(comm.allgather(counters["coll_accelerator_staged"]))
    checks.append(("coll_accelerator_staged", staged, 0))
    spans["check_s"] = time.perf_counter() - t

    if rank != 0:
        return {"checks": checks, "spans": spans, "counters": counters}

    iters = {s: np.max(np.stack([r[s] for r in everyone]), axis=0)
             for s in sizes}
    attempted = int(sum(len(v) for v in iters.values()))
    curve = {str(s): {"iterations": len(v),
                      "median_us": float(np.median(v)) * 1e6,
                      "p95_us": float(np.percentile(v, 95)) * 1e6,
                      "min_us": float(v.min()) * 1e6}
             for s, v in iters.items()}
    for s, c in curve.items():
        say(f"size {s} B: {c['iterations']} iterations, median "
            f"{c['median_us']:.3f} us, p95 {c['p95_us']:.3f} us")
    with open(os.path.join(ctx.out_dir, "curve.json"), "w") as f:
        json.dump({"collective": name, "ranks": n, "seed": ctx.seed,
                   "window_s": window_s, "sizes": curve}, f, indent=1)
    for s in (small, large):
        parts = np.array_split(iters[s], PARTS)
        p50 = [round(float(np.median(p)) * 1e6, 2) for p in parts]
        p95 = [round(float(np.percentile(p, 95)) * 1e6, 2) for p in parts]
        say(f"size {s} B in {PARTS} parts of the slice, in time order: "
            f"median us {p50} p95 us {p95} longest "
            f"{float(iters[s].max()) * 1e6:.1f} us (information)")
    bus = coll.bus_bytes(large, n)
    busbw = bus * len(iters[large]) / float(iters[large].sum()) / 1e9
    say(f"size {large} B: {len(iters[large])} iterations in "
        f"{float(iters[large].sum()):.4f}s of calls -> coll_busbw "
        f"{busbw:.4f} GB/s; from the median iteration "
        f"{bus / float(np.median(iters[large])) / 1e9:.4f} GB/s "
        "(information)")
    say(f"window {window_s:.3f}s of {ctx.seconds}s asked; "
        f"compile requests in window {counters['compiles_in_window']}; "
        f"memory_stats {stats}; peaks of all ranks {peaks_mem}")
    return {
        "end_to_end": {
            "coll_busbw": busbw,
            "coll_lat_p50": float(np.median(iters[small])) * 1e6,
            "coll_lat_p95": float(np.percentile(iters[small], 95)) * 1e6,
        },
        "attempted": attempted, "failed": 0, "checks": checks,
        "spans": spans, "counters": counters,
        "memory_peak_bytes": max(peaks_mem),
        "facts": {"collective": name, "small_bytes": small,
                  "large_bytes": large, "window_s": window_s,
                  "host_small_median_us":
                      float(np.median(mine[small])) * 1e6,
                  "iterations": {k: v["iterations"]
                                 for k, v in curve.items()}},
    }
