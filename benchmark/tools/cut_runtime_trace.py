"""Cut a small fixture out of a real trace, for the tests of
layer_metrics/_runtime.py.

    python benchmark/tools/cut_runtime_trace.py IN.xplane.pb OUT.xplane.pb WINDOW:N [WINDOW:N ...]

cut_program_trace.py keeps the program's and the benchmark's spans;
this keeps what _runtime.py reads: EVERY event of every host line —
jax's and the runtime's beside the program's — with its statistics
(`call`, `program`, `run_id`, ...), and of every chip's `XLA Ops` /
`XLA Modules` lines the events with their own statistics (`run_id`),
none of their metadata's (an op's are kilobytes). Of each WINDOW the
first N traced iterations stay: the stretch from the window's start
to the start of its N+1-th `bench:collective call` (so that a
completion the runtime writes after the caller woke stays too); the
chip's events are cut at the same instants, moved onto the chip's
clock by the middle of _runtime.py's bracket over the whole trace (0
where it finds none). Needs tensorflow's copy of the xplane schema, so
it is a tool for the sandbox and not part of a run.
"""

import sys

from tensorflow.tsl.profiler.protobuf import xplane_pb2

from benchmark import trace_reduce as tr
from benchmark.layer_metrics import _runtime

STARTS = (tr.SPAN + "collective call", tr.SPAN + "dispatch step")


def _times(line, e):
    a = line.timestamp_ns * 1000 + e.offset_ps
    return a, a + e.duration_ps


def _copy_stats(stats, to, used_stats: set) -> None:
    for s in stats:
        to.stats.add().CopyFrom(s)
        used_stats.add(s.metadata_id)
        if s.WhichOneof("value") == "ref_value":
            used_stats.add(s.ref_value)


def main(src: str, dst: str, cuts: dict) -> None:
    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    clock = _runtime.analyse(_runtime.load(src))["bracket_ns"]
    shift = int((clock[0] + clock[1]) / 2 * 1000) if clock else 0
    keep = []  # (lo, hi, the window's metadata id), picoseconds
    for plane in space.planes:
        if not plane.name.startswith("/host:"):
            continue
        names = {k: v.name for k, v in plane.event_metadata.items()}
        for line in plane.lines:
            starts = sorted(_times(line, e)[0] for e in line.events
                            if names.get(e.metadata_id) in STARTS)
            for e in line.events:
                w = names.get(e.metadata_id, "")
                if w.startswith(tr.WINDOW) and w[len(tr.WINDOW):] in cuts:
                    lo, hi = _times(line, e)
                    inside = [t for t in starts if lo <= t <= hi]
                    n = cuts[w[len(tr.WINDOW):]]
                    keep.append((lo, inside[n] - 1 if n < len(inside)
                                 else hi, e.metadata_id))
    if len(keep) != len(cuts):
        raise SystemExit(f"windows {sorted(cuts)} not all in {src}")
    out = xplane_pb2.XSpace()
    for plane in space.planes:
        chip = bool(tr.DEVICE_PLANE.match(plane.name))
        if not (chip or plane.name.startswith("/host:")):
            continue
        new = out.planes.add(id=plane.id, name=plane.name)
        used, used_stats = set(), set()
        d = shift if chip else 0
        for line in plane.lines:
            if chip and line.name not in (tr.OPS_LINE, tr.MODULES_LINE):
                continue
            kept = []
            for e in line.events:
                a, b = _times(line, e)
                for lo, hi, window_id in keep:
                    if e.metadata_id == window_id and not chip:
                        kept.append((e, hi - lo))  # the cut window
                    elif lo <= a - d and b - d <= hi:
                        kept.append((e, e.duration_ps))
            if not kept:
                continue
            nl = new.lines.add(id=line.id, name=line.name,
                               timestamp_ns=line.timestamp_ns)
            for e, dur in kept:
                ne = nl.events.add(metadata_id=e.metadata_id,
                                   offset_ps=e.offset_ps, duration_ps=dur)
                used.add(e.metadata_id)
                _copy_stats(e.stats, ne, used_stats)
        for k in used:
            m = new.event_metadata[k]
            m.id, m.name = k, plane.event_metadata[k].name
            if not chip:  # a host event's constant arguments
                _copy_stats(plane.event_metadata[k].stats, m, used_stats)
        for k in used_stats:
            new.stat_metadata[k].id = k
            new.stat_metadata[k].name = plane.stat_metadata[k].name
    with open(dst, "wb") as f:
        f.write(out.SerializeToString())
    print(f"{dst}: {sum(len(ln.events) for p in out.planes for ln in p.lines)}"
          f" events, {len(out.SerializeToString())} bytes; clock bracket "
          f"{clock}")


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2],
         {w: int(n) for w, n in (a.split(":") for a in sys.argv[3:])})
