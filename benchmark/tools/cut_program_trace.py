"""Cut a small fixture out of a real trace, for the tests of
layer_metrics/_program.py.

    python benchmark/tools/cut_program_trace.py IN.xplane.pb OUT.xplane.pb WINDOW:N [WINDOW:N ...]

cut_trace.py keeps what trace_reduce.py reads and drops every
statistic; this keeps what _program.py reads as well: of the host
plane the program's `ompi:` spans beside the benchmark's, WITH their
arguments (`call`, `program`, `cold`, ...), and of every chip's `XLA
Ops` / `XLA Modules` lines the events with their metadata's `tf_op`
(the jax.named_scope path). Of each WINDOW the first N traced
iterations stay (an iteration ends with a `bench:wait for result` or a
`bench:wait for loss`); the chip's events are cut at the same instant,
moved onto the chip's clock by the middle of the causality bracket
that _program.py reads from the whole trace (0 where it finds none).
Needs tensorflow's copy of the xplane schema, so it is a tool for the
sandbox and not part of a run.
"""

import sys

from tensorflow.tsl.profiler.protobuf import xplane_pb2

from benchmark import trace_reduce as tr
from benchmark.layer_metrics import _program

KEEP_HOST = (_program.OMPI, tr.SPAN, tr.WINDOW)
ENDS = (tr.SPAN + "wait for result", tr.SPAN + "wait for loss")
KEEP_STAT = "tf_op"


def _times(line, e):
    a = line.timestamp_ns * 1000 + e.offset_ps
    return a, a + e.duration_ps


def main(src: str, dst: str, cuts: dict) -> None:
    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    clock = _program.analyse(_program.load(src))["clock_bracket_ns"]
    shift = int((clock[0] + clock[1]) / 2 * 1000) if clock else 0
    # the stretches of host time that stay: window start -> end of its
    # N-th iteration
    keep = []
    for plane in space.planes:
        if not plane.name.startswith("/host:"):
            continue
        names = {k: v.name for k, v in plane.event_metadata.items()}
        for line in plane.lines:
            ends = sorted(_times(line, e)[1] for e in line.events
                          if names.get(e.metadata_id) in ENDS)
            for e in line.events:
                w = names.get(e.metadata_id, "")
                if w.startswith(tr.WINDOW) and w[len(tr.WINDOW):] in cuts:
                    lo, hi = _times(line, e)
                    inside = [t for t in ends if lo <= t <= hi]
                    n = cuts[w[len(tr.WINDOW):]]
                    keep.append((lo, inside[n - 1] if n <= len(inside)
                                 else hi, e.metadata_id))
    if len(keep) != len(cuts):
        raise SystemExit(f"windows {sorted(cuts)} not all in {src}")
    out = xplane_pb2.XSpace()
    for plane in space.planes:
        chip = bool(tr.DEVICE_PLANE.match(plane.name))
        if not (chip or plane.name.startswith("/host:")):
            continue
        names = {k: v.name for k, v in plane.event_metadata.items()}
        new = out.planes.add(id=plane.id, name=plane.name)
        used, used_stats = set(), set()
        d = shift if chip else 0
        for line in plane.lines:
            if chip and line.name not in (tr.OPS_LINE, tr.MODULES_LINE):
                continue
            kept = []
            for e in line.events:
                name = names.get(e.metadata_id, "")
                if not chip and not name.startswith(KEEP_HOST):
                    continue
                a, b = _times(line, e)
                for lo, hi, window_id in keep:
                    if e.metadata_id == window_id and not chip:
                        kept.append((e, hi - lo))  # the cut window
                    elif lo <= a - d and b - d <= hi \
                            and not name.startswith(tr.WINDOW):
                        kept.append((e, e.duration_ps))
            if not kept:
                continue
            nl = new.lines.add(id=line.id, name=line.name,
                               timestamp_ns=line.timestamp_ns)
            for e, dur in kept:
                ne = nl.events.add(metadata_id=e.metadata_id,
                                   offset_ps=e.offset_ps, duration_ps=dur)
                used.add(e.metadata_id)
                if not chip:  # the span's arguments
                    for s in e.stats:
                        ne.stats.add().CopyFrom(s)
                        used_stats.add(s.metadata_id)
                        if s.WhichOneof("value") == "ref_value":
                            used_stats.add(s.ref_value)
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        for k in used:
            m = new.event_metadata[k]
            m.id, m.name = k, names[k]
            for s in plane.event_metadata[k].stats:
                if chip and stat_names.get(s.metadata_id) == KEEP_STAT:
                    m.stats.add().CopyFrom(s)
                    used_stats.add(s.metadata_id)
        for k in used_stats:
            new.stat_metadata[k].id = k
            new.stat_metadata[k].name = stat_names.get(k, "")
    with open(dst, "wb") as f:
        f.write(out.SerializeToString())
    print(f"{dst}: {sum(len(ln.events) for p in out.planes for ln in p.lines)}"
          f" events, {len(out.SerializeToString())} bytes; clock bracket "
          f"{clock}")


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2],
         {w: int(n) for w, n in (a.split(":") for a in sys.argv[3:])})
