"""Cut a small fixture out of a real trace, for benchmark/tests.

    python benchmark/tools/cut_trace.py IN.xplane.pb OUT.xplane.pb WINDOW [SECONDS]

Keeps what trace_reduce.py reads and nothing else: of every chip's
plane the `XLA Ops` and `XLA Modules` lines, of the host plane the
benchmark's own annotations, all cut to the first SECONDS (default:
all) of the window named WINDOW, with the event statistics dropped.
Needs tensorflow's copy of the xplane schema, so it is a tool for the
sandbox and not part of a run.
"""

import sys

from tensorflow.tsl.profiler.protobuf import xplane_pb2

from benchmark import trace_reduce as tr


def main(src: str, dst: str, window: str, seconds: float = None) -> None:
    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    lo = hi = None
    for plane in space.planes:
        if not plane.name.startswith("/host:"):
            continue
        names = {k: v.name for k, v in plane.event_metadata.items()}
        for line in plane.lines:
            for e in line.events:
                if names.get(e.metadata_id) == tr.WINDOW + window:
                    lo = line.timestamp_ns * 1000 + e.offset_ps
                    hi = lo + e.duration_ps
    if lo is None:
        raise SystemExit(f"no window {window!r} in {src}")
    if seconds is not None:
        hi = min(hi, lo + int(seconds * 1e12))
    out = xplane_pb2.XSpace()
    for plane in space.planes:
        chip = bool(tr.DEVICE_PLANE.match(plane.name))
        if not (chip or plane.name.startswith("/host:")):
            continue
        names = {k: v.name for k, v in plane.event_metadata.items()}
        new = out.planes.add(id=plane.id, name=plane.name)
        used = set()
        for line in plane.lines:
            if chip and line.name not in (tr.OPS_LINE, tr.MODULES_LINE):
                continue
            kept = []
            for e in line.events:
                a = line.timestamp_ns * 1000 + e.offset_ps
                name = names.get(e.metadata_id, "")
                if not chip:
                    if not name.startswith((tr.WINDOW, tr.SPAN)):
                        continue
                    if name == tr.WINDOW + window:
                        kept.append((e, hi - lo))  # the cut window
                        continue
                    if name.startswith(tr.WINDOW):
                        continue
                if a < lo or a + e.duration_ps > hi:
                    continue
                kept.append((e, e.duration_ps))
            if not kept:
                continue
            nl = new.lines.add(id=line.id, name=line.name,
                               timestamp_ns=line.timestamp_ns)
            for e, dur in kept:
                nl.events.add(metadata_id=e.metadata_id,
                              offset_ps=e.offset_ps, duration_ps=dur)
                used.add(e.metadata_id)
        for k in used:
            new.event_metadata[k].id = k
            new.event_metadata[k].name = names[k]
    with open(dst, "wb") as f:
        f.write(out.SerializeToString())
    print(f"{dst}: {sum(len(ln.events) for p in out.planes for ln in p.lines)}"
          f" events, {len(out.SerializeToString())} bytes")


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], sys.argv[3],
         float(sys.argv[4]) if len(sys.argv) > 4 else None)
