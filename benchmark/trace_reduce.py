"""From a profiler trace (.xplane.pb) to the numbers the per-layer
metrics read. Only jax is needed (`jax.profiler.ProfileData`).

What a trace of this chip holds (looked at by hand, PERF.md section 5):
one plane per chip named `/device:TPU:<n>` whose line `XLA Ops` has
one event per executed HLO operation and whose line `XLA Modules` has
one per launched executable; and `/host:CPU`, whose thread lines hold
the benchmark's own annotations. The runner marks each stretch to be
measured with an annotation `bench_window:<name>` and says what the
host is doing with `bench:<what>`.

Per window and per chip: busy time is the UNION of the operation
intervals clipped to the window (operations of concurrent cores or
queues overlap; a sum would count them twice); idle share is
1 - busy / window; every idle gap is named by the innermost `bench:`
span that covers its middle. Busy time is averaged over the chips
that ran any operation.
"""

from __future__ import annotations

import re
from typing import Dict, List, Tuple

WINDOW = "bench_window:"
SPAN = "bench:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")

Interval = Tuple[float, float]


def union(intervals: List[Interval]) -> List[Interval]:
    """Disjoint sorted cover of the intervals."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(iv: Interval, lo: float, hi: float):
    a, b = max(iv[0], lo), min(iv[1], hi)
    return (a, b) if b > a else None


def short(name: str) -> str:
    """An `XLA Ops` event is named by its whole HLO instruction,
    `%fusion.225 = (bf16[7168]{...}, ...) fusion(...)`: keep the
    instruction's own name."""
    return name.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> dict:
    """The events this reduction needs, in plain lists of
    (name, start_ns, end_ns)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    chips, host = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [
                        (short(e.name), e.start_ns,
                         e.start_ns + e.duration_ns)
                        for e in line.events]
            chips[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events
                         if e.name.startswith((WINDOW, SPAN))]
    return {"chips": chips, "host": host}


def reduce_events(events: dict) -> dict:
    windows = [(n[len(WINDOW):], a, b) for n, a, b in events["host"]
               if n.startswith(WINDOW)]
    spans = [(n[len(SPAN):], a, b) for n, a, b in events["host"]
             if n.startswith(SPAN)]
    out: Dict[str, dict] = {}
    op_total: Dict[str, float] = {}
    gap_total: Dict[str, float] = {}
    busy_all = window_all = 0.0
    for wname, lo, hi in windows:
        per_chip, ops, modules = [], {}, {}
        for lines in events["chips"].values():
            clipped = []
            for name, a, b in lines.get(OPS_LINE, []):
                iv = _clip((a, b), lo, hi)
                if iv is None:
                    continue
                clipped.append(iv)
                ops.setdefault(name, []).append((b - a) / 1e3)
                op_total[name] = op_total.get(name, 0.0) + (iv[1] - iv[0])
            if not clipped:
                continue
            cover = union(clipped)
            per_chip.append(sum(b - a for a, b in cover))
            edges = [lo] + [t for iv in cover for t in iv] + [hi]
            for a, b in zip(edges[0::2], edges[1::2]):
                if b > a:
                    who = _covering(spans, (a + b) / 2)
                    gap_total[who] = gap_total.get(who, 0.0) + (b - a)
            for name, a, b in lines.get(MODULES_LINE, []):
                if _clip((a, b), lo, hi):
                    busy = sum(
                        y - x for x, y in
                        (c for c in (_clip(iv, a, b) for iv in cover) if c))
                    modules.setdefault(name, []).append(
                        {"span_us": (b - a) / 1e3, "busy_us": busy / 1e3})
        busy = sum(per_chip) / len(per_chip) if per_chip else 0.0
        out[wname] = {
            "window_s": (hi - lo) / 1e9, "busy_s": busy / 1e9,
            "idle_share": 1.0 - busy / (hi - lo),
            "chips": len(per_chip),
            "op_durations_us": ops, "modules": modules}
        busy_all += busy
        window_all += hi - lo
    top = lambda d: [[k, v / 1e9] for k, v in sorted(  # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:10]]
    return {"windows": out, "busy_s": busy_all / 1e9,
            "window_s": window_all / 1e9,
            "breakdown": {"device_ops": top(op_total),
                          "idle_gaps": top(gap_total)}}


def _covering(spans, t: float) -> str:
    best = None
    for name, a, b in spans:
        if a <= t <= b and (best is None or b - a < best[1]):
            best = (name, b - a)
    return best[0] if best else "no benchmark span"


def reduce_file(path: str) -> dict:
    return reduce_events(load(path))


if __name__ == "__main__":
    import json
    import sys

    red = reduce_file(sys.argv[1])
    for w in red["windows"].values():
        w["op_durations_us"] = {k: len(v) for k, v in
                                w["op_durations_us"].items()}
    json.dump(red, sys.stdout, indent=1)
