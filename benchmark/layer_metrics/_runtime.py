"""What jax and the runtime say of a blocking device collective, beside
what the program says (`_program.py`, which this file reads and does
not change): their own events lie in the same `.xplane.pb`, on the
profiler's one clock, beneath the program's `ompi:coll_xla.launch` and
the caller's wait. `_program.load` drops them; this loads the trace
once more and keeps every event of every `/host:*` line that lies
inside a `bench_window:` span, all threads.

Per traced warm iteration — a `launch` span (`cold=0`) and the first
wait after it (`bench:wait for result`, or the program's own
`ompi:coll_xla.wait`) — events are found BY ROLE through `ROLES`, by
time containment over all host lines (libtpu writes the caller
thread's runtime events on a line of its own, `main/<tid>`, beside
jaxlib's `python3`):

- `jit_call`: the outermost `PjitFunction(<program>)` inside `launch`;
- `execute`: the runtime's execute call inside it;
- `enqueue`: the innermost event of the enqueue that carries `run_id`;
- `await`: the runtime's event inside the wait;
- `done`: the runtime's completion of this launch, on whichever host
  thread writes it, joined by `run_id`, else by order, else None.

A role is the FIRST name of its row that the trace holds; a role with
no match is None, the readers that need it return None, none raises.
The chip's module event is joined to the launch by `run_id` where both
carry one (identity), else by order as `_program.iterations` does; the
json says which.

The clock. With `d` = device clock - host clock, `_program.bracket`
has two inequalities per iteration (launch span's start + d <=
program's start; program's end <= end of the wait + d). Every enqueue
whose `run_id` a module carries gives two closer ones,
    enqueue's start + d <= program's start
    program's end <= done's START + d      (the runtime completes a
                                            run's callbacks after it
                                            has seen the run end)
and the bracket is the intersection of all four over all windows. Its
width is the smallest enqueue-to-start latency plus the smallest
end-to-notice latency of the run: nothing in a trace is closer to the
chip than those. Per iteration
    enqueue_to_start + (program's end - start) + wake
        = end of the wait - end of `launch`
exactly, whatever `d`: the bracket decides the split, never the sum.

`write()` leaves `runtime_path.json` beside `host_path.json` and
prints one `program:` line per role and one with both brackets. In a
window with no `launch` span (a train window) it prints ONE line: the
longest device idle gap and, per host thread, the innermost event of
any name that covers its middle.

    python3 -m benchmark.layer_metrics._runtime FILE.xplane.pb

prints the same for any trace (nothing written).
"""

from __future__ import annotations

import bisect
import json
import os
import statistics
import sys
from typing import Dict, List, Optional, Tuple

from benchmark import trace_reduce as tr
from benchmark.layer_metrics import _program, _xplane

#: role -> event names, first match wins. Read on jaxlib 0.9.0 with
#: libtpu 0.0.34 (the chip's name first) and jaxlib 0.9.0's CPU client
ROLES = {
    "jit_call": ("PjitFunction(",),  # a prefix: the program follows
    "execute": ("PJRT_LoadedExecutable_Execute",
                "PjRtCpuExecutable::Execute"),
    "enqueue": ("DoEnqueueProgram", "PjRtCpuExecutable::ExecuteHelper"),
    "await": ("CommonPjRtBuffer::Await",),
    "done": ("CompleteCallbacks",
             "ThunkExecutor::Execute (wait for completion)"),
}
WAITS = (_program.WAIT, _program.OMPI + "coll_xla.wait")
#: the six metrics, from the per-iteration quantity each is the median of
METRICS = {"launch_jit_us": "launch_jit", "launch_execute_us": "execute",
           "enqueue_to_start_us": "enqueue_to_start", "wake_us": "wake",
           "wake_after_done_us": "wake_after_done"}

_cache: Dict[str, Optional[dict]] = {}


# -- events -------------------------------------------------------------------

def load(path: str) -> dict:
    """{"host": {line: [Event]} inside the windows, "caller": the
    line that holds them, "windows": [(name, lo, hi)], "chips": ...}."""
    got = _xplane.planes(
        path,
        keep=lambda p: p.startswith("/host:")
        or bool(tr.DEVICE_PLANE.match(p)),
        keep_line=lambda p, ln: p.startswith("/host:")
        or ln in (tr.OPS_LINE, tr.MODULES_LINE))
    host: Dict[str, list] = {}
    for p, lines in got.items():
        if p.startswith("/host:"):
            host.update({ln: ev for ln, ev in lines.items() if ev})
    caller = max(host, default=None, key=lambda ln: sum(
        e.name.startswith(tr.WINDOW) for e in host[ln]))
    windows = [(e.name[len(tr.WINDOW):], e.start_ns, e.end_ns)
               for e in host.get(caller, [])
               if e.name.startswith(tr.WINDOW)]
    inside = {ln: [e for e in ev if any(
        lo <= e.start_ns and e.end_ns <= hi for _, lo, hi in windows)]
        for ln, ev in host.items()}
    return {"host": {ln: ev for ln, ev in inside.items() if ev},
            "caller": caller if windows else None, "windows": windows,
            "chips": {p: lines for p, lines in got.items()
                      if not p.startswith("/host:")}}


class Role:
    """The events of one role, in time order, under the name found."""

    def __init__(self, role: str, host: Dict[str, list]) -> None:
        self.role, self.name, self.line = role, None, None
        self.events: list = []
        for want in ROLES[role]:
            match = str.startswith if want.endswith("(") else str.__eq__
            for ln, ev in host.items():
                hit = [e for e in ev if match(e.name, want)]
                if hit:
                    self.events += hit
                    self.line = ln if self.line is None \
                        else self.line + ", " + ln
            if self.events:
                self.name = want
                break
        self.events.sort(key=lambda e: (e.start_ns, -e.end_ns))
        self._starts = [e.start_ns for e in self.events]
        self.by_run = {e.stats["run_id"]: e for e in self.events
                       if "run_id" in e.stats}

    def within(self, lo: float, hi: float):
        """The first (so the outermost) event inside [lo, hi]."""
        for e in self.events[bisect.bisect_left(self._starts, lo):]:
            if e.start_ns > hi:
                return None
            if e.end_ns <= hi:
                return e
        return None


def _span(e) -> Optional[Tuple[float, float]]:
    return None if e is None else (e.start_ns, e.end_ns)


def iterations(spans: List[dict], roles: Dict[str, Role]) -> List[dict]:
    """The traced warm iterations on the caller's thread, in order:
    each `launch` span with `cold=0`, the first wait that begins after
    it and before the next launch, and the role events inside them."""
    launches = sorted((s for s in spans if s["name"] == _program.LAUNCH
                       and not s["args"].get("cold")),
                      key=lambda s: s["a"])
    waits = sorted((s for s in spans if s["name"] in WAITS),
                   key=lambda s: s["a"])
    out, w = [], 0
    for k, s in enumerate(launches):
        while w < len(waits) and waits[w]["a"] < s["b"]:
            w += 1
        nxt = launches[k + 1]["a"] if k + 1 < len(launches) \
            else float("inf")
        if w == len(waits) or waits[w]["a"] > nxt:
            continue  # launched and never waited for here
        a, b = s["a"], s["b"]
        enq = roles["enqueue"].within(a, b)
        out.append({
            "program": str(s["args"].get("program")),
            "launch": (a, b), "wait": (waits[w]["a"], waits[w]["b"]),
            "jit_call": _span(roles["jit_call"].within(a, b)),
            "execute": _span(roles["execute"].within(a, b)),
            "enqueue": _span(enq),
            "run_id": enq.stats.get("run_id") if enq else None,
            "await": _span(roles["await"].within(
                waits[w]["a"], waits[w]["b"])),
            "done": None, "prog": None})
    return out


def join_done(iters: List[dict], done: Role) -> Optional[str]:
    """Each iteration's `done`: by `run_id`, else by order where the
    counts agree. Returns how ("run_id", "order") or None."""
    if not done.events or not iters:
        return None
    if any(i["run_id"] in done.by_run for i in iters):
        for i in iters:
            i["done"] = _span(done.by_run.get(i["run_id"]))
        return "run_id"
    lo, hi = iters[0]["launch"][0], iters[-1]["wait"][1]
    mine = [e for e in done.events if lo <= e.start_ns <= hi]
    if len(mine) != len(iters):
        return None
    for i, e in zip(iters, mine):
        i["done"] = _span(e)
    return "order"


def modules_by_run_id(iters: List[dict], modules: list) -> list:
    by = {m.stats["run_id"]: m for m in modules if "run_id" in m.stats}
    return [by.get(i["run_id"]) if i["run_id"] is not None else None
            for i in iters]


def modules_by_order(iters: List[dict], modules: list) -> list:
    """The k-th iteration of program P with the k-th launch of module
    `jit_<P>`, only where both counts agree (`_program.iterations`)."""
    out: list = [None] * len(iters)
    for prog in {i["program"] for i in iters}:
        mine = [k for k, i in enumerate(iters) if i["program"] == prog]
        dev = sorted((m for m in modules
                      if _program.program_of(m.name) == prog),
                     key=lambda m: m.start_ns)
        if len(dev) == len(mine):
            for k, m in zip(mine, dev):
                out[k] = m
    return out


def join_modules(iters: List[dict], modules: list) -> Optional[str]:
    how, found = "run_id", modules_by_run_id(iters, modules)
    if not any(found):
        how, found = "order", modules_by_order(iters, modules)
    if not any(found):
        return None
    for i, m in zip(iters, found):
        i["prog"] = _span(m)
    return how


# -- the clock ----------------------------------------------------------------

def bracket(iters: List[dict], roles: Dict[str, Role], modules: list
            ) -> Optional[Tuple[float, float]]:
    """[lo, hi] ns of (device clock - host clock) from the four
    inequalities of the module docstring: the program's two over the
    paired iterations, the runtime's two over EVERY enqueue whose
    `run_id` a module carries (a train step's too). None with nothing
    to go by; lo > hi would mean a pairing is wrong."""
    los, his = [], []
    for i in iters:
        if i["prog"] is None:
            continue
        his.append(i["prog"][0] - i["launch"][0])
        los.append(i["prog"][1] - i["wait"][1])
        first = i["enqueue"] or i["execute"]
        if first:
            his.append(i["prog"][0] - first[0])
        if i["done"]:
            los.append(i["prog"][1] - i["done"][0])
    by = {m.stats["run_id"]: m for m in modules if "run_id" in m.stats}
    for run_id, m in by.items():
        enq = roles["enqueue"].by_run.get(run_id)
        end = roles["done"].by_run.get(run_id)
        if enq is not None:
            his.append(m.start_ns - enq.start_ns)
        if end is not None:
            los.append(m.end_ns - end.start_ns)
    return (max(los), min(his)) if los and his else None


def split(i: dict, d: float) -> Dict[str, Optional[float]]:
    """One iteration's quantities, ns. `d` moves the program's start
    and end onto the host's clock."""
    (la, lb), (_, wb) = i["launch"], i["wait"]
    ex, prog, done = i["execute"], i["prog"], i["done"]
    return {
        "launch": lb - la,
        "execute": ex[1] - ex[0] if ex else None,
        "launch_jit": (lb - la) - (ex[1] - ex[0]) if ex else None,
        "before_jit": i["jit_call"][0] - la if i["jit_call"] else None,
        "jit_before_execute": ex[0] - i["jit_call"][0]
        if ex and i["jit_call"] else None,
        "jit_after_execute": i["jit_call"][1] - ex[1]
        if ex and i["jit_call"] else None,
        "after_jit": lb - i["jit_call"][1] if i["jit_call"] else None,
        "launch_end_to_wait_end": wb - lb,
        "program": prog[1] - prog[0] if prog else None,
        "enqueue_to_start": prog[0] - d - lb if prog else None,
        "wake": wb - (prog[1] - d) if prog else None,
        "end_to_done": done[0] - (prog[1] - d) if prog and done else None,
        "done": done[1] - done[0] if done else None,
        "wake_after_done": wb - done[1] if done else None,
    }


# -- every event of an iteration, by name ---------------------------------------

def timeline(host: Dict[str, list], iters: List[dict]) -> List[dict]:
    """Every event name (any line) that lies inside a traced
    iteration — start of `launch` to end of the wait — as {name, line,
    per_iteration, median start after the launch's start, median
    duration}, in time order: the whole account in one table."""
    starts = [i["launch"][0] for i in iters]
    by: Dict[Tuple[str, str], Tuple[list, list]] = {}
    for ln, ev in host.items():
        for e in ev:
            k = bisect.bisect_right(starts, e.start_ns) - 1
            if k < 0 or e.end_ns > iters[k]["wait"][1]:
                continue
            off, dur = by.setdefault((ln, e.name), ([], []))
            off.append(e.start_ns - starts[k])
            dur.append(e.end_ns - e.start_ns)
    rows = [{"name": n, "line": ln,
             "per_iteration": len(off) / len(iters),
             "start_us": statistics.median(off) / 1e3,
             "dur_us": statistics.median(dur) / 1e3}
            for (ln, n), (off, dur) in by.items()]
    return sorted(rows, key=lambda r: (r["start_us"], -r["dur_us"]))


# -- the late host ------------------------------------------------------------

def longest_gap(host: Dict[str, list], ops: list, lo: float, hi: float,
                d: float) -> Optional[dict]:
    """The longest stretch of [lo, hi] (host clock) with no operation
    on the chip, and per host line the innermost event that covers
    its middle."""
    cover = tr.union([(o.start_ns - d, o.end_ns - d) for o in ops
                      if o.end_ns - d > lo and o.start_ns - d < hi])
    if not cover:
        return None
    edges = [lo] + [min(max(t, lo), hi) for iv in cover for t in iv] + [hi]
    a, b = max(zip(edges[0::2], edges[1::2]), key=lambda g: g[1] - g[0])
    mid, over = (a + b) / 2, {}
    for ln, ev in host.items():
        inner = min((e for e in ev if e.start_ns <= mid <= e.end_ns
                     and not e.name.startswith(tr.WINDOW)),
                    key=lambda e: e.end_ns - e.start_ns, default=None)
        over[ln] = inner.name if inner else None
    return {"gap_us": (b - a) / 1e3, "after_window_start_us": (a - lo) / 1e3,
            "covered_by": over}


# -- one run ------------------------------------------------------------------

def _stats(values: List[Optional[float]]) -> Optional[dict]:
    got = [v for v in values if v is not None]
    return _program._summary(got) if got else None


def analyse(events: dict) -> dict:
    host = events["host"]
    roles = {r: Role(r, host) for r in ROLES}
    spans = _program.nest(host.get(events["caller"], []))
    chip = next((lines for lines in events["chips"].values()
                 if lines.get(tr.MODULES_LINE)), {})
    modules = chip.get(tr.MODULES_LINE, [])
    iters = iterations(spans, roles)
    done_by = join_done(iters, roles["done"])
    prog_by = join_modules(iters, modules)
    old = _program.bracket(_program.iterations(spans, modules))
    new = bracket(iters, roles, modules)
    d = (new[0] + new[1]) / 2 if new else 0.0
    out = {"roles": {r: {"event": x.name, "line": x.line,
                         "count": len(x.events)}
                     for r, x in roles.items()},
           "done_joined_by": done_by, "program_joined_by": prog_by,
           "bracket_program_ns": list(old) if old else None,
           "bracket_ns": list(new) if new else None,
           "iterations": len(iters), "windows": {}}
    for name, lo, hi in events["windows"]:
        mine = [i for i in iters
                if lo <= i["launch"][0] and i["wait"][1] <= hi]
        if not mine:
            out["windows"][name] = {"longest_idle_gap": longest_gap(
                host, chip.get(tr.OPS_LINE, []), lo, hi, d)}
            continue
        per = [split(i, d) for i in mine]
        row = {"iterations": len(mine),
               "split_us": {k: _stats([p[k] for p in per])
                            for k in per[0]},
               "roles_us": {r: _stats([i[r][1] - i[r][0] if i[r] else None
                                       for i in mine])
                            for r in ROLES},
               "timeline": timeline(host, mine)}
        row["identity_worst_ns"] = max(
            (abs(p["enqueue_to_start"] + p["program"] + p["wake"]
                 - p["launch_end_to_wait_end"])
             for p in per if p["program"] is not None), default=None)
        out["windows"][name] = row
    # the six metrics: only of a trace that holds the runtime's events
    # (without them the split is `_program`'s, under `_program`'s name)
    small = out["windows"].get("small", {}).get("split_us")
    out["metrics"] = {}
    if small and (roles["execute"].name or roles["enqueue"].name):
        for metric, key in METRICS.items():
            if small[key]:
                out["metrics"][metric] = small[key]["median_us"]
        if new and small["program"]:
            out["metrics"]["clock_bracket_us"] = (new[1] - new[0]) / 1e3
    return out


def analysis() -> Optional[dict]:
    """The run's analysis (made once per process), or None where the
    rank has no trace."""
    path = _program.trace_path()
    if path is None:
        return None
    if path not in _cache:
        _cache[path] = analyse(load(path))
        write(_cache[path], _program.out_dir())
    return _cache[path]


def metric(name: str) -> Optional[float]:
    a = analysis()
    return a["metrics"].get(name) if a else None


def write(a: dict, out: Optional[str]) -> None:
    from benchmark.common import say

    if out is not None:
        with open(os.path.join(out, "runtime_path.json"), "w") as f:
            json.dump(a, f, indent=1)
    small = a["windows"].get("small", {})
    for role, r in a["roles"].items():
        s = small.get("roles_us", {}).get(role)
        say(f"program: runtime role {role}: "
            + (f"{r['event']!r} on line {r['line']!r}" if r["event"]
               else f"no event of {list(ROLES[role])}")
            + (f", window small: {s['count']} x median "
               f"{s['median_us']:.3f} us p95 {s['p95_us']:.3f} us"
               if s else "") + " (information)")
    width = lambda c: f"{c} ns (width {c[1] - c[0]:.0f} ns)" \
        if c else "none"  # noqa: E731
    say(f"program: device clock - host clock: from the program's spans "
        f"{width(a['bracket_program_ns'])}, with the runtime's events "
        f"{width(a['bracket_ns'])}; {a['iterations']} traced iterations, "
        f"program joined by {a['program_joined_by']}, done by "
        f"{a['done_joined_by']} (information)")
    for name, w in a["windows"].items():
        if "split_us" in w:
            say(f"program: window {name}: runtime split us "
                f"{ {k: round(v['median_us'], 3) for k, v in w['split_us'].items() if v} }"
                f"; identity holds to {w['identity_worst_ns']} ns "
                "(information)")
        elif w["longest_idle_gap"]:
            say(f"program: window {name}: longest device idle gap "
                f"{json.dumps(w['longest_idle_gap'])} (information)")


if __name__ == "__main__":
    write(analyse(load(sys.argv[1])), None)
