"""What the PROGRAM says of itself in a traced run: its spans on the
profiler's clock (`ompi:<subsys>.<name>`, ompi_tpu/trace/recorder.py's
one span source), its device programs and model parts by name, and its
always-on counters (pvars). The readers beside this file take their
numbers from `analysis()`; a program that has none of this (a parent
commit) makes every one of them return None, and none raises.

Per traced window (`bench_window:<name>`), on rank 0's thread and chip:

- every `ompi:`/`bench:` span: count, median and p95 duration, and
  median SELF time (its duration less its direct children's: guide
  section 4), the children found by containment on the thread's line;
- the launches on the chip's `XLA Modules` line, by program;
- the host/device clock offset, bracketed by causality. With `d` the
  device clock less the host clock, every traced iteration gives
      launch span's start + d <= program's start   (d <= hi_i)
      program's end <= end of the caller's wait + d  (d >= lo_i)
  and the bracket is [max lo_i, min hi_i] over the iterations of ALL
  windows (one clock pair). PR 23 saw the device timeline ~0.8 ms
  early: nothing a microsecond-scale loop can be attributed through
  without this;
- device idle gaps by the innermost span that covers them on the
  host's clock — only where the bracket is narrower than the gap; the
  others stay `unattributed (bracket wider than gap)`;
- device-busy time under each `jax.named_scope` of the model (from the
  ops' `tf_op` path; `_xplane.py`), per launch of the program with
  most device time in the window, the median over its launches.

`write()` leaves `host_path.json` beside the trace and prints one line
per span.
"""

from __future__ import annotations

import glob
import json
import os
import re
import statistics
import sys
from typing import Dict, List, Optional, Tuple

from benchmark import trace_reduce as tr
from benchmark.layer_metrics import _xplane

OMPI = "ompi:"
LAUNCH = OMPI + "coll_xla.launch"
CALL = tr.SPAN + "collective call"
WAIT = tr.SPAN + "wait for result"
UNATTRIBUTED = "unattributed (bracket wider than gap)"
NO_SPAN = "no span"
#: the parts of the model step that are named (models/transformer.py)
SCOPE = re.compile(r"^(embed|layer_\d+|ln|attn_proj|attn_core|mlp|"
                   r"head_loss|grad_sync|sgd_update)$")
WORD = re.compile(r"[A-Za-z_][A-Za-z_0-9]*")

_cache: Dict[str, Optional[dict]] = {}


# -- where the run's own files are ------------------------------------------

def out_dir() -> Optional[str]:
    """The rank's own `--out` (readers run inside rank 0)."""
    argv = sys.argv
    return argv[argv.index("--out") + 1] if "--out" in argv[:-1] else None


def trace_path() -> Optional[str]:
    out = out_dir()
    if out is None:
        return None
    found = sorted(glob.glob(os.path.join(
        out, "trace", "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def counter(name: str) -> Optional[int]:
    """An always-on counter of the program, None where the program has
    no such counter (or never counted)."""
    try:
        from ompi_tpu.core import pvar
    except ImportError:
        return None
    return pvar.read(name) or None


def counter_seconds(name: str) -> Optional[float]:
    ns = counter(name)
    return None if ns is None else ns / 1e9


#: the phases of mpi.Init(), in the order they run
INIT_PHASES = ("import", "rte", "accelerator", "distributed", "client",
               "fence", "pml", "world")


def init_phases() -> Dict[str, float]:
    """Seconds of each phase of this rank's mpi.Init() that the
    program counted (every one of them, also those no metric reads)."""
    got = {p: counter_seconds(f"init_{p}_ns") for p in INIT_PHASES}
    return {p: s for p, s in got.items() if s is not None}


# -- events -------------------------------------------------------------------

def load(path: str) -> dict:
    """{"host": {thread line: [Event]}, "chips": {plane: {line: [Event]}}}
    — of the host only the program's and the benchmark's spans."""
    wanted = (OMPI, tr.SPAN, tr.WINDOW)
    got = _xplane.planes(
        path,
        keep=lambda p: p.startswith("/host:")
        or bool(tr.DEVICE_PLANE.match(p)),
        keep_line=lambda p, ln: p.startswith("/host:")
        or ln in (tr.OPS_LINE, tr.MODULES_LINE),
        keep_event=lambda p, n: not p.startswith("/host:")
        or n.startswith(wanted))
    host = {}
    for p, lines in got.items():
        if p.startswith("/host:"):
            host.update({ln: ev for ln, ev in lines.items() if ev})
    return {"host": host,
            "chips": {p: lines for p, lines in got.items()
                      if not p.startswith("/host:")}}


def nest(events: list) -> List[dict]:
    """Spans of one thread as dicts {name, a, b, args, self, parent}:
    `self` is the duration less the direct children's, `parent` the
    index of the innermost enclosing span (None at the top)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i].start_ns, -events[i].end_ns))
    out, stack = [], []
    for i in order:
        e = events[i]
        while stack and out[stack[-1]]["b"] < e.end_ns:
            stack.pop()
        row = {"name": e.name, "a": e.start_ns, "b": e.end_ns,
               "args": e.stats, "self": e.end_ns - e.start_ns,
               "parent": stack[-1] if stack else None}
        if stack:
            out[stack[-1]]["self"] -= row["self"]
        stack.append(len(out))
        out.append(row)
    return out


def _summary(values_ns: List[float]) -> dict:
    v = sorted(values_ns)
    return {"count": len(v),
            "median_us": statistics.median(v) / 1e3,
            "p95_us": v[min(len(v) - 1, int(0.95 * len(v)))] / 1e3}


def span_stats(spans: List[dict], lo: float, hi: float) -> Dict[str, dict]:
    """Per span name inside [lo, hi]: count, median, p95, self median.
    A launch with `cold=1` is kept apart (`<name> cold`): it compiles."""
    by: Dict[str, Tuple[list, list]] = {}
    for s in spans:
        if s["a"] < lo or s["b"] > hi or s["name"].startswith(tr.WINDOW):
            continue
        name = s["name"]
        if name == LAUNCH and s["args"].get("cold"):
            name += " cold"
        dur, own = by.setdefault(name, ([], []))
        dur.append(s["b"] - s["a"])
        own.append(s["self"])
    return {n: dict(_summary(d), self_median_us=statistics.median(o) / 1e3)
            for n, (d, o) in by.items()}


def host_path(spans: List[dict], stats: Dict[str, dict], lo: float,
              hi: float) -> Optional[dict]:
    """The traced iteration of a blocking collective as the caller
    sees it — start of `bench:collective call` to the end of the
    `bench:wait for result` that follows — held against the sum of its
    parts: the API binding's and the slot's self times, `to_global`,
    `launch`, `my_shard` and the wait. `sum_us` adds the parts'
    MEDIANS (a sum of medians of right-skewed parts falls short of the
    median of their sum); `accounted_share` is exact, per iteration:
    (the API span + the wait) / the iteration, the median — what it
    lacks of 1 is the benchmark's own span body and the Python between
    its two spans."""
    calls = sorted((s for s in spans if s["name"] == CALL
                    and lo <= s["a"] and s["b"] <= hi), key=lambda s: s["a"])
    waits = sorted((s for s in spans if s["name"] == WAIT
                    and lo <= s["a"] and s["b"] <= hi), key=lambda s: s["a"])
    api = [n for n in stats if n.startswith(OMPI + "api.")]
    if not calls or len(calls) != len(waits) or not api:
        return None
    names = {"api_self": api_span_name(stats),
             "slot_self": slot_span_name(stats),
             "to_global": OMPI + "coll_xla.to_global", "launch": LAUNCH,
             "my_shard": OMPI + "coll_xla.my_shard", "wait": WAIT}
    parts = {}
    for part, name in names.items():
        if name not in stats:
            return None
        parts[part] = stats[name][
            "self_median_us" if part.endswith("_self") else "median_us"]
    whole = statistics.median(w["b"] - c["a"]
                              for c, w in zip(calls, waits)) / 1e3
    inner = sorted((s for s in spans if s["name"] == names["api_self"]
                    and lo <= s["a"] and s["b"] <= hi), key=lambda s: s["a"])
    out = {"iteration_median_us": whole, "parts_us": parts,
           "sum_us": sum(parts.values()),
           "sum_share": sum(parts.values()) / whole}
    if len(inner) == len(calls):
        out["accounted_share"] = statistics.median(
            (a["b"] - a["a"] + w["b"] - w["a"]) / (w["b"] - c["a"])
            for c, a, w in zip(calls, inner, waits))
    return out


# -- the clock ----------------------------------------------------------------

def program_of(module: str) -> str:
    """`jit_ompi_allreduce(123)` -> `ompi_allreduce`."""
    name = module.split("(", 1)[0]
    return name[len("jit_"):] if name.startswith("jit_") else name


def iterations(spans: List[dict], modules: list) -> List[dict]:
    """The traced iterations that can be paired with a launch on the
    chip: the k-th warm `launch` span of program P with the k-th launch
    of module `jit_<P>` in the trace (only where both counts agree: the
    clocks differ, so nothing but the order pairs them), and the first
    `bench:wait for result` that begins after it."""
    launches: Dict[str, list] = {}
    for s in spans:
        if s["name"] == LAUNCH and not s["args"].get("cold"):
            launches.setdefault(str(s["args"].get("program")), []).append(s)
    waits = sorted((s for s in spans if s["name"] == WAIT),
                   key=lambda s: s["a"])
    out = []
    for prog, host in launches.items():
        dev = sorted((m for m in modules if program_of(m.name) == prog),
                     key=lambda m: m.start_ns)
        if len(dev) != len(host):
            continue
        host.sort(key=lambda s: s["a"])
        w = 0
        for s, m in zip(host, dev):
            while w < len(waits) and waits[w]["a"] < s["b"]:
                w += 1
            if w == len(waits):
                break
            out.append({"launch_a": s["a"], "launch_b": s["b"],
                        "prog_a": m.start_ns, "prog_b": m.end_ns,
                        "wait_a": waits[w]["a"], "wait_b": waits[w]["b"]})
    return out


def bracket(iters: List[dict]) -> Optional[Tuple[float, float]]:
    """[lo, hi] ns of (device clock - host clock), or None with no
    iteration to go by. lo > hi would mean a pairing is wrong."""
    if not iters:
        return None
    return (max(i["prog_b"] - i["wait_b"] for i in iters),
            min(i["prog_a"] - i["launch_a"] for i in iters))


# -- idle gaps ----------------------------------------------------------------

def gaps_by_span(spans: List[dict], ops: list, lo: float, hi: float,
                 clock: Optional[Tuple[float, float]]) -> Dict[str, float]:
    """Idle ns of one chip inside the window [lo, hi] (host clock), by
    the innermost span covering each gap's middle. A gap shorter than
    the clock bracket is not attributed."""
    if not ops:
        return {}
    d = (clock[0] + clock[1]) / 2 if clock else 0.0
    width = clock[1] - clock[0] if clock else float("inf")
    cover = tr.union([(o.start_ns - d, o.end_ns - d) for o in ops
                      if o.end_ns - d > lo and o.start_ns - d < hi])
    edges = [lo] + [min(max(t, lo), hi) for iv in cover for t in iv] + [hi]
    out: Dict[str, float] = {}
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        if b - a <= width:
            who = UNATTRIBUTED
        else:
            mid, who, best = (a + b) / 2, NO_SPAN, None
            for s in spans:
                if s["a"] <= mid <= s["b"] and not s["name"].startswith(
                        tr.WINDOW) and (best is None
                                        or s["b"] - s["a"] < best):
                    who, best = s["name"], s["b"] - s["a"]
        out[who] = out.get(who, 0.0) + (b - a)
    return out


# -- the model's parts --------------------------------------------------------

def scopes_of(op_path: str) -> List[str]:
    """`jit(ompi_train_step)/transpose(jvp(layer_0))/attn_core/dot:` ->
    ['layer_0', 'attn_core']."""
    return [w for w in WORD.findall(op_path or "") if SCOPE.match(w)]


def scope_busy(ops: list, lo: float, hi: float) -> Dict[str, float]:
    """Device-busy ns inside [lo, hi] (device clock) of the ops under
    each scope name, forward and backward together (an op under
    `layer_0/attn_core` counts for both names), `unscoped` for ops no
    scope claims and `all` for every op."""
    by: Dict[str, list] = {}
    for o in ops:
        iv = (max(o.start_ns, lo), min(o.end_ns, hi))
        if iv[1] <= iv[0]:
            continue
        names = set(scopes_of(o.stats.get("tf_op"))) or {"unscoped"}
        for n in names | {"all"}:
            by.setdefault(n, []).append(iv)
    return {n: sum(b - a for a, b in tr.union(ivs))
            for n, ivs in by.items()}


def scope_busy_per_launch(ops: list, launches: list) -> Dict[str, float]:
    """Per scope name, the median over a program's launches of the
    device-busy ns inside the launch."""
    per = [scope_busy(ops, m.start_ns, m.end_ns) for m in launches]
    names = set().union(*per) if per else set()
    return {n: statistics.median(p.get(n, 0.0) for p in per)
            for n in names}


# -- one run ------------------------------------------------------------------

def analyse(events: dict) -> dict:
    """Everything above for every window of one trace."""
    threads = [nest(ev) for ev in events["host"].values()]
    main = max(threads, key=len, default=[])  # the thread that ran it
    chip = next((lines for lines in events["chips"].values()
                 if lines.get(tr.MODULES_LINE)), {})
    ops = chip.get(tr.OPS_LINE, [])
    modules = chip.get(tr.MODULES_LINE, [])
    windows = [(s["name"][len(tr.WINDOW):], s["a"], s["b"])
               for s in main if s["name"].startswith(tr.WINDOW)]
    all_iters = iterations(main, modules)
    per = {name: {"lo": lo, "hi": hi, "spans": span_stats(main, lo, hi),
                  "iters": [i for i in all_iters
                            if lo <= i["launch_a"] and i["wait_b"] <= hi]}
           for name, lo, hi in windows}
    clock = bracket(all_iters)
    d = (clock[0] + clock[1]) / 2 if clock else 0.0
    out = {"clock_bracket_ns": list(clock) if clock else None,
           "iterations_paired": len(all_iters),
           "init_phases_s": init_phases(), "windows": {}}
    for name, w in per.items():
        lo, hi, its = w["lo"], w["hi"], w["iters"]
        inside: Dict[str, list] = {}  # whole launches, by program
        for m in modules:
            if lo <= m.start_ns - d and m.end_ns - d <= hi:
                inside.setdefault(program_of(m.name), []).append(m)
        heaviest = max(inside.values(), default=[], key=lambda ms: sum(
            m.end_ns - m.start_ns for m in ms))
        row = {"window_us": (hi - lo) / 1e3, "spans": w["spans"],
               "module_launches": {p: len(ms) for p, ms in inside.items()},
               "idle_gaps_us": {
                   k: v / 1e3 for k, v in sorted(
                       gaps_by_span(main, ops, lo, hi, clock).items(),
                       key=lambda kv: -kv[1])},
               # of the program with most device time in the window
               "scope_busy_us": {
                   k: v / 1e3 for k, v in
                   scope_busy_per_launch(ops, heaviest).items()}}
        row["host_path"] = host_path(main, w["spans"], lo, hi)
        if its:
            if clock and clock[1] >= clock[0]:
                row["wait_split"] = {
                    "launch_end_to_program_start": _summary(
                        [i["prog_a"] - d - i["launch_b"] for i in its]),
                    "program_end_to_wake": _summary(
                        [i["wait_b"] - (i["prog_b"] - d) for i in its]),
                    "uncertainty_us": (clock[1] - clock[0]) / 2e3}
        out["windows"][name] = row
    return out


def analysis() -> Optional[dict]:
    """The run's analysis (made once per process), or None where the
    rank has no trace."""
    path = trace_path()
    if path is None:
        return None
    if path not in _cache:
        _cache[path] = analyse(load(path))
        write(_cache[path])
    return _cache[path]


def window(name: str) -> Optional[dict]:
    a = analysis()
    return a["windows"].get(name) if a else None


def span(win: Optional[dict], name: Optional[str]) -> Optional[dict]:
    return win["spans"].get(name) if win else None


def api_span_name(stats: Dict[str, dict]) -> Optional[str]:
    """The API call a window's loop makes: the `ompi:api.*` span the
    benchmark's `collective call` holds (the most frequent one)."""
    api = {n: s["count"] for n, s in stats.items()
           if n.startswith(OMPI + "api.")}
    return max(api, key=api.get) if api else None


def slot_span_name(stats: Dict[str, dict]) -> Optional[str]:
    """The coll/xla slot under that call: the `ompi:coll_xla.*` span
    that is none of the helpers'."""
    helpers = {"to_global", "launch", "launch cold", "my_shard", "compile",
               "plan_build"}
    slots = {n: s["count"] for n, s in stats.items()
             if n.startswith(OMPI + "coll_xla.")
             and n[len(OMPI + "coll_xla."):] not in helpers}
    return max(slots, key=slots.get) if slots else None


def self_us(win: Optional[dict], name_of) -> Optional[float]:
    """Median self time of the span `name_of` picks in the window."""
    s = span(win, name_of(win["spans"])) if win else None
    return s["self_median_us"] if s else None


def scope_ms_per_step(scope: str) -> Optional[float]:
    """Device-busy ms per step (median over the traced steps) of the
    ops under `scope` in the train window; None where the trace names
    no model part at all."""
    win = window("train")
    if not win:
        return None
    busy = win["scope_busy_us"]
    if not set(busy) - {"all", "unscoped"}:
        return None
    return busy.get(scope, 0.0) / 1e3


def write(a: dict) -> None:
    from benchmark.common import say

    out = out_dir()
    if out is not None:
        with open(os.path.join(out, "host_path.json"), "w") as f:
            json.dump(a, f, indent=1)
    if a["init_phases_s"]:
        say(f"program: mpi.Init() on rank 0, seconds by phase "
            f"{a['init_phases_s']}, together "
            f"{sum(a['init_phases_s'].values()):.3f} (information)")
    clock = a["clock_bracket_ns"]
    if clock:
        say(f"program: device clock - host clock in [{clock[0]:.0f}, "
            f"{clock[1]:.0f}] ns (width {clock[1] - clock[0]:.0f} ns) from "
            f"{a['iterations_paired']} traced iterations (information)")
    for wname, w in a["windows"].items():
        for n, s in sorted(w["spans"].items(),
                           key=lambda kv: -kv[1]["median_us"]):
            say(f"program: window {wname}: {n}: {s['count']} x median "
                f"{s['median_us']:.3f} us p95 {s['p95_us']:.3f} us self "
                f"{s['self_median_us']:.3f} us (information)")
        if w["host_path"]:
            say(f"program: window {wname}: host path "
                f"{json.dumps(w['host_path'])}; wait split "
                f"{json.dumps(w.get('wait_split'))} (information)")
        say(f"program: window {wname}: launches {w['module_launches']}; "
            f"idle gaps us {w['idle_gaps_us']} (information)")
        if set(w["scope_busy_us"]) - {"all", "unscoped"}:
            say(f"program: window {wname}: device-busy us by scope "
                f"{ {k: round(v, 1) for k, v in sorted(w['scope_busy_us'].items(), key=lambda kv: -kv[1])} } "
                "(information)")
