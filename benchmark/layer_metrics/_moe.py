"""Device-busy time of a MoE train step's own parts, from the op paths
of a traced run: `moe_route`, `moe_dispatch`, `moe_experts`,
`moe_combine` (inside `layer_<i>/mlp`) and `qk_rope` (inside
`attn_proj`) — the `jax.named_scope` names ompi_tpu/models/transformer.py
and ompi_tpu/ops/moe.py give them. `_program.py`'s own table of scopes
is closed and stays as it is (these sit INSIDE `mlp` and `attn_proj`,
so its sums are unchanged); this file reads the same events through
`_program.load` with its own list of names.

One thing no scope can say: the TPU compiler turns `lax.ragged_dot`
into kernels of its own (`ragged-dot-*` custom calls) and gives them
no op path, so they carry no scope at all. They are the grouped
matmuls of `moe_experts` and nothing else in the step, and are counted
there by their name.

Per part: the union of its ops' intervals inside each launch of the
step's executable in the window `train`, forward and backward
together, the median over the launches. A trace without any of these
names (a program without the MoE path) gives None for every part.
"""

from __future__ import annotations

import statistics
from typing import Dict, Optional

from benchmark import trace_reduce as tr
from benchmark.layer_metrics import _program

PARTS = ("moe_route", "moe_dispatch", "moe_experts", "moe_combine",
         "qk_rope")
#: what libtpu calls the kernels it makes of lax.ragged_dot
GROUPED_MATMUL = "ragged-dot"
WINDOW = "train"

_cache: Dict[str, Optional[dict]] = {}


def parts_of(event) -> set:
    """The parts an op counts for (innermost and enclosing alike)."""
    path = event.stats.get("tf_op") or ""
    found = {w for w in _program.WORD.findall(path) if w in PARTS}
    # an event's name is the whole instruction, operands and all: the
    # op's own name ends at " = "
    own = event.name.split(" = ", 1)[0].lstrip("%")
    if own.startswith(GROUPED_MATMUL) or path.startswith(GROUPED_MATMUL):
        found.add("moe_experts")
    return found


def step_launches(events: dict):
    """(launches of the executable with most device time inside the
    window `train`, the chip's ops), as _program.analyse picks them."""
    threads = [_program.nest(ev) for ev in events["host"].values()]
    main = max(threads, key=len, default=[])
    win = next((s for s in main if s["name"] == tr.WINDOW + WINDOW), None)
    chip = next((lines for lines in events["chips"].values()
                 if lines.get(tr.MODULES_LINE)), None)
    if win is None or chip is None:
        return [], []
    inside: Dict[str, list] = {}
    for m in chip[tr.MODULES_LINE]:
        if win["a"] <= m.start_ns and m.end_ns <= win["b"]:
            inside.setdefault(_program.program_of(m.name), []).append(m)
    heaviest = max(inside.values(), default=[], key=lambda ms: sum(
        m.end_ns - m.start_ns for m in ms))
    return heaviest, chip.get(tr.OPS_LINE, [])


def busy_ms(events: dict) -> Optional[Dict[str, float]]:
    """part -> device-busy ms per step (median over the launches), or
    None where no op of the step names any part."""
    launches, ops = step_launches(events)
    per = []
    for m in launches:
        by: Dict[str, list] = {}
        for o in ops:
            iv = (max(o.start_ns, m.start_ns), min(o.end_ns, m.end_ns))
            if iv[1] > iv[0]:
                for part in parts_of(o):
                    by.setdefault(part, []).append(iv)
        per.append({p: sum(b - a for a, b in tr.union(ivs))
                    for p, ivs in by.items()})
    if not any(per):
        return None
    return {p: statistics.median(x.get(p, 0.0) for x in per) / 1e6
            for p in PARTS}


def part_ms(part: str) -> Optional[float]:
    """Device-busy ms per train step of `part` in this rank's trace
    (read once per process), None without a trace or without the
    names."""
    path = _program.trace_path()
    if path is None:
        return None
    if path not in _cache:
        _cache[path] = busy_ms(_program.load(path))
        if _cache[path] is not None:
            from benchmark.common import say

            say(f"program: window {WINDOW}: device-busy ms per step by "
                f"MoE part { {k: round(v, 3) for k, v in _cache[path].items()} } "
                "(information)")
    got = _cache[path]
    return None if got is None else got[part]
