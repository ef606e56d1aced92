"""Device-busy time of what the `mellum2-12b-a2.5b` configuration
brought to a train step, from the op paths of a traced run — the two
`jax.named_scope` names ompi_tpu/models/transformer.py gives the
attention core of a config that mixes kinds of attention, both INSIDE
`layer_<i>/attn_core` and around nothing the accepted readers sum, so
every accepted sum stands:

- `attn_window`: the scores, softmax and values of the layers under the
  sliding window (three of the cell's four), with the layout changes
  into and out of the kernels' head-major operands;
- `attn_full`: the same of the full layers (one).

Forward, recomputed forward and backward together. Read as `_nemo.py`
reads its parts (the union of a part's op intervals inside each launch
of the step's executable in the window `train`, the median over the
launches) through `_moe.step_launches` and `_program.load`. A trace
without either name (a program without the configuration) gives None
for both.

A kind's share of its roofline is compute-bound: the operations its
cores REQUIRE (benchmark/flops_mellum2.py: QK^T and PV over exactly the
pairs the mask keeps, every query head, forward and backward, nothing
recomputed) at the chip's peak bf16 rate (`_nemo.roofline`), over the
kind's device-busy time. A tile the kernels walk whole where the mask
keeps part of it, the forward made again and the layout changes only
lower the share: it cannot pass 100%.

`tile_facts` reads the program's two counters of the rule that picked
the windowed kernels' tile back into (tile, the (query tile, key tile)
pairs walked a layer).
"""

from __future__ import annotations

import math
import statistics
from typing import Dict, Optional

from benchmark import trace_reduce as tr
from benchmark.layer_metrics import _moe, _program

PARTS = ("attn_window", "attn_full")

_cache: Dict[str, Optional[dict]] = {}


def _parts_of(event) -> set:
    return set(_program.WORD.findall(
        event.stats.get("tf_op") or "")).intersection(PARTS)


def busy_ms(events: dict) -> Optional[Dict[str, float]]:
    """part -> device-busy ms per step (median over the launches), or
    None where no op of the step is under either name."""
    launches, ops = _moe.step_launches(events)
    mine = [(o, _parts_of(o)) for o in ops]
    mine = [(o, ps) for o, ps in mine if ps]
    per = []
    for m in launches:
        by: Dict[str, list] = {}
        for o, ps in mine:
            iv = (max(o.start_ns, m.start_ns), min(o.end_ns, m.end_ns))
            if iv[1] > iv[0]:
                for part in ps:
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

            say(f"program: window train: device-busy ms per step by kind "
                f"of attention { {k: round(v, 3) for k, v in _cache[path].items()} } "
                "(information)")
    got = _cache[path]
    return None if got is None else got[part]


def tile_facts(run: dict):
    """(the windowed kernels' tile, the tile pairs they walk a layer)
    from the counters `attn_window_tiles` / `attn_causal_tiles` /
    `attn_window_layers` of the step's one trace and the sequence
    length, or None where the program counted none (no kernel ran, or a
    program without the window)."""
    counters, seq = run["counters"], run["facts"].get("seq")
    layers = counters.get("attn_window_layers")
    walked, whole = (counters.get(n) for n in ("attn_window_tiles",
                                               "attn_causal_tiles"))
    if not (layers and walked and whole and seq):
        return None
    # the triangle's n (n + 1) / 2 tiles a layer give n, the tiles a side
    side = (math.isqrt(8 * whole // layers + 1) - 1) // 2
    return seq // side, walked / layers
