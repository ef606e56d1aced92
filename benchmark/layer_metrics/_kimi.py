"""Device-busy time of what the `kimi-vl-a3b` configuration brought to
a train step, from the op paths of a traced run — the
`jax.named_scope` names ompi_tpu/models/vision.py gives the tower, all
INSIDE the decoder's `embed` scope and AROUND the names the accepted
readers sum (`ln`, `attn_proj`, `attn_core`, `mlp`), so every accepted
sum stands and `unscoped_ms.train` takes none of the tower:

- `vit`: every op under `vision` (the patch product and position
  table, the 27 blocks, the final norm, merge, projector and the
  scatter into the sequence), forward, recomputed forward and backward
  together;
- `vit_attn`: the ops under `vision/.../attn_core` (the tower's
  attention: scores, softmax, AV; on the TPU the blockwise kernels);
- `vit_merge`: the ops under `vit_embed` or `vit_merge` (the ends of
  the tower: what is not a block).

Read as `_moe.py` reads its parts (the union of a part's op intervals
inside each launch of the step's executable in the window `train`, the
median over the launches) through `_moe.step_launches` and
`_program.load`. A trace without any of these names (a program without
the configuration) gives None for every part.

The rooflines are compute-bound shares: required operations
(`facts[...]`, benchmark/flops_kimivl.py) over the chip's peak bf16
rate, as a share of the part's busy time. The decoder's attention has
no scope of its own apart from the tower's: its busy time is
`attn_core_ms.train`'s (every `attn_core` of the step) less the
tower's.
"""

from __future__ import annotations

import statistics
from typing import Dict, Optional

from benchmark import trace_reduce as tr
from benchmark.layer_metrics import _moe, _program

VISION, CORE = "vision", "attn_core"
ENDS = {"vit_embed", "vit_merge"}
PARTS = ("vit", "vit_attn", "vit_merge")

_cache: Dict[str, Optional[dict]] = {}


def _parts_of(event) -> set:
    words = set(_program.WORD.findall(event.stats.get("tf_op") or ""))
    if VISION not in words:
        return set()
    found = {"vit"}
    if CORE in words:
        found.add("vit_attn")
    if words & ENDS:
        found.add("vit_merge")
    return found


def busy_ms(events: dict) -> Optional[Dict[str, float]]:
    """part -> device-busy ms per step (median over the launches), or
    None where no op of the step is the tower's."""
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

            say(f"program: window train: device-busy ms per step by "
                f"tower part { {k: round(v, 3) for k, v in _cache[path].items()} } "
                "(information)")
    got = _cache[path]
    return None if got is None else got[part]


def decoder_attn_ms() -> Optional[float]:
    """Every `attn_core` of the step less the tower's."""
    whole, tower = _program.scope_ms_per_step(CORE), part_ms("vit_attn")
    if whole is None or tower is None:
        return None
    return whole - tower


def roofline(run: dict, ms: Optional[float], fact: str) -> Optional[float]:
    """facts[fact] operations at the chip's peak bf16 rate, as a share
    of `ms`, in percent."""
    flops = run["facts"].get(fact)
    if not ms or not flops or not run.get("peaks"):
        return None
    return 100.0 * flops / run["peaks"]["bf16_flops_per_s"] * 1e3 / ms
