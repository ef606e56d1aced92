"""Device-busy time of what the `nemotron-3-nano-30b-a3b` configuration
brought to a train step, from the op paths of a traced run — the
`jax.named_scope` names ompi_tpu/models/transformer.py and
ompi_tpu/ops/ssm.py give a state-space layer, all INSIDE `layer_<i>`
and AROUND nothing the accepted readers sum, so every accepted sum
stands:

- `ssm`: every op under `layer_<i>/ssm` (the Mamba-2 mixer of the four
  `M` layers), forward, recomputed forward and backward together;
- `ssm_proj` (both products, `in_proj` and `out_proj`), `ssm_conv` (the
  causal depthwise convolution and its SiLU), `ssm_scan` (softplus, the
  chunked scan's decays, its four products and the carry between
  chunks), `ssm_gate_norm` (the gate and the grouped RMSNorm): its
  four parts.

Read as `_moe.py` reads its parts (the union of a part's op intervals
inside each launch of the step's executable in the window `train`, the
median over the launches) through `_moe.step_launches` and
`_program.load`. A trace without any of these names (a program without
the configuration) gives None for every part.

The scan's share of its roofline takes the larger of two least times,
both of REQUIRED work (benchmark/flops_nemotron.py): its operations at
the chip's peak bf16 rate and its bytes at the chip's peak HBM rate.
The attention layer's share is compute-bound: required operations of
the causal 32-over-2 attention at the peak bf16 rate over every
`attn_core` of the step (`attn_core_ms.train`: this model has one kind
of attention).
"""

from __future__ import annotations

import statistics
from typing import Dict, Optional

from benchmark import trace_reduce as tr
from benchmark.layer_metrics import _moe, _program

PARTS = ("ssm", "ssm_proj", "ssm_conv", "ssm_scan", "ssm_gate_norm")

_cache: Dict[str, Optional[dict]] = {}


def _parts_of(event) -> set:
    words = set(_program.WORD.findall(event.stats.get("tf_op") or ""))
    return words.intersection(PARTS) if "ssm" in words else set()


def busy_ms(events: dict) -> Optional[Dict[str, float]]:
    """part -> device-busy ms per step (median over the launches), or
    None where no op of the step is a state-space layer's."""
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
                f"state-space part { {k: round(v, 3) for k, v in _cache[path].items()} } "
                "(information)")
    got = _cache[path]
    return None if got is None else got[part]


def roofline(run: dict, ms: Optional[float], flops: str,
             nbytes: Optional[str] = None) -> Optional[float]:
    """The least time of facts[flops] operations at the chip's peak
    bf16 rate — and, where `nbytes` is given, of facts[nbytes] bytes at
    its peak HBM rate, whichever is longer — as a share of `ms`, in
    percent."""
    peaks, facts = run.get("peaks"), run["facts"]
    if not ms or not peaks or not facts.get(flops):
        return None
    least_s = facts[flops] / peaks["bf16_flops_per_s"]
    if nbytes is not None:
        if not facts.get(nbytes) or not peaks.get("hbm_bytes_per_s"):
            return None
        least_s = max(least_s, facts[nbytes] / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s * 1e3 / ms
