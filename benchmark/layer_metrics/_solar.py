"""Device-busy time of what the `solar-open2-250b` configuration
brought to a train step, from the op paths of a traced run — the
`jax.named_scope` names ompi_tpu/models/transformer.py and
ompi_tpu/ops/kda.py give a delta-rule layer, all INSIDE `layer_<i>` and
AROUND nothing the accepted readers sum, so every accepted sum stands:

- `kda`: every op under `layer_<i>/kda` (the Kimi-Delta-Attention
  mixer of the three `K` layers), forward, recomputed forwards and
  backward together;
- `kda_proj` (the q, k, v products, the decay's and the gate's
  bottlenecks, beta's and the output product, with the residual add),
  `kda_conv` (the three causal depthwise convolutions and their SiLU),
  `kda_core` (the l2 norms, softplus and the log-decay, its sums and
  exponentials, the pair sums, the intra-chunk system, the carry's
  kernels and the outputs), `kda_gate_norm` (the per-head RMSNorm and
  the gate): its four parts.

Read as `_nemo.py` reads its parts (the union of a part's op intervals
inside each launch of the step's executable in the window `train`, the
median over the launches) through `_moe.step_launches` and
`_program.load`. A trace without any of these names (a program without
the configuration) gives None for every part.

The core's share of its roofline takes the larger of two least times,
both of REQUIRED work (benchmark/flops_solar2.py): the chunked form's
operations at the chip's peak bf16 rate and its bytes at the chip's
peak HBM rate (`_nemo.roofline`). The attention layer's share is
gqa_attn_roofline.py's: this model has one attention layer.
"""

from __future__ import annotations

import statistics
from typing import Dict, Optional

from benchmark import trace_reduce as tr
from benchmark.layer_metrics import _moe, _program

PARTS = ("kda", "kda_proj", "kda_conv", "kda_core", "kda_gate_norm")

_cache: Dict[str, Optional[dict]] = {}


def _parts_of(event) -> set:
    words = set(_program.WORD.findall(event.stats.get("tf_op") or ""))
    return words.intersection(PARTS) if "kda" in words else set()


def busy_ms(events: dict) -> Optional[Dict[str, float]]:
    """part -> device-busy ms per step (median over the launches), or
    None where no op of the step is a delta-rule layer's."""
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
                f"delta-rule part { {k: round(v, 3) for k, v in _cache[path].items()} } "
                "(information)")
    got = _cache[path]
    return None if got is None else got[part]
