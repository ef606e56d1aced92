"""Device-busy time of the parts of a train step that the `glm-5`
configuration brought, from the op paths of a traced run — the
`jax.named_scope` names ompi_tpu/models/transformer.py gives them, all
INSIDE scopes the accepted readers already sum (`attn_proj`,
`attn_core`, `mlp`, `layer_<n>`, `head_loss`), so those sums stand:

- `mla_proj`: latent attention's projections (`attn_proj/{mla_q,
  mla_kv, mla_o}`);
- `dsa_index`: the indexer (`attn_proj/dsa_index_proj`: its three
  projections; `attn_core/dsa_index`: the score products summed over
  its heads, and the top-k selection);
- `dsa_attend` (`attn_core/dsa_attend`: attention over the selected
  keys), `dsa_kl` (`attn_core/dsa_kl`: the indexer's loss);
- `moe_shared` (`mlp/moe_shared`: the shared expert);
- `mtp`: the multi-token-prediction module — every op of the
  `layer_<n>` that holds `attn_proj/mtp_merge`, and its head
  (`head_loss/mtp`).

Read as `_moe.py` reads its parts (the union of a part's op intervals
inside each launch of the step's executable in the window `train`,
forward, recomputation and backward together, the median over the
launches) through `_moe.step_launches` and `_program.load`. A trace
without any of these names (a program without the configuration)
gives None for every part.
"""

from __future__ import annotations

import re
import statistics
from typing import Dict, Optional

from benchmark import trace_reduce as tr
from benchmark.layer_metrics import _moe, _program

#: part -> the scope names that count for it
PARTS = {
    "mla_proj": {"mla_q", "mla_kv", "mla_o"},
    "dsa_index": {"dsa_index_proj", "dsa_index"},
    "dsa_attend": {"dsa_attend"},
    "dsa_kl": {"dsa_kl"},
    "moe_shared": {"moe_shared"},
    "mtp": {"mtp", "mtp_merge"},
}
MERGE = "mtp_merge"
LAYER = re.compile(r"^layer_\d+$")

_cache: Dict[str, Optional[dict]] = {}


def _words(event) -> set:
    return set(_program.WORD.findall(event.stats.get("tf_op") or ""))


def busy_ms(events: dict) -> Optional[Dict[str, float]]:
    """part -> device-busy ms per step (median over the launches), or
    None where no op of the step names any part."""
    launches, ops = _moe.step_launches(events)
    words = [_words(o) for o in ops]
    # the MTP module is a whole layer: the one its merge sits in
    mtp_layers = {w for ws in words if MERGE in ws for w in ws
                  if LAYER.match(w)}
    per = []
    for m in launches:
        by: Dict[str, list] = {}
        for o, ws in zip(ops, words):
            iv = (max(o.start_ns, m.start_ns), min(o.end_ns, m.end_ns))
            if iv[1] <= iv[0]:
                continue
            for part, names in PARTS.items():
                if ws & names or (part == "mtp" and ws & mtp_layers):
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
                f"GLM part { {k: round(v, 3) for k, v in _cache[path].items()} } "
                "(information)")
    got = _cache[path]
    return None if got is None else got[part]


def roofline(run: dict, part: str, fact: str) -> Optional[float]:
    """Required operations (`facts[fact]`, benchmark/flops_glm5.py)
    over the chip's peak bf16 rate, as a share of the part's busy
    time, in percent."""
    ms = part_ms(part)
    flops = run["facts"].get(fact)
    if not ms or not flops or not run.get("peaks"):
        return None
    return 100.0 * flops / run["peaks"]["bf16_flops_per_s"] * 1e3 / ms


def share(run: dict, part: str, whole: str) -> Optional[float]:
    """counters[part] / counters[whole] (the program's set-up probes),
    None where the program counted neither."""
    counters = run["counters"]
    if not counters.get(whole) or part not in counters:
        return None
    return counters[part] / counters[whole]
