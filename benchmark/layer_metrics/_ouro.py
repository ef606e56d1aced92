"""Device-busy time of what the `ouro-2.6b` configuration brought to a
train step, from the op paths of a traced run — the `jax.named_scope`
names ompi_tpu/models/transformer.py gives them:

- `loop_<s>`: pass s over the layer list, AROUND the `layer_<i>/{ln,
  attn_proj, attn_core, mlp}` scopes the accepted readers sum (so their
  sums stand), with the norm between passes (`loop_<s>/ln`); forward,
  recomputed forward and backward together;
- `exit_gate` (`head_loss/exit_gate`: the gates, the exit distribution,
  its entropy and the blend of the exits' losses; the exits' heads
  themselves are `head_loss/exit_<s>` and are `head_loss_ms.train`'s).

Read as `_moe.py` reads its parts (the union of a part's op intervals
inside each launch of the step's executable in the window `train`, the
median over the launches) through `_moe.step_launches` and
`_program.load`. A trace without any of these names (a program without
the configuration) gives None.

The two counters are the program's own, as the runner
(runners/ouro_train.py) read them in set-up.
"""

from __future__ import annotations

import re
import statistics
from typing import Dict, Optional

from benchmark import trace_reduce as tr
from benchmark.layer_metrics import _moe, _program

LOOP = re.compile(r"^loop_(\d+)$")
GATE = "exit_gate"

_cache: Dict[str, Optional[dict]] = {}


def busy_ms(events: dict) -> Optional[Dict[str, float]]:
    """`loop_<s>` for every pass found and `exit_gate` -> device-busy ms
    per step (median over the launches), or None where no op of the
    step names a pass."""
    launches, ops = _moe.step_launches(events)
    words = [{w for w in _program.WORD.findall(o.stats.get("tf_op") or "")
              if w == GATE or LOOP.match(w)} for o in ops]
    per = []
    for m in launches:
        by: Dict[str, list] = {}
        for o, ws in zip(ops, words):
            iv = (max(o.start_ns, m.start_ns), min(o.end_ns, m.end_ns))
            if iv[1] > iv[0]:
                for w in ws:
                    by.setdefault(w, []).append(iv)
        per.append({p: sum(b - a for a, b in tr.union(ivs))
                    for p, ivs in by.items()})
    names = set().union(*per) if per else set()
    if not any(LOOP.match(n) for n in names):
        return None
    return {n: statistics.median(x.get(n, 0.0) for x in per) / 1e6
            for n in names | {GATE}}


def parts() -> Optional[Dict[str, float]]:
    """busy_ms of this rank's trace (read once per process), None
    without a trace or without the names."""
    path = _program.trace_path()
    if path is None:
        return None
    if path not in _cache:
        _cache[path] = busy_ms(_program.load(path))
        if _cache[path] is not None:
            from benchmark.common import say

            say(f"program: window train: device-busy ms per step by "
                f"pass and gate { {k: round(v, 3) for k, v in sorted(_cache[path].items())} } "
                "(information)")
    return _cache[path]


def passes() -> Optional[list]:
    """Device-busy ms per step of each pass, in the passes' order."""
    got = parts()
    if got is None:
        return None
    return [got[n] for n in sorted((n for n in got if LOOP.match(n)),
                                   key=lambda n: int(LOOP.match(n)[1]))]
