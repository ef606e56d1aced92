"""The comparison that decides `correct`: numbers, each beside a limit
of its own. A check is (name, value, limit); it holds when the value is
a finite number no greater than the limit. Every run prints them all.
"""

from __future__ import annotations

import math
from typing import List, Tuple

Check = Tuple[str, float, float]


def holds(check: Check) -> bool:
    _, value, limit = check
    return isinstance(value, (int, float)) and math.isfinite(value) \
        and value <= limit


def verdict(checks: List[Check], say) -> bool:
    ok = True
    for c in checks:
        good = holds(c)
        ok &= good
        say(f"check {c[0]}: {c[1]!r} against limit {c[2]!r} "
            f"{'ok' if good else 'NOT CORRECT'}")
    return ok and bool(checks)


def leaf_delta_norms(now, start):
    """Per leaf, the float32 norm of (now - start), as one device
    vector in tree order. How far the optimizer moved each leaf: after
    one step of plain SGD this is lr x the norm of the first gradient
    as the optimizer got it."""
    import jax
    import jax.numpy as jnp

    def norms(a, b):
        return jnp.stack([
            jnp.sqrt(jnp.sum((x.astype(jnp.float32)
                              - y.astype(jnp.float32)) ** 2))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])

    return jax.jit(norms)(now, start)


def _leaf_gaps(program, reference):
    import numpy as np

    p = np.asarray(program, np.float64)
    r = np.asarray(reference, np.float64)
    floor = float(np.median(r))
    if not floor > 0:
        floor = float(r.max())
    if not floor > 0:
        return None  # a reference that moved nothing
    return np.abs(p - r) / np.maximum(r, floor)


def rms_leaf_gap(program, reference) -> float:
    """The same per-leaf gaps as worst_leaf_gap, taken by their root
    mean square over the leaves: the worst leaf swings from seed to
    seed by its nature, this does not (PERF.md section 2)."""
    import numpy as np

    gaps = _leaf_gaps(program, reference)
    return float("inf") if gaps is None else float(
        np.sqrt(np.mean(gaps ** 2)))


def worst_leaf_gap(program, reference) -> float:
    """The largest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger (some gradients are all but
    zero). The gap between norms, not the norm of the difference."""
    gaps = _leaf_gaps(program, reference)
    return float("inf") if gaps is None else float(gaps.max())


def rel_gap(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)
