"""What a recomputed application keeps for its backward pass.

A model that recomputes (``Config.remat``) runs each application of a
layer — a decoder layer, a block of the vision tower — as ONE jitted
function whose backward pass is given the application's input and the
values it made under the names in a keep-set, and makes the rest
again. This module owns the three things both towers share, and knows
neither a ``Config`` nor a tower: the NAMES a layer gives what its
backward pass reads (beside those of ops/attention.py and ops/ssm.py),
the RULE that chooses the keep-set (`remat_order`, `whole_step_peak`,
`remat_keep`) from per-application costs handed in as data
(`Application`), and the WRAPPER (`Recomputed`) with its counters.
models/vision.py and models/transformer.py import it; it imports
neither.
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional, Sequence, Tuple

import jax

from ompi_tpu.core import pvar

#: Names (``jax.ad_checkpoint.checkpoint_name``) of what a layer
#: application makes that its backward pass reads, beside those
#: ops/attention.py gives (QKV, ATTN_OUT, DSA_PROBS): a sub-layer's
#: output before its residual add and output norm; the FFN's (and a
#: shared expert's) up-projections; latent attention's down-projections
#: before their norms; the indexer's scores and the selection.
ATTN_PROJ_OUT = "attn_proj_out"
MLP_OUT = "mlp_out"
MLP_UP = "mlp_up"
MLA_LATENTS = "mla_latents"
DSA_SELECT = "dsa_select"

#: The share of the device's memory limit the reckoned peak may reach.
#: The rest is the room for what the reckoning misses: on a v5e
#: (16.9 GB) 2.5 GB, where the four full-size compiles of PR 35 put
#: the compiled peak between 0.9 GB under and 1.0 GB over the
#: reckoned one (PERF.md section 6; tests/test_remat_policy.py holds
#: the rule to twice that).
REMAT_SHARE = 0.85


class Application(NamedTuple):
    """What ONE application of a layer costs the rule: the bytes it
    holds under each name its backward pass reads, the operations of
    the PRODUCTS that pass need not make again where a name is kept
    (each name as if kept alone), and the bytes of its input."""
    sizes: Dict[str, int]
    spared: Dict[str, int]
    input_bytes: int


def remat_order(applications: Sequence[Application]):
    """[(name, bytes all the step's applications hold under it)], the
    dearest first: by the operations a name spares per byte it holds
    (over a product's result that is 2 x the contracted width / the
    item size: 16,384 wide, GLM-5's attention output projection stands
    first; 2,048 wide, Ouro's stands behind its attention and its FFN's
    output), of equals the smaller first. A name is one entry whatever
    kinds of layer make it: a tower's blocks and the decoder's layers
    keep or drop it together."""
    held, spared = {}, {}
    for sizes, ops, _ in applications:
        for name, size in sizes.items():
            held[name] = held.get(name, 0) + size
            spared[name] = spared.get(name, 0) + ops[name]
    return sorted(held.items(),
                  key=lambda kv: (-spared[kv[0]] / kv[1], kv[1], kv[0]))


def whole_step_peak(applications: Sequence[Application],
                    fixed_bytes: int) -> int:
    """The bytes a train step is reckoned to hold at its peak with
    every application recomputed from its input alone: `fixed_bytes`
    (what the caller's program holds whatever its layers do: the
    parameters, their gradients, the loss's logits); an input per
    application; the values ONE application's backward pass makes
    again — the application that makes most — and a cotangent for
    each."""
    again = max(sum(a.sizes.values()) for a in applications)
    return fixed_bytes + sum(a.input_bytes for a in applications) + 2 * again


def remat_keep(applications: Sequence[Application], fixed_bytes: int,
               limit: Optional[int]) -> Tuple[str, ...]:
    """The rule that says what a recomputed application keeps for its
    backward pass. It starts from `whole_step_peak`, walks the names in
    `remat_order`, adding what all the applications hold under a name,
    and stops before the first name that would take the reckoned peak
    past `REMAT_SHARE` of the limit. No limit (the CPU) or no room: the
    empty tuple, every application recomputed whole."""
    if not limit:
        return ()
    peak = whole_step_peak(applications, fixed_bytes)
    keep = []
    for name, held in remat_order(applications):
        if peak + held > REMAT_SHARE * limit:
            break
        keep.append(name)
        peak += held
    return tuple(keep)


def _memory_limit() -> Optional[int]:
    """The bytes a process may hold on its first device (None where the
    backend does not say: the CPU)."""
    stats = jax.local_devices()[0].memory_stats() or {}
    return stats.get("bytes_limit")


def recomputed(fn, keep: Tuple[str, ...], static_argnums=()):
    """`fn`, a function of arrays, recomputed in the backward pass but
    for the names in `keep`."""
    # no policy where nothing is kept: under one, even an empty one, jax
    # splits every inner jitted function afresh at each call site
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.save_only_these_names(*keep)
        if keep else None, static_argnums=static_argnums)


def kept(fn, keep: Tuple[str, ...], static_argnums=()):
    """`recomputed` as ONE jitted function: with a policy jax splits
    every inner jitted function (the attention kernels', the
    activation's) into what is kept and what is made again, afresh at
    each call site — 48 applications of the same layer traced, split
    and lowered 48 times, kernels and all (ouro-train-t4096's step:
    17 s of tracing and 6 of lowering where the recomputation with no
    policy, which splits nothing, takes 6 and 2; PERF.md section 6,
    PR 35). Behind a `jit` of its own the function is traced,
    linearized, split, transposed and lowered once per shape, and
    called; XLA inlines the calls."""
    return jax.jit(recomputed(fn, keep, static_argnums),
                   static_argnums=static_argnums)


class Recomputed:
    """`fn` as the one jitted, recomputed function (`kept`) that all
    its applications in ONE trace of a step call — made anew per trace,
    so a later trace sees the rules and the device as they are then.
    jax traces the function when it likes (once per shape behind the
    `jit`), so what `fn` counts (pvars) while traced on arguments of
    these shapes and types is set aside and counted once per
    APPLICATION. `sizes`: where the applications are the rule's (an
    `Application.sizes`), each also counts ``remat_kept_applications``
    or ``remat_whole_applications`` and ``remat_kept_bytes``, the
    rule's reckoning of what it holds. The function keeps its NAME: it
    is part of the lowered text (``func.func private @layer``)."""

    def __init__(self, fn, keep: Tuple[str, ...],
                 sizes: Optional[Dict[str, int]] = None, static_argnums=()):
        self.keep, self._sizes = keep, sizes
        counted = self._counted = {}  # the closure holds this, not self

        @functools.wraps(fn)
        def counting(*args):
            with pvar.captured() as counts:
                out = fn(*args)
            counted[_shapes(args)] = counts
            return out

        self._fn = kept(counting, keep, static_argnums)

    def __call__(self, *args):
        if self._sizes is not None:
            pvar.record("remat_kept_applications" if self.keep
                        else "remat_whole_applications")
            pvar.record("remat_kept_bytes", sum(
                self._sizes.get(name, 0) for name in self.keep))
        out = self._fn(*args)
        for name, count in self._counted[_shapes(args)].items():
            pvar.record(name, count)
        return out


def _shapes(args):
    return tuple((getattr(x, "shape", ()), getattr(x, "dtype", type(x)))
                 for x in jax.tree.leaves(args))
