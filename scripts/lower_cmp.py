#!/usr/bin/env python3
"""Is a train cell's step still the same program on the TPU? (No chip.)

For each train cell of ``BENCHMARK.json`` (or the cells named), lower the
runner's ``build_step`` for ``("tpu",)`` on abstract parameters and one
abstract batch at the cell's full shape — the static rules that choose
a kernel (``att.blockwise_tile``, ``segment_tile``, ``dsa_tile``,
``moe.grouped_tiles``, ``expert_width_pad``, ``row_reduce_kernel``,
``reduce_tiles``, ``ssm.scan_tile``,
``ssm.conv_tile``, ``kda.carry_tile``) and the
device's memory limit answering as on a v5e — and print one line a cell:

    <cell> text <sha256> scopes <sha256> <characters> {counters}

``text`` is the lowered module without locations and backend configs
(a Pallas kernel's carries file lines), its private functions numbered
by first appearance (jax numbers them with one counter a module, which
any new equation moves: tests/lowered_text.py). ``scopes`` is every
operation's ``jax.named_scope`` path in order, source lines cut: what
``benchmark/layer_metrics/`` reads a trace by. ``counters`` is what the
step's one trace counted of the path-choosing pvars.

    python scripts/lower_cmp.py [--root CHECKOUT] [--out DIR] [cell ...]

Run it from two checkouts (``--root``: a ``git worktree`` or ``git
archive`` of the parent) and compare the lines; equal hashes say the
chip is handed the parent's program. ``--out DIR`` keeps the canonical
texts for a ``diff``. Reads ``benchmark/``, edits nothing. ~40 s.
"""

import argparse
import hashlib
import importlib
import inspect
import os
import re
import sys

#: a v5e's ``memory_stats()["bytes_limit"]``
V5E_LIMIT = 16_909_336_064
#: (module under ompi_tpu, rule): asked about "tpu" whatever the backend
RULES = (("ops.attention", "blockwise_tile"),
         ("ops.attention", "segment_tile"),
         ("ops.attention", "dsa_tile"), ("ops.moe", "grouped_tiles"),
         ("ops.moe", "expert_width_pad"), ("ops.moe", "row_reduce_kernel"),
         ("ops.moe", "reduce_tiles"), ("ops.ssm", "scan_tile"),
         ("ops.ssm", "conv_tile"), ("ops.kda", "carry_tile"))
#: the pvars that say which path a traced layer took
COUNTED = ("moe_bounded_layers", "moe_full_layers",
           "moe_row_sum_gather_layers", "moe_row_sum_product_layers",
           "moe_row_reduce_kernel_layers", "moe_row_reduce_xla_layers",
           "moe_grouped_kernel_layers", "moe_ragged_dot_layers",
           "attn_blockwise_layers", "attn_reference_layers",
           "attn_window_layers", "attn_full_layers", "attn_window_tiles",
           "attn_causal_tiles", "attn_dsa_kernel_layers", "ssm_scan_kernel_layers",
           "ssm_scan_product_layers", "conv_kernel_layers",
           "conv_shifted_layers", "kda_carry_kernel_layers",
           "kda_carry_scan_layers", "kda_core_kernel_layers",
           "attn_gated_layers", "attn_head_norm_layers",
           "attn_unrotated_layers", "mtp_full_layers", "mtp_window_layers",
           "remat_kept_applications",
           "remat_whole_applications", "remat_kept_bytes")

_NUMBERED = re.compile(r"@([A-Za-z_][A-Za-z_0-9]*?)_(\d+)\b")
_LOC_LINE = re.compile(r"^#loc(\d*) = loc\((.*)\)$")
_NAMED = re.compile(r'^"([^"]*)"\(')
_REF = re.compile(r"#loc(\d*)")


def canonical(text: str) -> str:
    """The lowered text without what may differ between two checkouts
    of one program: locations, backend configs, jax's numbering."""
    text = re.sub(r"loc\([^\n]*\)", "", text)
    text = re.sub(r"#loc[^\n]*\n", "", text)
    text = re.sub(r'backend_config = "[^"]*"', "", text)
    text = re.sub(r"backend_config = \{[^\n]*\}", "", text)
    seen = {}
    return _NUMBERED.sub(lambda f: seen.setdefault(
        f.group(0), f"@{f.group(1)}_{len(seen)}"), text)


def scopes(text: str) -> str:
    """One line an operation of a text with debug information: the name
    stack its location carries (none where the location is a bare
    traceback: an operation made under no scope, whose frames are the
    callers' function names and may change as they like)."""
    table = {}
    for line in text.splitlines():
        found = _LOC_LINE.match(line)
        if found:
            table[found.group(1)] = found.group(2)

    def name(ref):
        named = _NAMED.match(table.get(ref, ""))
        return named.group(1) if named else ""

    return "\n".join(
        name(_REF.findall(line)[-1]) for line in text.splitlines()
        if not line.startswith("#loc") and "loc(#loc" in line)


def as_on_a_v5e():
    """Patch the static rules and the memory limit to a v5e's answers."""
    for module, rule in RULES:
        try:
            mod = importlib.import_module("ompi_tpu." + module)
        except ImportError:
            continue
        if hasattr(mod, rule):
            setattr(mod, rule, lambda backend, *a, _rule=getattr(mod, rule),
                    **kw: _rule("tpu", *a, **kw))
    for module in ("models.remat", "models.transformer"):
        try:
            mod = importlib.import_module("ompi_tpu." + module)
        except ImportError:
            continue
        if "_memory_limit" in vars(mod):
            mod._memory_limit = lambda: V5E_LIMIT


def step_and_shapes(name: str, manifest, mf, rehearsal: bool = False):
    """(cell `name`'s jitted step, its abstract parameters, one
    abstract batch's tokens and labels); `rehearsal`: of its toy twin
    (`benchmark/configs/<config>.rehearsal.json`)."""
    import jax

    from benchmark import weights

    _, workload, traffic, config, _ = mf.cell_inputs(manifest, name,
                                                     rehearsal)
    runner = importlib.import_module("benchmark.runners." + workload["runner"])
    own = [m for m in vars(runner).values() if inspect.ismodule(m)
           and m.__name__.startswith("benchmark.weights_")]
    w = own[0] if own else weights
    sizes = runner.model_sizes(config)
    params = jax.eval_shape(lambda: w.device_init(sizes, 0))
    if w is not weights and hasattr(w, "batches"):  # more than token ids
        toks, labs = jax.eval_shape(lambda: w.batches(sizes, traffic, 0))
    else:
        toks, labs = jax.eval_shape(lambda: weights.batches(
            sizes["vocab"], traffic["n_batches"], traffic["batch"],
            traffic["seq"], 0))
    return (runner.build_step(sizes, traffic["lr"]), params, toks[0],
            labs[0])


def lowered(name: str, manifest, mf):
    """(text with debug information, what the trace counted) of cell
    `name`'s step, lowered for the TPU on abstract arrays."""
    from ompi_tpu.core import pvar

    step, *shapes = step_and_shapes(name, manifest, mf)
    names = tuple(n for n in COUNTED if n in pvar.WELL_KNOWN)
    before = {n: pvar.read(n) for n in names}
    text = step.trace(*shapes).lower(lowering_platforms=("tpu",)).as_text(
        debug_info=True)
    return text, {n: pvar.read(n) - was for n, was in before.items()
                  if pvar.read(n) != was}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), help="the checkout to lower")
    ap.add_argument("--out", help="keep each cell's canonical text and "
                    "scopes in this directory")
    ap.add_argument("cells", nargs="*")
    args = ap.parse_args()
    root = os.path.abspath(args.root)
    sys.path.insert(0, root)
    os.chdir(root)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from benchmark import manifest as mf

    manifest = mf.load()
    as_on_a_v5e()
    for name in args.cells or [
            w["name"] for w in manifest["workloads"]
            if mf.workload_file(w["name"])["runner"] != "osu_collective"]:
        text, counted = lowered(name, manifest, mf)
        plain, paths = canonical(text), scopes(text)
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            for kind, body in (("text", plain), ("scopes", paths)):
                with open(os.path.join(args.out, f"{name}.{kind}"), "w") as f:
                    f.write(body)
        print(name, "text", hashlib.sha256(plain.encode()).hexdigest(),
              "scopes", hashlib.sha256(paths.encode()).hexdigest(),
              len(plain), counted, flush=True)


if __name__ == "__main__":
    main()
