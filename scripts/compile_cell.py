#!/usr/bin/env python3
"""What the chip's compiler makes of a train cell's step. (No chip.)

Compiles the runner's ``build_step`` for ONE chip of a described (not
attached) v5e on abstract parameters and one abstract batch at the
cell's full shape, the static rules answering as on a v5e
(``lower_cmp.as_on_a_v5e``), and prints one line a cell:

    <cell> temp <bytes> arguments <bytes> pathless copies <n> <bytes>
        code <bytes> fusions <n>

``temp`` + ``arguments`` is what the chip then reads as
``memory_peak_bytes`` (PR 44: 10.25 GB here, 10.26 there). ``pathless
copies`` are the entry computation's ``copy`` instructions of 32 MB
and more that carry no ``jax.named_scope`` path (the scheduler's
prefetches, ``copy-start``, are not counted: every step has dozens):
what a trace books under ``unscoped_ms.train`` (PR 44: a sum written
as a Python loop over slices added 31 of them and 0.5 GB, and the chip
read +13 ms there). ``code`` is the executable's own size on the chip
and ``fusions`` the fusion instructions of its text (PR 30: 1.36 GB and
10,720 in ``glm5-train-t4096``). ``--keep none`` compiles the step
with nothing kept by the recomputation rule (what a configuration is
sized by before the rule is given the room that is left). ``--out DIR``
keeps the compiled text.

    python scripts/compile_cell.py [--root CHECKOUT] [--out DIR]
        [--keep none] cell ...

~1.5 min and ~4 GB of host memory a cell; ONE such process at a time
(it holds the TPU library). Proves no time and no result.
"""

import argparse
import os
import re
import sys

_COPY = re.compile(r"^  (?:ROOT )?%\S+ = (\w+)\[([\d,]+)\]\S* copy\(")
_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1,
          "s8": 1, "u8": 1}


def pathless_copies(text: str, least: int = 32 << 20):
    """(how many, their bytes) of the entry computation's copies of
    `least` bytes and more without an op path."""
    sizes = []
    for line in text[text.index("\nENTRY "):].splitlines():
        found = _COPY.match(line)
        if found and 'op_name="' not in line:
            n = _BYTES.get(found.group(1), 4)
            for dim in found.group(2).split(","):
                n *= int(dim)
            if n >= least:
                sizes.append(n)
    return len(sizes), sum(sizes)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), help="the checkout to compile")
    ap.add_argument("--out", help="keep each cell's compiled text here")
    ap.add_argument("--keep", choices=["rule", "none"], default="rule",
                    help="what a recomputed layer keeps: the rule's "
                    "answer, or nothing")
    ap.add_argument("cells", nargs="+")
    args = ap.parse_args()
    root = os.path.abspath(args.root)
    out = args.out and os.path.abspath(args.out)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)  # the checkout's program and benchmark
    os.chdir(root)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import lower_cmp
    from benchmark import manifest as mf
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    manifest = mf.load()
    lower_cmp.as_on_a_v5e()
    if args.keep == "none":
        from ompi_tpu.models import remat

        remat.remat_keep = lambda *a, **kw: ()
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    for name in args.cells:
        step, *shapes = lower_cmp.step_and_shapes(name, manifest, mf)
        compiled = step.lower(*jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
            shapes)).compile()
        memory, text = compiled.memory_analysis(), compiled.as_text()
        if out:
            os.makedirs(out, exist_ok=True)
            with open(os.path.join(out, name + ".hlo"), "w") as f:
                f.write(text)
        print(name, "temp", memory.temp_size_in_bytes, "arguments",
              memory.argument_size_in_bytes, "pathless copies",
              *pathless_copies(text), "code",
              memory.generated_code_size_in_bytes, "fusions",
              len(re.findall(r" fusion\(", text)), flush=True)


if __name__ == "__main__":
    main()
