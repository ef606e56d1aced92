#!/usr/bin/env python3
"""The expert layer's per-token sum alone on one chip: XLA's and the kernel's.

At the seven expert cells' shapes (`CELLS`: tokens, k, the bound of
rows, the model's and the experts' widths, the experts held of how
many) two readings, every array MADE INSIDE the timed program (XLA
places a source that its own program makes where it likes, a jit
argument it cannot: PERF.md 7 "From PR 44" (2)):

- ``reduce`` (the default): the ``w2`` product and the weighted sum
  behind it (`moe._weigh_held`, the combine) or the plain one
  (`moe._sum_held`, the dispatch's transpose) — the product in the plain
  layout with XLA's row gather and float32 sum, against the product in
  the PACKED layout (``grouped_matmul.gmm(packed=True)``) with
  ``grouped_matmul.row_reduce``. Four programs a sum: each product
  alone, each product and its sum; a sum's time is the difference
  (`reduce_ms`), the packed epilogue's cost the difference of the two
  products, and the two results' largest gap is printed (0: bit-equal).
- ``layer``: ``jax.value_and_grad`` of one recomputed
  (``jax.checkpoint``) `moe.sorted_moe_ffn` as the rules have it with
  `moe.row_reduce_kernel` answering no (XLA's gather and sum; the 0/1
  product at glm5-train-t4096's shape), and with the kernel's sums.

    chiprun -- python scripts/row_reduce_probe.py [--what reduce layer]
        [--cells mellum2-train-t16384 ...] [--calls 10]

``--interpret 1`` is its CPU twin at a toy size (results, no times).
PERF.md 6 (PR 51) quotes the table; the result lines are also kept as
JSON (``--out``).
"""

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: (tokens, k, bound, model width, experts' width as the kernels see it,
#: experts held, of how many): benchmark/configs/*.json through each
#: runner's `model_sizes`, `moe.held_rows_bound` and
#: `moe.expert_width_pad`
CELLS = {
    "mellum2-train-t16384": (16384, 8, 131072, 2304, 896, 64, 64),
    "solar2-train-t8192": (8192, 8, 32768, 4096, 1280, 40, 320),
    "kexaone-train-t8192": (8192, 8, 16384, 6144, 2048, 8, 128),
    "nemotron-train-t8192": (8192, 6, 24576, 2688, 1920, 16, 128),
    "glm5-train-t4096": (4096, 8, 4096, 6144, 2048, 8, 256),
    "olmoe-train-t4096": (4096, 8, 32768, 2048, 1024, 64, 64),
    "kimivl-train-t4096": (4096, 6, 24576, 2048, 1408, 64, 64),
}
TOY = (256, 4, 1024, 256, 128, 4, 4)
#: (probe) further settings of the kernel's constants it is timed at
VARIANTS = []
#: the sums read: the combine's weighted one, the dispatch's plain one
SUMS = [True, False]


def reduce_ms(times: dict) -> dict:
    """The four programs' ms a call -> what each part costs: `xla` and
    `kernel` (a sum: its program less its product's), `epilogue` (the
    packed product less the plain one) and `gain` (what the kernel's
    path saves a sum, the epilogue paid)."""
    xla = times["plain+xla"] - times["plain"]
    kernel = times["packed+kernel"] - times["packed"]
    epilogue = times["packed"] - times["plain"]
    return {"xla": xla, "kernel": kernel, "epilogue": epilogue,
            "gain": xla - kernel - epilogue}


def timed(fn, args, calls):
    import jax

    out = jax.block_until_ready(fn(*args))
    for _ in range(2):
        jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return out, (time.perf_counter() - start) / calls * 1e3


def routed(key, t, k, held, of):
    """A seeded routing as the chip that holds `held` of `of` experts
    sees it (`moe.held_share`): experts [t, k] (another chip's: number
    `held`), weights [t, k], the held experts' counts."""
    import jax
    import jax.numpy as jnp

    ke, kw = jax.random.split(key)
    experts = jax.random.randint(ke, (t, k), 0, of, jnp.int32)
    here = experts < held
    experts = jnp.where(here, experts, held)
    weights = jnp.where(here, jax.random.uniform(kw, (t, k)), 0.0)
    counts = (experts.reshape(-1, 1) == jnp.arange(held)).sum(
        0, dtype=jnp.int32)
    return experts, weights, counts


def reduce_programs(shape, weighted: bool, interpret: bool):
    """name -> jitted program of one seed (see the module's text)."""
    import jax
    import jax.numpy as jnp

    from ompi_tpu.ops import grouped_matmul as gk
    from ompi_tpu.ops import moe

    t, k, bound, d, f, held, of = shape
    dtype = jnp.bfloat16
    tm = 128 if interpret else moe._TM
    tiles = (tm, min(tm, moe._SUB),
             moe._row_product_tiles(f, d, 2)[2])

    def program(packed: bool, summed: bool, seed):
        kr, kh, kw = jax.random.split(jax.random.key(seed), 3)
        experts, weights, counts = routed(kr, t, k, held, of)
        order, inv = moe._expert_order(experts)
        n_held = jnp.minimum(counts.sum(), bound)
        hidden = jax.random.normal(kh, (bound, f)).astype(dtype)
        w2 = (jax.random.normal(kw, (held, f, d)) * f ** -0.5).astype(dtype)
        out = gk.gmm(hidden, w2, counts, tiles, packed=packed,
                     interpret=interpret)
        if not summed:
            return out
        if packed:
            return gk.row_reduce(
                out, inv.reshape(-1, k).T, weights.T if weighted else None,
                n_held, d, tiles[2], dtype, compact=bound < t * k,
                interpret=interpret)
        if weighted:
            return moe._weigh_held(out, weights, order, inv, n_held, bound)
        return moe._sum_held(out, order, inv, k, bound)

    return {name: jax.jit(functools.partial(program, packed, summed))
            for name, packed, summed in (
                ("plain", False, False), ("plain+xla", False, True),
                ("packed", True, False), ("packed+kernel", True, True))}


def probe_reduce(cell, shape, calls, interpret, seed):
    import numpy as np

    lines = []
    for weighted in SUMS:
        times, results = {}, {}
        for name, fn in reduce_programs(shape, weighted, interpret).items():
            results[name], times[name] = timed(fn, (seed,), calls)
        for variant in VARIANTS:
            # the kernel's constants of a pass (read when it is traced)
            from ompi_tpu.ops import grouped_matmul as gk

            kept = {name: getattr(gk, name) for name in variant}
            for name, value in variant.items():
                setattr(gk, name, value)
            gk.row_reduce.clear_cache()
            try:
                results[str(variant)], times[f"packed+kernel {variant}"] = \
                    timed(reduce_programs(shape, weighted, interpret)[
                        "packed+kernel"], (seed,), calls)
            except Exception as e:  # noqa: BLE001
                times[f"packed+kernel {variant}"] = float("nan")
                print("variant", variant, "FAILED", str(e)[-300:], flush=True)
            finally:
                for name, value in kept.items():
                    setattr(gk, name, value)
                gk.row_reduce.clear_cache()
        gap = float(np.abs(
            np.asarray(results["plain+xla"], np.float32)
            - np.asarray(results["packed+kernel"], np.float32)).max())
        gaps = {name: float(np.abs(
            np.asarray(results["plain+xla"], np.float32)
            - np.asarray(got, np.float32)).max())
            for name, got in results.items() if name.startswith("{")}
        lines.append({"cell": cell, "what": "reduce",
                      "sum": "weighted" if weighted else "plain",
                      "shape": list(shape), "ms": times,
                      **reduce_ms(times), "gap": gap,
                      **({"variant_gaps": gaps} if gaps else {})})
        print(json.dumps(lines[-1]), flush=True)
    return lines


def layer_program(shape, act: str = "silu"):
    """seed -> value and the five gradients of one recomputed layer at
    `shape`, every array made inside."""
    import jax
    import jax.numpy as jnp

    from ompi_tpu.ops import moe

    t, k, bound, d, f, held, of = shape
    dtype = jnp.bfloat16

    def program(seed):
        kr, kx, k1, k3, k2, kg = jax.random.split(jax.random.key(seed), 6)
        experts, weights, counts = routed(kr, t, k, held, of)
        route = moe.TopKRoute(experts, weights, counts, None, None)
        x = jax.random.normal(kx, (t, d)).astype(dtype)
        g = jax.random.normal(kg, (t, d)).astype(dtype)
        w1, w3 = ((jax.random.normal(key, (held, d, f)) * d ** -0.5).astype(
            dtype) for key in (k1, k3))
        w2 = (jax.random.normal(k2, (held, f, d)) * f ** -0.5).astype(dtype)

        def loss(x, weights, w1, w3, w2):
            y = moe.sorted_moe_ffn(
                x, route._replace(weights=weights), w1, w3, w2, act,
                bound if bound < t * k else None)
            return jnp.sum(y.astype(jnp.float32) * g)

        return jax.value_and_grad(jax.checkpoint(loss), argnums=(
            0, 1, 2, 3, 4))(x, weights, w1, w3, w2)

    return program


class forced:
    """`moe.row_reduce_kernel` answering `answer` wherever the kernel
    can run at all — and with yes, `moe.row_sum_gathers` too: the
    kernel's layer where the rules keep the 0/1 product
    (glm5-train-t4096), against the layer as the rules have it
    (`interpret`: as on a TPU with 128-row tiles, the kernels in
    interpret mode)."""

    def __init__(self, answer: bool, interpret: bool = False):
        self.answer, self.interpret = answer, interpret

    def __enter__(self):
        from ompi_tpu.ops import moe

        self.kept = {n: getattr(moe, n) for n in (
            "row_reduce_kernel", "ROW_REDUCE_MIN_BYTES", "_TM",
            "grouped_tiles", "reduce_tiles", "grouped_matmul",
            "_reduced_rows", "row_sum_gathers")}
        moe.ROW_REDUCE_MIN_BYTES = 0 if self.answer else 1 << 62
        if self.answer:
            moe.row_sum_gathers = lambda *a: True
        if self.interpret:
            moe._TM = 128
            for name in ("row_reduce_kernel", "grouped_tiles",
                         "reduce_tiles"):
                setattr(moe, name, functools.partial(
                    lambda rule, backend, *a: rule("tpu", *a),
                    self.kept[name]))
            for name in ("grouped_matmul", "_reduced_rows"):
                setattr(moe, name, functools.partial(
                    self.kept[name], interpret=True))

    def __exit__(self, *exc):
        from ompi_tpu.ops import moe

        for name, was in self.kept.items():
            setattr(moe, name, was)


def probe_layer(cell, shape, calls, interpret, seed):
    """One recomputed layer, value and gradient, the rule answering no
    and yes; the largest gaps of the value and the five gradients."""
    import jax
    import numpy as np

    times, results = {}, {}
    for name, answer in (("rules", False), ("kernel", True)):
        with forced(answer, interpret):
            results[name], times[name] = timed(
                jax.jit(layer_program(shape)), (seed,), calls)
    flat = [jax.tree.leaves(results[name]) for name in ("rules", "kernel")]
    gaps = [float(np.abs(np.asarray(a, np.float32)
                         - np.asarray(b, np.float32)).max()
                  / max(float(np.abs(np.asarray(a, np.float32)).max()),
                        1e-30)) for a, b in zip(*flat)]
    line = {"cell": cell, "what": "layer", "shape": list(shape),
            "ms": times, "gain": times["rules"] - times["kernel"],
            "gaps": gaps}
    print(json.dumps(line), flush=True)
    return [line]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--what", nargs="+", default=["reduce"],
                    choices=["reduce", "layer"])
    ap.add_argument("--cells", nargs="*", default=sorted(CELLS))
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--variants", nargs="*", default=[],
                    help="also time the kernel with its constants set "
                    "so (grouped_matmul.REDUCE_<NAME>), e.g. unroll=8")
    ap.add_argument("--sums", nargs="+", default=["weighted", "plain"],
                    choices=["weighted", "plain"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--interpret", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/row_reduce_probe.json")
    args = ap.parse_args()

    import jax

    interpret = bool(args.interpret)
    VARIANTS[:] = [{"REDUCE_" + kv.split("=")[0].upper():
                    int(kv.split("=")[1]) for kv in v.split(",")}
                   for v in args.variants]
    SUMS[:] = [name == "weighted" for name in args.sums]
    device = jax.devices()[0]
    if not interpret and device.platform != "tpu":
        raise SystemExit("a time comes from the chip: no TPU here "
                         "(--interpret 1 for the CPU twin)")
    print(f"device {device.platform} {device.device_kind}; ms a call over "
          f"{args.calls} calls on the host's clock", flush=True)
    lines = []
    for cell in (["toy"] if interpret else args.cells):
        shape = TOY if interpret else CELLS[cell]
        for what in args.what:
            try:
                lines += {"reduce": probe_reduce, "layer": probe_layer}[what](
                    cell, shape, 1 if interpret else args.calls, interpret,
                    args.seed)
            except Exception as e:  # noqa: BLE001 - a form the compiler refuses
                lines.append({"cell": cell, "what": what,
                              "failed": str(e)[-600:]})
                print(json.dumps(lines[-1]), flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(lines, f, indent=1)


if __name__ == "__main__":
    main()
