"""Read the numbers the `kimivl-train-t4096` limits are set from, on
the chip, in one process (calibrate_glm5.py's twin for the kimivl_train
runner): for each seed the program's first steps, the plain
reference's, and the control's — the reference with every bfloat16
matmul operand rounded to float8_e4m3fn — each with its losses and
every leaf's movement after the first step and after the last, the
first expert layer's routing and the projector's rows on the first
batch (one `CALIBRATE` line of JSON a seed with the gaps as the runner
computes them). The control then goes through the comparison under the
cell's own limits (`compare.verdict`: one `check` line a limit, `NOT
CORRECT` on each it fails, and one `CONTROL` line a seed): it has to
come out not correct.

    python -m ompi_tpu.runtime.launcher -n 1 --mca device_plane on \
        --mca device_plane_platform tpu benchmark/tools/calibrate_kimivl.py \
        --workload kimivl-train-t4096 --seeds 1,2,3 [--control-seeds 1,2,3]

No measured window. PERF.md section 2 records the readings each limit
was set from.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--rehearsal", type=int, default=0)
    ns = ap.parse_args()

    from ompi_tpu import mpi

    mpi.Init()
    import jax.numpy as jnp

    from benchmark import compare, manifest as mf, weights_kimivl
    from benchmark.common import say
    from benchmark.runners import glm5_train as gt, kimivl_train as kt

    _, _, traffic, config, limits = mf.cell_inputs(
        mf.load(), ns.workload, bool(ns.rehearsal))
    sizes = kt.model_sizes(config)
    n, lr = traffic["check_steps"], traffic["lr"]
    loose = {k: float("inf") for k in limits}
    control = {int(s) for s in ns.control_seeds.split(",") if s}
    step = None
    for seed in (int(s) for s in ns.seeds.split(",")):
        params = weights_kimivl.device_init(sizes, seed)
        toks, labs = weights_kimivl.batches(sizes, traffic, seed)
        remade = float(weights_kimivl.delta_norms(sizes, seed, params).max())
        if step is None:
            step = kt.build_step(sizes, lr).lower(
                params, toks[0], labs[0]).compile()
        probe = kt.probes(sizes, params, toks, n)
        experts, rows = probe.pop("experts"), probe.pop("rows")
        params, program = kt.first_steps(step, params, toks, labs, sizes,
                                         seed, n)
        del params
        runs = {"program": program,
                "reference": kt.reference_steps(sizes, traffic, toks, labs,
                                                seed, lr, n)}
        chosen, ref_rows = kt.reference_first_batch(sizes, traffic, toks,
                                                    seed)

        def first_batch(mine_experts, mine_rows):
            return {"route_disagreement": gt.route_disagreement(
                        mine_experts, chosen),
                    "vision_embed_gap": kt.rows_gap(mine_rows, ref_rows)}

        row = {"seed": seed, "probe": probe, "gaps": {},
               "seed_tree_remade_gap": remade}
        if seed in control:
            fp8 = jnp.float8_e4m3fn
            runs["control"] = kt.reference_steps(
                sizes, traffic, toks, labs, seed, lr, n, quantize=fp8)
            c_chosen, c_rows = kt.reference_first_batch(
                sizes, traffic, toks, seed, fp8)
            row["gaps"]["control"] = first_batch(
                gt.chosen_numbers(c_chosen, sizes["top_k"]), c_rows)
        row["gaps"]["program"] = first_batch(experts, rows)
        del rows, ref_rows
        for name in runs:
            if name != "reference":
                row["gaps"][name].update({
                    c[0]: c[1] for c in kt.checks_against(
                        runs[name], runs["reference"], loose, sizes)})
        if seed in control:
            held = compare.verdict(
                [(k, v, limits[k]) for k, v in row["gaps"]["control"].items()],
                lambda line: say(f"control seed {seed} {line}"))
            row["control_correct"] = held
            say(f"CONTROL seed {seed} "
                f"{'CORRECT: the limits do not hold it' if held else 'not correct'}")
        for name, (losses, first, last) in runs.items():
            row[name] = {"losses": losses,
                         "first_norms": [float(x) for x in first],
                         "last_norms": [float(x) for x in last]}
        say("CALIBRATE " + json.dumps(row))
    mpi.Finalize()
    return 0


if __name__ == "__main__":
    sys.exit(main())
