"""Read the numbers the `glm5-train-t4096` limits are set from, on the
chip, in one process (calibrate_olmoe.py's twin for the glm5_train
runner): for each seed the program's first steps, the plain
reference's, and the control's — the reference with every bfloat16
matmul operand rounded to float8_e4m3fn — each with its losses and
every leaf's movement after the first step and after the last (one
`CALIBRATE` line of JSON a seed, from which any of a run's gaps can be
read, and the gaps themselves as the runner computes them); and the two
discrete choices: the share of the first expert layer's token-expert
assignments, and of layer 0's selected (query, key) pairs, on which the
program, and the control, differ from the reference. The control
then goes through the comparison under the cell's own limits
(`compare.verdict`: one `check` line a limit, `NOT CORRECT` on each it
fails, and one `CONTROL` line a seed): it has to come out not correct.

    python -m ompi_tpu.runtime.launcher -n 1 --mca device_plane on \
        --mca device_plane_platform tpu benchmark/tools/calibrate_glm5.py \
        --workload glm5-train-t4096 --seeds 1,2,3 [--control-seeds 1,2,3]

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

    from benchmark import compare, manifest as mf, weights, weights_glm5
    from benchmark.common import say
    from benchmark.runners import glm5_train as gt

    _, _, traffic, config, limits = mf.cell_inputs(
        mf.load(), ns.workload, bool(ns.rehearsal))
    sizes = gt.model_sizes(config)
    n, lr = traffic["check_steps"], traffic["lr"]
    loose = {k: float("inf") for k in (
        "loss_gap", "first_grad_norm_gap", "first_grad_norm_rms_gap",
        "param_change_norm_gap", "router_grad_norm_gap",
        "indexer_grad_norm_gap")}
    control = {int(s) for s in ns.control_seeds.split(",") if s}
    step = None
    for seed in (int(s) for s in ns.seeds.split(",")):
        params = weights_glm5.device_init(sizes, seed)
        toks, labs = weights.batches(sizes["vocab"], traffic["n_batches"],
                                     traffic["batch"], traffic["seq"], seed)
        remade = float(weights_glm5.delta_norms(sizes, seed, params).max())
        if step is None:
            step = gt.build_step(sizes, lr).lower(
                params, toks[0], labs[0]).compile()
        probe = gt.probes(sizes, params, toks, n)
        experts, keep = probe.pop("experts"), probe.pop("keep")
        params, program = gt.first_steps(step, params, toks, labs, sizes,
                                         seed, n)
        del params
        runs = {"program": program,
                "reference": gt.reference_steps(sizes, toks, labs, seed, lr,
                                                n)}
        chosen, ref_keep = gt.reference_choices(sizes, toks, seed)

        def choices(mine_experts, mine_keep):
            out = {"route_disagreement": gt.route_disagreement(
                mine_experts, chosen)}
            if ref_keep is not None:
                out["select_disagreement"] = gt.select_disagreement(
                    mine_keep, ref_keep, sizes["index_topk"])
            return out

        row = {"seed": seed, "probe": probe, "gaps": {},
               "seed_tree_remade_gap": remade}
        if seed in control:
            fp8 = jnp.float8_e4m3fn
            runs["control"] = gt.reference_steps(sizes, toks, labs, seed,
                                                 lr, n, quantize=fp8)
            c_chosen, c_keep = gt.reference_choices(sizes, toks, seed, fp8)
            row["gaps"]["control"] = choices(
                gt.chosen_numbers(c_chosen, sizes["top_k"]), c_keep)
        row["gaps"]["program"] = choices(experts, keep)
        for name in runs:
            if name != "reference":
                row["gaps"][name].update({
                    c[0]: c[1] for c in gt.checks_against(
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
