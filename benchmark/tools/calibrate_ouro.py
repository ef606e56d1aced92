"""Read the numbers the `ouro-train-t4096` limits are set from, on the
chip, in one process (calibrate_glm5.py's twin for the ouro_train
runner): for each seed the program's first steps, the plain
reference's, and the control's — the reference with every bfloat16
matmul operand rounded to float8_e4m3fn — each with its losses, every
leaf's movement after the first step and after the last, and every
exit's mean cross-entropy and mean probability on the first batch (one
`CALIBRATE` line of JSON a seed, from which any of a run's gaps can be
read, and the gaps themselves as the runner computes them). The control
then goes through the comparison under the cell's own limits
(`compare.verdict`: one `check` line a limit, `NOT CORRECT` on each it
fails, and one `CONTROL` line a seed): it has to come out not correct.

    python -m ompi_tpu.runtime.launcher -n 1 --mca device_plane on \
        --mca device_plane_platform tpu benchmark/tools/calibrate_ouro.py \
        --workload ouro-train-t4096 --seeds 1,2,3 [--control-seeds 1,2,3]

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

    from benchmark import compare, manifest as mf, weights, weights_ouro
    from benchmark.common import say
    from benchmark.runners import ouro_train as ot

    _, _, traffic, config, limits = mf.cell_inputs(
        mf.load(), ns.workload, bool(ns.rehearsal))
    sizes = ot.model_sizes(config)
    n, lr = traffic["check_steps"], traffic["lr"]
    loose = {k: float("inf") for k in limits}
    control = {int(s) for s in ns.control_seeds.split(",") if s}
    step = None
    for seed in (int(s) for s in ns.seeds.split(",")):
        params = weights_ouro.device_init(sizes, seed)
        toks, labs = weights.batches(sizes["vocab"], traffic["n_batches"],
                                     traffic["batch"], traffic["seq"], seed)
        remade = float(ot._moved(sizes, seed, params).max())
        if step is None:
            step = ot.build_step(sizes, lr).lower(
                params, toks[0], labs[0]).compile()
        probe = ot.exit_probe(sizes, params, toks[0], labs[0])
        params, program = ot.first_steps(step, params, toks, labs, sizes,
                                         seed, n)
        del params
        runs = {"program": (program, (probe["nll"], probe["mass"])),
                "reference": ot.reference_steps(sizes, toks, labs, seed, lr,
                                                n)}
        if seed in control:
            runs["control"] = ot.reference_steps(
                sizes, toks, labs, seed, lr, n, quantize=jnp.float8_e4m3fn)
        reference, ref_exits = runs["reference"]
        row = {"seed": seed, "seed_tree_remade_gap": remade, "gaps": {
            name: {c[0]: c[1] for c in ot.checks_against(
                steps, reference, loose, sizes) + ot.exit_checks(
                exits, ref_exits, loose)}
            for name, (steps, exits) in runs.items() if name != "reference"}}
        if seed in control:
            held = compare.verdict(
                [(k, v, limits[k]) for k, v in row["gaps"]["control"].items()],
                lambda line: say(f"control seed {seed} {line}"))
            row["control_correct"] = held
            say(f"CONTROL seed {seed} "
                f"{'CORRECT: the limits do not hold it' if held else 'not correct'}")
        for name, ((losses, first, last), exits) in runs.items():
            row[name] = {"losses": losses,
                         "first_norms": [float(x) for x in first],
                         "last_norms": [float(x) for x in last],
                         "exit_nll": [float(x) for x in exits[0]],
                         "exit_mass": [float(x) for x in exits[1]]}
        say("CALIBRATE " + json.dumps(row))
    mpi.Finalize()
    return 0


if __name__ == "__main__":
    sys.exit(main())
