"""Read the numbers a train cell's limits are set from, on the chip, in
one process: for each seed the program's first steps, the plain
reference's, and the control's — the reference with every matmul
operand rounded to float8_e4m3fn, the step below bfloat16 — each
compared with the reference exactly as a run compares the program.

    python -m ompi_tpu.runtime.launcher -n 1 --mca device_plane on \
        --mca device_plane_platform tpu benchmark/tools/calibrate_train.py \
        --workload opt30b-train-t1024 --seeds 1,2,3 [--control-seeds 1,2,3]

No measured window (training's readings need none). PERF.md section 2
records the readings each limit was set from.
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

    from benchmark import manifest as mf, weights
    from benchmark.common import say
    from benchmark.runners import train_step as ts

    _, wl, traffic, config, _ = mf.cell_inputs(
        mf.load(), ns.workload, bool(ns.rehearsal))
    sizes = ts.model_sizes(config)
    n, lr = traffic["check_steps"], traffic["lr"]
    loose = {k: float("inf") for k in wl["limits"]}
    control = {int(s) for s in ns.control_seeds.split(",") if s}
    step = None
    for seed in (int(s) for s in ns.seeds.split(",")):
        params = weights.device_init(sizes, seed)
        toks, labs = weights.batches(sizes["vocab"], traffic["n_batches"],
                                     traffic["batch"], traffic["seq"], seed)
        if step is None:
            step = ts.build_step(sizes, lr).lower(
                params, toks[0], labs[0]).compile()
        params, program = ts.first_steps(step, params, toks, labs, sizes,
                                         seed, n)
        del params
        ref = ts.reference_steps(sizes, toks, labs, seed, lr, n)
        row = {"seed": seed, "program": {
            c[0]: c[1] for c in ts.checks_against(program, ref, loose)}}
        if seed in control:
            ctl = ts.reference_steps(sizes, toks, labs, seed, lr, n,
                                     quantize=jnp.float8_e4m3fn)
            row["control_fp8"] = {
                c[0]: c[1] for c in ts.checks_against(ctl, ref, loose)}
            row["control_losses"] = ctl[0]
            row["control_first_norms"] = [float(x) for x in ctl[1]]
            row["control_last_norms"] = [float(x) for x in ctl[2]]
        row["losses"] = {"program": program[0], "reference": ref[0]}
        row["reference_first_norms"] = [float(x) for x in ref[1]]
        row["program_first_norms"] = [float(x) for x in program[1]]
        row["reference_last_norms"] = [float(x) for x in ref[2]]
        row["program_last_norms"] = [float(x) for x in program[2]]
        say("CALIBRATE " + json.dumps(row))
    mpi.Finalize()
    return 0


if __name__ == "__main__":
    sys.exit(main())
