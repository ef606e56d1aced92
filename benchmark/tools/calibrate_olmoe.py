"""Read the numbers the `olmoe-train-t4096` limits are set from, on the
chip, in one process (calibrate_train.py's twin for the olmoe_train
runner): for each seed the program's first steps, the plain
reference's, and the control's — the reference with every bfloat16
matmul operand rounded to float8_e4m3fn — each with its losses and every leaf's
movement after the first step and after the last (one `CALIBRATE`
line of JSON a seed, from which any of a run's gaps can be read); and
the share of layer 0's token-expert assignments on which the program,
and the control, differ from the reference (top-8 of 64 is discrete).

    python -m ompi_tpu.runtime.launcher -n 1 --mca device_plane on \
        --mca device_plane_platform tpu benchmark/tools/calibrate_olmoe.py \
        --workload olmoe-train-t4096 --seeds 1,2,3 [--control-seeds 1,2,3]

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
    import numpy as np

    from benchmark import manifest as mf, weights, weights_olmoe
    from benchmark.common import say
    from benchmark.reference import olmoe_decoder
    from benchmark.runners import olmoe_train as ot

    _, _, traffic, config, _ = mf.cell_inputs(
        mf.load(), ns.workload, bool(ns.rehearsal))
    sizes = ot.model_sizes(config)
    n, lr = traffic["check_steps"], traffic["lr"]
    control = {int(s) for s in ns.control_seeds.split(",") if s}
    step = None
    for seed in (int(s) for s in ns.seeds.split(",")):
        params = weights_olmoe.device_init(sizes, seed)
        toks, labs = weights.batches(sizes["vocab"], traffic["n_batches"],
                                     traffic["batch"], traffic["seq"], seed)
        if step is None:
            step = ot.build_step(sizes, lr).lower(
                params, toks[0], labs[0]).compile()
        probe = ot.routing_probe(sizes, params, toks, n)
        experts = probe.pop("experts")
        params, program = ot.first_steps(step, params, toks, labs, sizes,
                                         seed, n)
        del params
        ref = ot.reference_steps(sizes, toks, labs, seed, lr, n)
        row = {"seed": seed, "probe": probe,
               "route_disagreement": ot.route_disagreement(
                   experts, sizes, toks, seed)}
        runs = {"program": program, "reference": ref}
        if seed in control:
            runs["control"] = ot.reference_steps(
                sizes, toks, labs, seed, lr, n, quantize=jnp.float8_e4m3fn)
            fp8 = np.asarray(olmoe_decoder.chosen_experts(
                weights_olmoe.device_init(sizes, seed), toks[0],
                ot.reference_spec(sizes), jnp.float8_e4m3fn))
            row["control_route_disagreement"] = ot.route_disagreement(
                fp8, sizes, toks, seed)
        for name, (losses, first, last) in runs.items():
            row[name] = {"losses": losses,
                         "first_norms": [float(x) for x in first],
                         "last_norms": [float(x) for x in last]}
        say("CALIBRATE " + json.dumps(row))
    mpi.Finalize()
    return 0


if __name__ == "__main__":
    sys.exit(main())
