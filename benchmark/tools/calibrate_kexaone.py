"""Read the numbers the `kexaone-train-t8192` limits are set from, on
the chip, in one process (calibrate_mellum2.py's twin for the
kexaone_train runner): for each seed the program's first steps and its
set-up probes, the plain reference's, and the control's — the reference
with every bfloat16 matmul operand rounded to float8_e4m3fn — each with
its losses and every leaf's movement after the first step and after the
last (one `CALIBRATE` line of JSON a seed, and the gaps as the runner
computes them), and the first batch's four: the first windowed layer's,
the first full layer's and the MTP module's attention output, the first
expert layer's routing. The control
then goes through the comparison under the cell's own limits
(`compare.verdict`: one `check` line a limit, `NOT CORRECT` on each it
fails, and one `CONTROL` line a seed): it has to come out not correct.

    python -m ompi_tpu.runtime.launcher -n 1 --mca device_plane on \
        --mca device_plane_platform tpu benchmark/tools/calibrate_kexaone.py \
        --workload kexaone-train-t8192 --seeds 1,2,3 [--control-seeds 1,2,3]

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

    from benchmark import compare, manifest as mf, weights, weights_kexaone
    from benchmark.common import say
    from benchmark.runners import kexaone_train as mt

    _, _, traffic, config, limits = mf.cell_inputs(
        mf.load(), ns.workload, bool(ns.rehearsal))
    sizes = mt.model_sizes(config)
    n, lr = traffic["check_steps"], traffic["lr"]
    loose = {k: float("inf") for k in limits}
    control = {int(s) for s in ns.control_seeds.split(",") if s}
    step = None
    for seed in (int(s) for s in ns.seeds.split(",")):
        params = weights_kexaone.device_init(sizes, seed)
        toks, labs = weights.batches(sizes["vocab"], traffic["n_batches"],
                                     traffic["batch"], traffic["seq"], seed)
        remade = float(weights_kexaone.delta_norms(sizes, seed,
                                                   params).max())
        if step is None:
            step = mt.build_step(sizes, lr).lower(
                params, toks[0], labs[0]).compile()
        probe = mt.probes(sizes, params, toks, n, seed)
        params, runs = mt.first_steps(step, params, toks, labs, sizes, seed,
                                      n)
        del params
        runs = {"program": runs,
                "reference": mt.reference_steps(sizes, toks, labs, seed, lr,
                                                n)}
        first = mt.reference_first_batch(sizes, toks, seed)
        row = {"seed": seed, "seed_tree_remade_gap": remade,
               "probe": {k: v for k, v in probe.items()
                         if k not in mt.ARRAYS},
               "gaps": {"program": mt.first_batch_checks(probe, first,
                                                         loose)}}
        if seed in control:
            fp8 = jnp.float8_e4m3fn
            runs["control"] = mt.reference_steps(sizes, toks, labs, seed,
                                                 lr, n, quantize=fp8)
            chosen, outs = mt.reference_first_batch(sizes, toks, seed, fp8)
            row["gaps"]["control"] = mt.first_batch_checks(
                {"experts": mt.chosen_numbers(chosen, sizes["top_k"]),
                 **{k + "_out": v for k, v in outs.items()}}, first, loose)
        for name in row["gaps"]:
            row["gaps"][name] = {c[0]: c[1] for c in row["gaps"][name]
                                 + mt.checks_against(
                                     runs[name], runs["reference"], loose,
                                     sizes)}
        if seed in control:
            held = compare.verdict(
                [(k, v, limits[k]) for k, v in row["gaps"]["control"].items()],
                lambda line: say(f"control seed {seed} {line}"))
            row["control_correct"] = held
            say(f"CONTROL seed {seed} "
                f"{'CORRECT: the limits do not hold it' if held else 'not correct'}")
        for name, (losses, first_norms, last_norms) in runs.items():
            row[name] = {"losses": losses,
                         "first_norms": [float(x) for x in first_norms],
                         "last_norms": [float(x) for x in last_norms]}
        say("CALIBRATE " + json.dumps(row))
        # 6 GB of state and 14 GB of reference at its peak: nothing of
        # this seed stays alive while the next one runs
        del probe, runs, first, row
    mpi.Finalize()
    return 0


if __name__ == "__main__":
    sys.exit(main())
