"""The sweep cell's control, read on the chip at the cell's own sizes:
the plain sum put in the program's place and carried in bfloat16, the
step below the float32 the configuration states, through the same
comparison a run makes. One chip is enough: the control replaces the
program, so no collective runs.

    python benchmark/tools/control_osu.py --workload osu-allreduce-4rank --seeds 1,2,3
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
    ap.add_argument("--rehearsal", type=int, default=0)
    ns = ap.parse_args()
    import numpy as np

    from benchmark import manifest as mf
    from benchmark.reference import allreduce_sum

    _, wl, traffic, config, _ = mf.cell_inputs(
        mf.load(), ns.workload, bool(ns.rehearsal))
    item = np.dtype(config["dtype"]).itemsize
    for seed in (int(s) for s in ns.seeds.split(",")):
        gaps = {s: allreduce_sum.result_gap(
            None, seed, i, config["ranks"], s // item, config["dtype"],
            traffic["check_sample"], control_dtype="bfloat16")
            for i, s in enumerate(traffic["sizes_bytes"])}
        print("CONTROL " + json.dumps(
            {"seed": seed, "sum_gap_by_size": gaps,
             "sum_gap": max(gaps.values()),
             "limit": wl["limits"]["sum_gap"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
