#!/usr/bin/env bash
# Prof smoke lane: 2-rank CPU job with the attribution profiler +
# trace recorder on. The job stages host arrays to "device" under the
# staging phase (deliberately the dominant cost), runs a short train
# phase, and exports per-rank traces; `python -m ompi_tpu.prof report`
# must merge them and attribute the wall to staging. The report JSON
# stays on disk for the CI artifact upload.
set -euo pipefail
cd "$(dirname "$0")/.."

out="${1:-prof_smoke_out}"
rm -rf "$out"
mkdir -p "$out"

cat > "$out/staging_job.py" <<'EOF'
import os
import time

import numpy as np

from ompi_tpu import mpi
from ompi_tpu.accelerator import tpu as tpu_mod
from ompi_tpu.prof import ledger
from ompi_tpu.trace import export, recorder

world = mpi.Init()
me = world.rank
assert ledger.PROFILER is not None, "prof_enable must enable at init"
assert recorder.RECORDER is not None, "trace_enable must enable at init"

acc = tpu_mod.TpuAccelerator()
out = os.environ["PROF_SMOKE_OUT"]
with ledger.phase("staging"):
    # chunked H2D path (9 MiB) + a sleep so staging deterministically
    # dominates the wall regardless of host speed
    dev = acc.to_device(np.ones((9 << 20) // 4, np.float32))
    time.sleep(0.4)
with ledger.phase("train"):
    for _ in range(3):
        world.allreduce(me)
    time.sleep(0.05)
world.Barrier()
export.write(os.path.join(out, f"trace_r{me}.json"), recorder.RECORDER)
world.Barrier()
mpi.Finalize()
EOF

PROF_SMOKE_OUT="$out" JAX_PLATFORMS=cpu \
  python -m ompi_tpu.runtime.launcher -n 2 \
  --timeout 120 \
  --mca prof_enable 1 \
  --mca trace_enable 1 \
  "$out/staging_job.py"

python -m ompi_tpu.prof report -o "$out/attribution.json" \
  "$out"/trace_r*.json

python - "$out/attribution.json" <<'EOF'
import json
import sys

rep = json.load(open(sys.argv[1]))
assert rep["schema"] == "ompi_tpu.prof.attribution/1", rep["schema"]
assert rep["ranks"] == [0, 1], rep["ranks"]
phases = {p["phase"]: p for p in rep["phases"]}
assert "staging" in phases and "train" in phases, phases.keys()
top = rep["phases"][0]["phase"]
assert top == "staging", (
    f"staging must be the top wall-clock consumer, got {top!r}: "
    f"{rep['phases']}")
assert phases["staging"]["max_s"] >= 0.4, phases["staging"]
x = rep["transfers"]["h2d"]
assert x["bytes"] >= 2 * (9 << 20) and x["spans"] >= 2, x
# the always-on compile ledger: whatever the job compiled (here the
# chunked upload's concatenate), program by phase
assert rep["compile"] and all(
    c["program"] and c["phases_s"] for c in rep["compile"]), rep["compile"]
print(f"prof smoke OK: staging {phases['staging']['max_s']:.3f}s "
      f"worst-rank (train {phases['train']['max_s']:.3f}s), "
      f"{x['bytes']} h2d bytes in {x['spans']} spans")
EOF
