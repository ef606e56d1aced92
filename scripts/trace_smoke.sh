#!/usr/bin/env bash
# Trace smoke lane: run examples/partitioned_gradients.py on two CPU
# ranks with the recorder on, verify rank 0's exported Chrome trace is
# Perfetto-shaped (traceEvents list, ph:"X" spans from the
# api/coll_xla/part layers, monotone per-tid timestamps), and
# exercise the merge and report CLIs on the per-rank files. The JSON
# stays on disk for the CI artifact upload.
set -euo pipefail
cd "$(dirname "$0")/.."

out="${1:-trace_smoke.json}"
driver="$(mktemp --suffix=.py)"
trap 'rm -f "$driver"' EXIT
# the example as it is; the ring outlives its mpi.Finalize()
cat > "$driver" <<'EOF'
import runpy
import sys

from ompi_tpu.trace import export, recorder

runpy.run_path("examples/partitioned_gradients.py", run_name="__main__")
rec = recorder.RECORDER
assert rec is not None, "OMPI_TPU_TRACE=1 did not bring the ring up"
base = sys.argv[1]
export.write(base if rec.rank == 0
             else base[:-len(".json")] + f"_r{rec.rank}.json", rec)
EOF
OMPI_TPU_TRACE=1 python -m ompi_tpu.runtime.launcher -n 2 --timeout 300 \
    --mca device_plane on --mca coll_xla_bucket_bytes 16384 \
    "$driver" "$out"

python - "$out" <<'EOF'
import json
import sys

path = sys.argv[1]
doc = json.load(open(path))
evs = doc["traceEvents"]
assert isinstance(evs, list) and evs, "empty traceEvents"
spans = [e for e in evs if e.get("ph") == "X"]
subsys = {e["cat"] for e in spans}
missing = {"api", "coll_xla", "part"} - subsys
assert not missing, f"missing subsystems: {missing} (have {subsys})"
by_tid = {}
for e in spans:
    by_tid.setdefault((e["pid"], e["tid"]), []).append(e["ts"])
for tid, ts in by_tid.items():
    assert ts == sorted(ts), f"non-monotone ts on tid {tid}"
print(f"trace smoke OK: {len(spans)} spans, subsystems "
      f"{sorted(subsys)}")
EOF

python -m ompi_tpu.trace merge -o "${out%.json}_merged.json" \
    "$out" "${out%.json}_r1.json"
python -m ompi_tpu.trace report "$out"
