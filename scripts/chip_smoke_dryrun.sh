#!/usr/bin/env bash
# chip_smoke dry-run lane (CI, not tier-1): the script that proves the
# system on the chip must itself keep working, and must never pass
# without one. Two runs on this chipless box:
#   1. `python chip_smoke.py --cpu-dryrun` — every leg at toy width on
#      4 virtual CPU devices / gloo, Pallas in interpret mode; must exit
#      0 and every line it prints must say `DRYRUN platform=cpu` (a dry
#      run can never be read as a chip pass);
#   2. a bare `python chip_smoke.py` — must fail fast, name the missing
#      TPU, and print no result.
set -euo pipefail
cd "$(dirname "$0")/.."

out=$(python chip_smoke.py --cpu-dryrun 2>&1)
echo "$out" | tail -n 12
if echo "$out" | grep -v '^DRYRUN platform=cpu ' | grep -q .; then
  echo "chip_smoke dry run: a line without the DRYRUN tag" >&2; exit 1
fi
# the last line, tag aside, is the driver's contract: these keys only
echo "$out" | tail -n 1 | sed 's/^DRYRUN platform=cpu //' | python -c '
import json, sys
r = json.loads(sys.stdin.read())
assert set(r) == {"ok", "device"} and r["ok"] is True, r
d = r["device"]
assert set(d) == {"platform", "kind", "count"}, d
assert isinstance(d["platform"], str) and isinstance(d["kind"], str), d
assert type(d["count"]) is int, d
' || { echo "chip_smoke dry run: the last line is not the result object" >&2; exit 1; }
echo "$out" | tail -n 2 | head -n 1 | grep -q '^DRYRUN platform=cpu SUMMARY {' \
  || { echo "chip_smoke dry run: no SUMMARY line before the result" >&2; exit 1; }

start=$(date +%s)
if bare=$(env -u JAX_PLATFORMS python chip_smoke.py 2>&1); then
  echo "chip_smoke without a TPU exited 0" >&2; exit 1
fi
took=$(( $(date +%s) - start ))
echo "$bare" | tail -n 3
echo "$bare" | grep -q 'no usable TPU' \
  || { echo "chip_smoke: the failure does not name the TPU" >&2; exit 1; }
if echo "$bare" | grep -q '"ok"'; then
  echo "chip_smoke without a TPU printed a result" >&2; exit 1
fi
[ "$took" -lt 60 ] \
  || { echo "chip_smoke took ${took}s to notice there is no TPU" >&2; exit 1; }
echo "chip_smoke dryrun OK (no-TPU failure in ${took}s)"
