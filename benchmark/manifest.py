"""BENCHMARK.json and the files it points at, as the harness reads them.

Pure Python (no jax): the parent process, the manifest checker and the
rank processes all load the manifest through here, so there is one
answer to "which metrics does this cell report at this --trace value".
"""

from __future__ import annotations

import json
import os
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")


def load(path: str = MANIFEST) -> dict:
    with open(path) as f:
        return json.load(f)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def cell(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                   f"(have {[w['name'] for w in manifest['workloads']]})")


def workload_file(name: str) -> dict:
    """The cell's own data file: runner, ranks, traffic parameters."""
    return load_json("workloads", name + ".json")


def config_file(manifest: dict, config: str) -> dict:
    for c in manifest["configs"]:
        if c["name"] == config:
            with open(os.path.join(ROOT, c["file"])) as f:
                return json.load(f)
    raise KeyError(f"no configuration {config!r} in BENCHMARK.json")


def cell_inputs(manifest: dict, name: str, rehearsal: bool = False):
    """(cell, workload file, traffic, configuration, limits) as a run
    of this cell uses them. A rehearsal takes the toy twin of the
    configuration and the workload file's `rehearsal` overrides;
    limits read at the chip's widths do not carry over to toy widths,
    so it has `rehearsal_limits` of its own where the file gives them."""
    c = cell(manifest, name)
    workload = workload_file(name)
    traffic = dict(workload["traffic"])
    limits = workload["limits"]
    if not rehearsal:
        return c, workload, traffic, config_file(manifest, c["config"]), \
            limits
    traffic.update(workload.get("rehearsal", {}))
    return (c, workload, traffic,
            load_json("configs", c["config"] + ".rehearsal.json"),
            workload.get("rehearsal_limits", limits))


def cells_of(manifest: dict, metric: dict) -> List[str]:
    """The cells a metric is reported in: its `workloads` list, or
    every cell where it has none (only `setup_s` and the set-up
    readers do that here; check_manifest.py holds the rest to a list)."""
    return list(metric.get("workloads")
                or [w["name"] for w in manifest["workloads"]])


def metrics_for(manifest: dict, cell_name: str, trace: int) -> Dict[str, dict]:
    """name -> entry of the metrics a result line of this cell carries:
    the end-to-end ones with --trace 0, the per-layer ones with 1."""
    group = manifest["per_layer"] if trace else manifest["end_to_end"]
    return {m["name"]: m for m in group
            if cell_name in cells_of(manifest, m)}


def reader_name(metric_name: str) -> str:
    """`step_device_ms.train` is read by layer_metrics/step_device_ms.py:
    metrics that differ only in the suffix after the first dot share
    a reader (the suffix says which end-to-end metric's cells it is
    reported in; the reader reads the same thing in all of them)."""
    return metric_name.split(".", 1)[0]
