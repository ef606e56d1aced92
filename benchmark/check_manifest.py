"""Check BENCHMARK.json against the rules a driver refuses it by.

    python benchmark/check_manifest.py
    python benchmark/check_manifest.py --workload CELL --trace 0|1 --line '<last line>'

Pure Python. The first form checks the manifest and the files it
names; the second also checks that a run's last printed line carries
exactly the metrics the manifest gives that cell for that --trace
value. PR 22 was refused for one fault this file is built around: a
per-layer metric reported in a cell that does not report the
end-to-end metric it names under `moves`.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from typing import List

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
from benchmark import manifest as mf  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
E2E_SOURCES = {"host_clock", "device_trace"}
TRAFFIC_EXT = (".json", ".jsonl", ".toml", ".txt", ".csv")
MAX_RUN_SECONDS = 51
MAX_BOUND = 0.1


def _line_ok(s, what: str, errs: List[str]) -> None:
    if not (isinstance(s, str) and 1 <= len(s) <= 200
            and "\n" not in s and "\t" not in s):
        errs.append(f"{what}: must be 1-200 characters on one line")


def _keys(entry: dict, need: set, may: set, what: str,
          errs: List[str]) -> None:
    have = set(entry)
    if need - have:
        errs.append(f"{what}: lacks {sorted(need - have)}")
    if have - need - may:
        errs.append(f"{what}: has keys that are refused "
                    f"{sorted(have - need - may)}")


def check(manifest: dict, root: str = mf.ROOT) -> List[str]:
    """Every rule broken, as a list of sentences (empty: passes)."""
    errs: List[str] = []
    if set(manifest) != TOP_KEYS:
        errs.append(f"top-level keys must be exactly {sorted(TOP_KEYS)}, "
                    f"got {sorted(manifest)}")
        return errs
    if len(json.dumps(manifest)) > 64 * 1024:
        errs.append("the file is over 64 KiB")

    # command, paths, run_seconds
    paths = manifest["paths"]
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16):
        errs.append("paths: 1 to 16 directories")
        paths = []
    for p in paths:
        if not (PATH.match(p) and not p.startswith("/")
                and ".." not in p.split("/")):
            errs.append(f"paths: {p!r} is not a plain relative path")
        elif not os.path.isdir(os.path.join(root, p)):
            errs.append(f"paths: {p!r} is not a directory")
    cmd = manifest["command"]
    if not (isinstance(cmd, list) and 1 <= len(cmd) <= 32):
        errs.append("command: a list of 1 to 32 strings")
        cmd = []
    for word in cmd:
        _line_ok(word, f"command word {word!r}", errs)
        if isinstance(word, str) and (
                word.startswith("/") or ".." in word.split("/")):
            errs.append(f"command: {word!r} leads out of the repo")
        elif isinstance(word, str) and os.path.exists(
                os.path.join(root, word)) and not _under(word, paths):
            errs.append(f"command: {word!r} is a file of the repo "
                        "outside `paths`")
    rs = manifest["run_seconds"]
    if not (isinstance(rs, int) and not isinstance(rs, bool)
            and 10 <= rs <= MAX_RUN_SECONDS):
        errs.append(f"run_seconds: one whole number from 10 to "
                    f"{MAX_RUN_SECONDS}, got {rs!r}")

    # configurations
    configs = manifest["configs"]
    if not 1 <= len(configs) <= 24:
        errs.append("configs: 1 to 24")
    _unique([c.get("name") for c in configs], "configuration", errs)
    _unique([c.get("file") for c in configs], "configuration file", errs)
    for c in configs:
        what = f"config {c.get('name')!r}"
        _keys(c, {"name", "source", "file", "reduced", "why"}, set(),
              what, errs)
        _name_ok(c.get("name"), what, errs)
        _line_ok(c.get("source"), what + " source", errs)
        _line_ok(c.get("why"), what + " why", errs)
        red = c.get("reduced", [])
        if not (isinstance(red, list) and len(red) <= 16):
            errs.append(f"{what}: reduced has at most 16 keys")
        for k in red if isinstance(red, list) else []:
            _name_ok(k, what + " reduced key", errs)
            if _is_width(k):
                errs.append(f"{what}: reduced may never name a width "
                            f"({k!r})")
        f = c.get("file", "")
        if not (_under(f, paths) and os.path.isfile(os.path.join(root, f))):
            errs.append(f"{what}: file {f!r} must exist under `paths`")

    # cells
    cells = manifest["workloads"]
    if not 1 <= len(cells) <= 24:
        errs.append("workloads: 1 to 24 cells")
    cell_names = [w.get("name") for w in cells]
    _unique(cell_names, "cell", errs)
    _unique([(w.get("config"), w.get("traffic")) for w in cells],
            "pair of configuration and traffic", errs)
    config_names = {c.get("name") for c in configs}
    for w in cells:
        what = f"cell {w.get('name')!r}"
        _keys(w, {"name", "config", "traffic", "chips", "why"}, set(),
              what, errs)
        for k in ("name", "config", "traffic"):
            _name_ok(w.get(k), f"{what} {k}", errs)
        _line_ok(w.get("why"), what + " why", errs)
        if w.get("config") not in config_names:
            errs.append(f"{what}: configuration {w.get('config')!r} "
                        "is not defined")
        if w.get("chips") not in (1, 4):
            errs.append(f"{what}: chips is 1 or 4")
        errs.extend(_cell_files(w, root))
    for c in configs:
        if c.get("name") not in {w.get("config") for w in cells}:
            errs.append(f"config {c.get('name')!r}: used by no cell")
    four = sum(1 for w in cells if w.get("chips") == 4)
    if four > max(1, len(cells) // 4):
        errs.append(f"{four} four-chip cells of {len(cells)}: at most "
                    f"{max(1, len(cells) // 4)} (25% rounded down, one "
                    "always allowed)")

    # metrics
    e2e, layer = manifest["end_to_end"], manifest["per_layer"]
    if not 1 <= len(e2e) <= 16:
        errs.append("end_to_end: 1 to 16 metrics")
    if not 1 <= len(layer) <= 128:
        errs.append("per_layer: 1 to 128 metrics")
    _unique([m.get("name") for m in e2e + layer], "metric", errs)
    if sum(1 for m in e2e if m.get("name") != "setup_s") > 4:
        errs.append("at most four end-to-end metrics besides setup_s")
    if "setup_s" not in [m.get("name") for m in e2e]:
        errs.append("end_to_end must hold setup_s")
    e2e_cells = {}
    for m in e2e:
        what = f"end-to-end metric {m.get('name')!r}"
        _keys(m, {"name", "unit", "better", "bound", "source"},
              {"workloads"}, what, errs)
        _metric_common(m, what, E2E_SOURCES, errs)
        b = m.get("bound")
        if not (isinstance(b, (int, float)) and not isinstance(b, bool)
                and 0.01 <= b <= MAX_BOUND):
            errs.append(f"{what}: bound is one relative number from "
                        f"0.01 to {MAX_BOUND}, got {b!r}")
        if m.get("name") == "setup_s":
            if "workloads" in m:
                errs.append("setup_s is reported by every cell and "
                            "takes no `workloads` list")
        elif not m.get("workloads"):
            errs.append(f"{what}: must list the cells that report it "
                        "(no metric but setup_s relies on 'all cells')")
        listed = _listed_cells(m, what, cell_names, errs)
        e2e_cells[m.get("name")] = set(listed)
    for m in layer:
        what = f"per-layer metric {m.get('name')!r}"
        _keys(m, {"name", "unit", "better", "source", "layer", "moves"},
              {"workloads"}, what, errs)
        _metric_common(m, what, SOURCES, errs)
        _line_ok(m.get("layer"), what + " layer", errs)
        if not m.get("workloads"):
            errs.append(f"{what}: must list the cells that report it")
        listed = _listed_cells(m, what, cell_names, errs)
        moves = m.get("moves")
        if not isinstance(moves, str) or moves not in e2e_cells:
            errs.append(f"{what}: moves {moves!r}, which is not one "
                        "end-to-end metric")
            continue
        for c in listed:
            if c not in e2e_cells[moves]:
                errs.append(
                    f"{what} is reported on workload {c}, where "
                    f"{moves}, which it should move, is not")
        reader = os.path.join(mf.HERE, "layer_metrics",
                              mf.reader_name(m.get("name", "")) + ".py")
        if not os.path.isfile(reader):
            errs.append(f"{what}: no reader {os.path.relpath(reader, root)}")

    # every cell: setup_s, one more end-to-end metric, one per-layer
    for c in cell_names:
        if not any(c in s for n, s in e2e_cells.items() if n != "setup_s"):
            errs.append(f"cell {c!r}: reports no end-to-end metric "
                        "besides setup_s")
        if not any(c in (m.get("workloads") or cell_names)
                   for m in layer):
            errs.append(f"cell {c!r}: reports no per-layer metric")
    return errs


def check_line(manifest: dict, cell_name: str, trace: int,
               line: str) -> List[str]:
    """A run's last printed line against what the manifest gives the
    cell for this --trace value."""
    errs: List[str] = []
    try:
        res = json.loads(line)
    except ValueError as e:
        return [f"the last line is not JSON: {e}"]
    for k in ("correct", "attempted", "failed", "metrics", "device"):
        if k not in res:
            errs.append(f"result lacks {k!r}")
    want = set(mf.metrics_for(manifest, cell_name, trace))
    got = set(res.get("metrics", {}))
    if got != want:
        errs.append(f"metrics of {cell_name} at --trace {trace}: "
                    f"missing {sorted(want - got)}, "
                    f"not in the manifest {sorted(got - want)}")
    for name, m in res.get("metrics", {}).items():
        if set(m) != {"value", "unit"} or isinstance(m["value"], bool) \
                or not isinstance(m["value"], (int, float)):
            errs.append(f"metric {name}: wants {{value, unit}} with a "
                        "number")
    dev = res.get("device", {})
    need = {"platform", "kind", "count", "memory_peak_bytes"}
    if trace:
        need |= {"busy_s", "window_s"}
    if need - set(dev):
        errs.append(f"device lacks {sorted(need - set(dev))}")
    return errs


def _under(path: str, paths: List[str]) -> bool:
    return any(path == p or path.startswith(p.rstrip("/") + "/")
               for p in paths)


def _unique(items, what: str, errs: List[str]) -> None:
    seen = set()
    for i in items:
        if i in seen:
            errs.append(f"{what} {i!r} appears twice")
        seen.add(i)


def _name_ok(name, what: str, errs: List[str]) -> None:
    if not (isinstance(name, str) and NAME.match(name)):
        errs.append(f"{what}: {name!r} is not a name (letters, digits, "
                    "_ . -, at most 64, not starting with . or -)")


def _is_width(key: str) -> bool:
    k = key.lower()
    return (k.endswith("_dim") or k.endswith("_rank")
            or any(s in k for s in (
                "hidden_size", "intermediate", "latent", "state_size",
                "proj", "d_model", "d_ff", "head_size", "expansion",
                "experts_per_tok")))


def _metric_common(m: dict, what: str, sources: set,
                   errs: List[str]) -> None:
    _name_ok(m.get("name"), what, errs)
    if not (isinstance(m.get("unit"), str) and UNIT.match(m["unit"])):
        errs.append(f"{what}: unit {m.get('unit')!r} is not a unit")
    if m.get("better") not in ("lower", "higher"):
        errs.append(f"{what}: better is 'lower' or 'higher'")
    if m.get("source") not in sources:
        errs.append(f"{what}: source must be one of {sorted(sources)}")


def _listed_cells(m: dict, what: str, cell_names, errs) -> List[str]:
    listed = m.get("workloads")
    if listed is None:
        return list(cell_names)
    if not isinstance(listed, list) or not listed:
        errs.append(f"{what}: workloads is a non-empty list")
        return []
    for c in listed:
        if c not in cell_names:
            errs.append(f"{what}: lists {c!r}, which is no cell")
    return [c for c in listed if c in cell_names]


def _cell_files(w: dict, root: str) -> List[str]:
    """The cell's workload file, its runner and its traffic data."""
    errs = []
    what = f"cell {w.get('name')!r}"
    wf = os.path.join(mf.HERE, "workloads", f"{w.get('name')}.json")
    if not os.path.isfile(wf):
        return [f"{what}: no workload file "
                f"{os.path.relpath(wf, root)}"]
    with open(wf) as f:
        data = json.load(f)
    if not wf.endswith(TRAFFIC_EXT):
        errs.append(f"{what}: traffic file must end in {TRAFFIC_EXT}")
    for k in ("config", "chips"):
        if data.get(k) != w.get(k):
            errs.append(f"{what}: workload file says {k} "
                        f"{data.get(k)!r}, the manifest {w.get(k)!r}")
    runner = os.path.join(mf.HERE, "runners",
                          f"{data.get('runner')}.py")
    if not os.path.isfile(runner):
        errs.append(f"{what}: no runner {os.path.relpath(runner, root)}")
    return errs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--manifest", default=mf.MANIFEST)
    ap.add_argument("--workload")
    ap.add_argument("--trace", type=int, choices=(0, 1))
    ap.add_argument("--line", help="a run's last printed line "
                    "('-' reads it from standard input)")
    ns = ap.parse_args(argv)
    manifest = mf.load(ns.manifest)
    errs = check(manifest, os.path.dirname(os.path.abspath(ns.manifest)))
    if ns.line is not None:
        if ns.workload is None or ns.trace is None:
            ap.error("--line needs --workload and --trace")
        line = sys.stdin.read().strip().splitlines()[-1] \
            if ns.line == "-" else ns.line
        errs += check_line(manifest, ns.workload, ns.trace, line)
    for e in errs:
        print("REFUSED: " + e)
    if not errs:
        n = len(manifest["workloads"])
        print(f"manifest ok: {n} cells, {len(manifest['end_to_end'])} "
              f"end-to-end and {len(manifest['per_layer'])} per-layer "
              "metrics")
    return 1 if errs else 0


if __name__ == "__main__":
    sys.exit(main())
