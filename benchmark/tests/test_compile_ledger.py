"""The five `compile_*` readers (PR 34): nothing from a program that
keeps no compile ledger (a parent commit), seconds and a share from
one that does, the information lines, and the manifest with the ten
entries."""

import importlib

import pytest

from benchmark import check_manifest, manifest as mf
from benchmark.layer_metrics import _compile, _program as P

TRAIN = ["opt30b-train-t1024", "opt30b-train-t2048", "olmoe-train-t4096",
         "glm5-train-t4096", "ouro-train-t4096"]
READERS = ("compile_trace_s", "compile_lower_s", "compile_backend_s",
           "compile_cache_load_s", "compile_cache_hit_share")
METRICS = [r + suffix for r in READERS for suffix in (".train", ".osu")]


def _read(name):
    reader = importlib.import_module(
        "benchmark.layer_metrics." + mf.reader_name(name))
    return reader.read({"spans": {}, "counters": {}, "facts": {},
                        "trace": None, "peaks": None, "ranks": 1})


def _counters(monkeypatch, values):
    monkeypatch.setattr(P, "counter", lambda name: values.get(name))


@pytest.mark.parametrize("metric", METRICS)
def test_reader_gives_nothing_without_the_counters(monkeypatch, metric):
    _counters(monkeypatch, {})
    assert _read(metric) is None


@pytest.mark.parametrize("reader, counter", [
    ("compile_trace_s", "compile_trace_ns"),
    ("compile_lower_s", "compile_lower_ns"),
    ("compile_backend_s", "compile_backend_ns"),
    ("compile_cache_load_s", "compile_cache_load_ns"),
])
def test_phase_reader_gives_seconds_and_zero_is_a_reading(
        monkeypatch, capsys, reader, counter):
    monkeypatch.setattr(_compile, "table", lambda: None)
    _counters(monkeypatch, {"compile_programs": 2, counter: 1_600_000_000})
    assert _read(reader + ".train") == pytest.approx(1.6)
    # a cold run loads nothing, a warm one compiles nothing: 0.0
    _counters(monkeypatch, {"compile_programs": 2})
    assert _read(reader + ".osu") == 0.0
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("values, want", [
    ({"compile_cache_requests": 4, "compile_cache_hits": 3}, 0.75),
    ({"compile_cache_requests": 2, "compile_cache_hits": 2}, 1.0),
    ({"compile_cache_requests": 3}, 0.0),  # a checkout's first run
    ({"compile_cache_hits": 1}, None),
])
def test_hit_share_is_hits_over_requests(monkeypatch, values, want):
    _counters(monkeypatch, values)
    assert _read("compile_cache_hit_share.train") == want


def test_trace_reader_prints_own_rows_and_one_foreign_line(
        monkeypatch, capsys):
    phases = ("trace", "lower", "backend", "cache_load")

    def row(program, own, ns, hits=0, misses=0):
        return {"program": program, "own": own, "hits": hits,
                "misses": misses, "ns": dict(zip(phases, ns)),
                "runs": dict(zip(phases, [1 if n else 0 for n in ns]))}

    rows = [row("convert_element_type", False, (1, 2, 3, 0), misses=1),
            row("ompi_train_step", True,
                (1_600_000_000, 500_000_000, 20_000_000, 1_700_000_000),
                hits=1),
            row("sgd_step", False, (4_000_000_000, 0, 0, 0))]
    monkeypatch.setattr(_compile, "table", lambda: rows)
    _counters(monkeypatch, {"compile_programs": 1,
                            "compile_trace_ns": 1_600_000_000})
    assert _read("compile_trace_s.train") == pytest.approx(1.6)
    own, foreign = capsys.readouterr().out.splitlines()
    assert own == ("program: compile ompi_train_step: trace 1.600 s x1, "
                   "lower 0.500 s x1, backend 0.020 s x1, cache_load "
                   "1.700 s x1; cache 1 hit(s) 0 miss(es) (information)")
    assert foreign.startswith("program: compile foreign: 2 program(s), "
                              "4.000 s in no metric; most: sgd_step "
                              "4.000 s, convert_element_type 0.000 s")


def test_table_is_the_programs_or_nothing(monkeypatch):
    from ompi_tpu import prof

    assert _compile.table() == prof.compile_table()
    monkeypatch.delattr(prof, "compile_table")  # a parent commit
    assert _compile.table() is None


def test_manifest_has_the_ten_entries_and_passes():
    manifest = mf.load()
    assert check_manifest.check(manifest) == []
    by = {m["name"]: m for m in manifest["per_layer"]}
    for name in METRICS:
        m = by[name]
        assert (m["layer"], m["moves"], m["source"]) == (
            "compile", "setup_s", "program_counter")
        share = name.startswith("compile_cache_hit_share")
        assert (m["unit"], m["better"]) == (
            ("ratio", "higher") if share else ("s", "lower"))
        assert m["workloads"] == (TRAIN if name.endswith(".train")
                                  else ["osu-allreduce-4rank"])
    # new entries go at the end of the list
    assert [m["name"] for m in manifest["per_layer"]][-10:] == METRICS
