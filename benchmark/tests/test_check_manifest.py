"""The manifest checker: the committed BENCHMARK.json passes, and each
rule refuses the fault it is there for — PR 22's among them."""

import copy
import json

import pytest

from benchmark import check_manifest as cm
from benchmark import manifest as mf


@pytest.fixture()
def good():
    return copy.deepcopy(mf.load())


def test_committed_manifest_passes(good):
    assert cm.check(good) == []
    assert cm.main([]) == 0


def test_pr22_fault_is_refused(good):
    """A per-layer metric listed on a cell that lacks its `moves`
    metric: the sentence the driver refused PR 22 with."""
    m = next(x for x in good["per_layer"]
             if x["name"] == "compiles_in_window.train")
    m["workloads"].append("osu-allreduce-4rank")
    errs = cm.check(good)
    assert any("compiles_in_window.train" in e
               and "is reported on workload osu-allreduce-4rank" in e
               and "tokens_per_s, which it should move, is not" in e
               for e in errs), errs


def _drop_list(m):
    next(x for x in m["per_layer"]
         if x["name"] == "step_device_ms.train").pop("workloads")


def _bad_name(m):
    m["per_layer"][0]["name"] = "has space"


def _bad_unit(m):
    m["end_to_end"][1]["unit"] = "tokens per second"


def _five_e2e(m):
    m["end_to_end"].append(dict(m["end_to_end"][1], name="fifth"))


def _bound_on_cell(m):
    m["workloads"][0]["bound"] = 0.01


def _absolute_bound(m):
    m["end_to_end"][1]["bound"] = 5


def _run_seconds(m):
    m["run_seconds"] = 52


def _two_four_chip(m):
    m["workloads"][0]["chips"] = 4


def _unused_config(m):
    m["configs"].append(dict(m["configs"][0], name="spare",
                             file="benchmark/configs/osu-allreduce"
                                  ".rehearsal.json"))


def _long_source(m):
    m["configs"][0]["source"] = "x" * 201


def _missing_workload_file(m):
    m["workloads"].append(dict(m["workloads"][0], name="no-such-cell",
                               traffic="other"))
    for x in m["end_to_end"][1:2]:
        x["workloads"].append("no-such-cell")


def _missing_reader(m):
    m["per_layer"].append(dict(m["per_layer"][2], name="nobody_reads.train"))


def _moves_two(m):
    m["per_layer"][2]["moves"] = ["tokens_per_s", "setup_s"]


def _extra_key(m):
    m["per_layer"][0]["why"] = "because"


def _width_reduced(m):
    m["configs"][0]["reduced"].append("hidden_size")


def _setup_listed(m):
    m["end_to_end"][0]["workloads"] = ["opt30b-train-t1024"]


@pytest.mark.parametrize("fault, says", [
    (_drop_list, "must list the cells"),
    (_bad_name, "is not a name"),
    (_bad_unit, "is not a unit"),
    (_five_e2e, "at most four end-to-end"),
    (_bound_on_cell, "keys that are refused"),
    (_absolute_bound, "bound is one relative number"),
    (_run_seconds, "run_seconds"),
    (_two_four_chip, "four-chip cells"),
    (_unused_config, "used by no cell"),
    (_long_source, "1-200 characters"),
    (_missing_workload_file, "no workload file"),
    (_missing_reader, "no reader"),
    (_moves_two, "is not one end-to-end metric"),
    (_extra_key, "keys that are refused"),
    (_width_reduced, "never name a width"),
    (_setup_listed, "setup_s is reported by every cell"),
], ids=lambda x: getattr(x, "__name__", None))
def test_each_rule_refuses_its_fault(good, fault, says):
    fault(good)
    errs = cm.check(good)
    assert any(says in e for e in errs), errs


def _line(metrics, trace):
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 1}
    if trace:
        device.update(busy_s=1.0, window_s=2.0)
    return json.dumps({"correct": True, "attempted": 1, "failed": 0,
                       "metrics": {k: {"value": 1.0, "unit": "x"}
                                   for k in metrics}, "device": device})


@pytest.mark.parametrize("cell, trace", [
    ("opt30b-train-t1024", 0), ("opt30b-train-t1024", 1),
    ("opt30b-train-t2048", 0), ("opt30b-train-t2048", 1),
    ("osu-allreduce-4rank", 0), ("osu-allreduce-4rank", 1)])
def test_result_line_must_carry_exactly_the_cells_metrics(good, cell, trace):
    want = list(mf.metrics_for(good, cell, trace))
    assert cm.check_line(good, cell, trace, _line(want, trace)) == []
    assert cm.check_line(good, cell, trace, _line(want[1:], trace))
    assert cm.check_line(good, cell, trace,
                         _line(want + ["stray"], trace))


def test_train_and_sweep_cells_share_only_setup(good):
    train = set(mf.metrics_for(good, "opt30b-train-t1024", 0))
    sweep = set(mf.metrics_for(good, "osu-allreduce-4rank", 0))
    assert train & sweep == {"setup_s"}
    t1 = set(mf.metrics_for(good, "opt30b-train-t1024", 1))
    s1 = set(mf.metrics_for(good, "osu-allreduce-4rank", 1))
    assert t1 & s1 == {"init_s", "compile_s"}
