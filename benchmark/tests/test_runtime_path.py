"""layer_metrics/_runtime.py: jax's and the runtime's own events beneath
the program's spans. On a fixture cut from a four-chip trace WITH those
events (PR 48, tools/cut_runtime_trace.py), on hand-built events under
the CPU client's names, and on what has none of them: the accepted
fixture cut to the `ompi:` / `bench:` names, and a run with no trace."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from benchmark import manifest as mf
from benchmark import trace_reduce as tr
from benchmark.layer_metrics import _program as P
from benchmark.layer_metrics import _runtime as R
from benchmark.layer_metrics._xplane import Event

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
CUT = os.path.join(DATA, "osu_small_runtime.xplane.pb")
BARE = os.path.join(DATA, "osu_small12_large2.xplane.pb")
TRAIN = os.path.join(DATA, "train_t1024_scoped_two_steps.xplane.pb")
SIX = ("launch_jit_us", "launch_execute_us", "enqueue_to_start_us",
       "wake_us", "wake_after_done_us", "clock_bracket_us")


@pytest.fixture(scope="module")
def cut():
    events = R.load(CUT)
    return events, R.analyse(events)


def _joined(events):
    roles = {r: R.Role(r, events["host"]) for r in R.ROLES}
    spans = P.nest(events["host"][events["caller"]])
    chip = next(iter(events["chips"].values()))
    modules = chip[tr.MODULES_LINE]
    iters = R.iterations(spans, roles)
    R.join_done(iters, roles["done"])
    R.join_modules(iters, modules)
    return roles, spans, modules, iters


# -- the chip's trace ---------------------------------------------------------

def test_roles_found_on_the_chips_trace(cut):
    _, a = cut
    found = {r: v["event"] for r, v in a["roles"].items()}
    assert found == {
        "jit_call": "PjitFunction(",
        "execute": "PJRT_LoadedExecutable_Execute",
        "enqueue": "DoEnqueueProgram",
        "await": None,  # libtpu 0.0.34 writes nothing under the wait
        "done": "CompleteCallbacks"}
    # the runtime writes the caller thread's events on a line of its
    # own and the completion on another thread
    lines = {r: v["line"] for r, v in a["roles"].items()}
    assert lines["jit_call"] == "python3"
    assert lines["execute"].startswith("main/")
    assert lines["done"] not in (lines["execute"], "python3")
    assert a["iterations"] == 14
    assert a["windows"]["small"]["iterations"] == 12
    assert a["windows"]["large"]["iterations"] == 2
    for role in ("jit_call", "execute", "enqueue", "done"):
        assert a["windows"]["small"]["roles_us"][role]["count"] == 12
    assert a["windows"]["small"]["roles_us"]["await"] is None


def test_every_iteration_is_whole_and_nested(cut):
    events, _ = cut
    _, _, _, iters = _joined(events)
    for i in iters:
        la, lb = i["launch"]
        assert la <= i["jit_call"][0] <= i["execute"][0] \
            <= i["enqueue"][0] <= i["enqueue"][1] <= i["execute"][1] \
            <= i["jit_call"][1] <= lb <= i["wait"][0]
        # the completion is written while the caller waits
        assert i["wait"][0] < i["done"][0] < i["wait"][1]
        assert i["prog"] is not None and i["run_id"] is not None


def test_identity_holds_per_iteration_whatever_the_clock(cut):
    events, a = cut
    _, _, _, iters = _joined(events)
    for d in (0.0, -1.7e6, sum(a["bracket_ns"]) / 2):
        for i in iters:
            s = R.split(i, d)
            assert abs(s["enqueue_to_start"] + s["program"] + s["wake"]
                       - (i["wait"][1] - i["launch"][1])) < 1.0  # ns
            assert abs(s["launch_jit"] + s["execute"] - s["launch"]) < 1e-6
    for w in a["windows"].values():
        assert w["identity_worst_ns"] < 1.0


def test_bracket_lies_inside_the_programs_and_is_narrower(cut):
    events, a = cut
    old = P.analyse(P.load(CUT))["clock_bracket_ns"]
    new = a["bracket_ns"]
    assert a["bracket_program_ns"] == old
    assert old[0] <= new[0] < new[1] <= old[1]
    assert new[1] - new[0] < 0.7 * (old[1] - old[0])
    assert a["metrics"]["clock_bracket_us"] == (new[1] - new[0]) / 1e3
    # the runtime's two inequalities hold for every iteration at
    # every d of the bracket, by construction
    _, _, _, iters = _joined(events)
    for i in iters:
        assert i["enqueue"][0] + new[1] <= i["prog"][0]
        assert i["prog"][1] <= i["done"][0] + new[0]


def test_run_id_pairs_what_order_pairs(cut):
    events, a = cut
    _, _, modules, iters = _joined(events)
    assert a["program_joined_by"] == "run_id"
    assert a["done_joined_by"] == "run_id"
    by_id = R.modules_by_run_id(iters, modules)
    by_order = R.modules_by_order(iters, modules)
    assert all(by_id) and by_id == by_order
    # and with the identities hidden it falls back to order
    bare = [dict(i, run_id=None, prog=None) for i in iters]
    assert R.join_modules(bare, modules) == "order"
    assert [i["prog"] for i in bare] == [i["prog"] for i in iters]


def test_the_six_metrics_of_the_cut(cut):
    _, a = cut
    m = a["metrics"]
    assert set(m) == set(SIX)
    launch = P.analyse(P.load(CUT))["windows"]["small"]["spans"][
        P.LAUNCH]["median_us"]
    assert abs(m["launch_jit_us"] + m["launch_execute_us"] - launch) \
        < 0.03 * launch
    assert m["launch_execute_us"] > 3 * m["launch_jit_us"]
    # the caller's own share of the wake is the small one
    assert 0 < m["wake_after_done_us"] < 0.1 * m["wake_us"]
    # whatever d of the bracket is the true one, most of the wait is
    # after the program's end
    assert m["wake_us"] - m["clock_bracket_us"] / 2 > 0
    # the table of everything in an iteration: the execute's largest
    # children are there by name
    names = [r["name"] for r in a["windows"]["small"]["timeline"]]
    for want in ("ompi:coll_xla.launch", "ParseArguments",
                 "AllocateOutputBuffersWithInputReuse",
                 "TpuLoadedExecutable::ExecuteLaunch", "ReadSyncFlag"):
        assert want in names


# -- the CPU client's names -----------------------------------------------------

def ev(name, a, b, **stats):
    return Event(name, float(a), float(b), stats)


def _cpu_iteration(t, run_id, inline):
    """One traced iteration of the CPU rehearsal (jaxlib 0.9.0): the
    program runs on the caller's thread (`inline`) or on the client's
    pool thread, which then writes the completion."""
    py = [
        ev("bench:collective call", t, t + 700),
        ev("ompi:api.Allreduce", t + 5, t + 690, call=run_id),
        ev(P.LAUNCH, t + 200, t + 660, call=run_id, cold=0,
           program="ompi_allreduce", nbytes=1024),
        ev("PjitFunction(ompi_allreduce)", t + 202, t + 655),
        ev("PjitFunction(ompi_allreduce)", t + 203, t + 654),
        ev("ParseArguments", t + 205, t + 206),
        ev("PjRtCpuExecutable::Execute", t + 220, t + 640),
        ev("PjRtCpuExecutable::ExecuteHelper", t + 222, t + 638,
           run_id=run_id),
        ev(P.WAIT, t + 710, t + 1500),
        ev("CommonPjRtBuffer::Await", t + 715, t + 716),
    ]
    done = ev("ThunkExecutor::Execute (wait for completion)",
              t + (600 if inline else 1400), t + (601 if inline else 1401),
              run_id=run_id)
    return (py + [done], []) if inline else (py, [done])


def test_roles_found_under_the_cpu_clients_names():
    py, pool = [], []
    for k, inline in enumerate([True, False, False, True]):
        a, b = _cpu_iteration(10_000 * k, run_id=-77 - k, inline=inline)
        py += a
        pool += b
    host = {"python": py + [ev("bench_window:small", -10, 50_000)],
            "tf_XLAPjRtCpuClient/1": pool}
    a = R.analyse({"host": host, "caller": "python",
                   "windows": [("small", -10, 50_000)], "chips": {}})
    assert {r: v["event"] for r, v in a["roles"].items()} == {
        "jit_call": "PjitFunction(",
        "execute": "PjRtCpuExecutable::Execute",
        "enqueue": "PjRtCpuExecutable::ExecuteHelper",
        "await": "CommonPjRtBuffer::Await",
        "done": "ThunkExecutor::Execute (wait for completion)"}
    assert a["done_joined_by"] == "run_id"
    assert a["program_joined_by"] is None and a["bracket_ns"] is None
    s = a["windows"]["small"]["split_us"]
    assert s["launch_jit"]["median_us"] == pytest.approx(0.04)
    assert s["execute"]["median_us"] == pytest.approx(0.42)
    assert s["wake_after_done"]["count"] == 4
    assert s["wake"] is None  # no chip: nothing on a device clock
    assert set(a["metrics"]) == {
        "launch_jit_us", "launch_execute_us", "wake_after_done_us"}


def test_done_falls_back_to_order_only_where_counts_agree():
    py, pool = [], []
    for k in range(3):
        a, b = _cpu_iteration(10_000 * k, run_id=k, inline=False)
        py += a
        pool += b
    strip = lambda evs: [Event(e.name, e.start_ns, e.end_ns, {})  # noqa: E731
                         for e in evs]
    host = {"python": strip(py), "pool": strip(pool)}
    roles = {r: R.Role(r, host) for r in R.ROLES}
    iters = R.iterations(P.nest(host["python"]), roles)
    assert R.join_done(iters, roles["done"]) == "order"
    assert all(i["done"] for i in iters)
    host["pool"] = host["pool"][:-1]  # one completion missing
    roles = {r: R.Role(r, host) for r in R.ROLES}
    iters = R.iterations(P.nest(host["python"]), roles)
    assert R.join_done(iters, roles["done"]) is None
    assert not any(i["done"] for i in iters)


def test_the_programs_own_wait_ends_an_iteration_too():
    """A nonblocking or persistent collective's wait is the program's
    `ompi:coll_xla.wait`, not the benchmark's span."""
    py, _ = _cpu_iteration(0, run_id=1, inline=True)
    py = [Event(P.OMPI + "coll_xla.wait", e.start_ns, e.end_ns,
                {"program": "ompi_allreduce", "launch_call": 1})
          if e.name == P.WAIT else e for e in py]
    roles = {r: R.Role(r, {"python": py}) for r in R.ROLES}
    (it,) = R.iterations(P.nest(py), roles)
    assert it["wait"] == (710.0, 1500.0) and it["await"] == (715.0, 716.0)


# -- nothing to read --------------------------------------------------------------

def _read_all(monkeypatch, path):
    monkeypatch.setattr(P, "trace_path", lambda: path)
    monkeypatch.setattr(P, "out_dir", lambda: None)
    R._cache.clear()
    try:
        return {name: importlib.import_module(
            "benchmark.layer_metrics." + name).read({}) for name in SIX}
    finally:
        R._cache.clear()


def test_readers_give_nothing_without_the_runtimes_events(monkeypatch,
                                                          capsys):
    assert set(_read_all(monkeypatch, BARE).values()) == {None}
    assert "no event of" in capsys.readouterr().out
    assert set(_read_all(monkeypatch, None).values()) == {None}


def test_readers_read_the_cut(monkeypatch, capsys):
    got = _read_all(monkeypatch, CUT)
    assert all(v is not None for v in got.values())
    out = capsys.readouterr().out
    assert out.count("program: runtime role ") == len(R.ROLES)
    assert "with the runtime's events" in out


def test_a_train_window_gets_one_line_naming_its_longest_gap(capsys):
    a = R.analyse(R.load(TRAIN))
    gap = a["windows"]["train"]["longest_idle_gap"]
    assert gap["gap_us"] > 0 and set(gap["covered_by"]) == {"python3"}
    assert a["metrics"] == {}
    R.write(a, None)
    out = capsys.readouterr().out
    assert out.count("longest device idle gap") == 1


def test_longest_gap_names_the_innermost_event_of_every_thread():
    host = {"python3": [ev("bench:wait for loss", 0, 1000),
                        ev("CommonPjRtBuffer::Await", 100, 900)],
            "tfrt-7": [ev("Compile", 450, 700)], "idle-thread": []}
    ops = [ev("fusion", 0 + 5, 200 + 5), ev("fusion", 800 + 5, 990 + 5)]
    gap = R.longest_gap(host, ops, 0, 1000, d=5.0)
    assert gap["gap_us"] == pytest.approx(0.6)
    assert gap["covered_by"] == {"python3": "CommonPjRtBuffer::Await",
                                 "tfrt-7": "Compile", "idle-thread": None}


# -- the manifest -----------------------------------------------------------------

def test_manifest_names_the_six_and_a_whole_line_passes():
    root = os.path.dirname(os.path.dirname(HERE))
    check = [sys.executable, os.path.join(root, "benchmark",
                                          "check_manifest.py")]
    p = subprocess.run(check, capture_output=True, text=True)
    assert p.returncode == 0, p.stdout
    manifest = mf.load()
    cell = "osu-allreduce-4rank"
    mine = {n: m for n, m in mf.metrics_for(manifest, cell, 1).items()
            if mf.reader_name(n) in SIX}
    assert len(mine) == 6
    for m in mine.values():
        assert (m["unit"], m["better"], m["moves"], m["workloads"]) == \
            ("us", "lower", "coll_lat_p50", [cell])
    line = json.dumps({
        "correct": True, "attempted": 1, "failed": 0,
        "metrics": {n: {"value": 1.0, "unit": m["unit"]} for n, m in
                    mf.metrics_for(manifest, cell, 1).items()},
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 4,
                   "memory_peak_bytes": 1, "busy_s": 0.1, "window_s": 0.2},
        "breakdown": {"device_ops": [], "idle_gaps": []}})
    p = subprocess.run(check + ["--workload", cell, "--trace", "1",
                                "--line", line],
                       capture_output=True, text=True)
    assert p.returncode == 0, p.stdout
