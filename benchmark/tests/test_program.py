"""layer_metrics/_program.py and _xplane.py: on hand-built event lists
(self time, the clock bracket's intersection, a gap left unattributed
while the bracket is wide, scope paths), against jax's own reader on a
recorded trace, and each new reader on fixtures cut from real traces of
the chip (PR 24, tools/cut_program_trace.py)."""

import importlib
import os
import sys

import pytest

from benchmark import manifest as mf
from benchmark import trace_reduce as tr
from benchmark.layer_metrics import _program as P
from benchmark.layer_metrics import _xplane
from benchmark.layer_metrics._xplane import Event

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
OSU = os.path.join(DATA, "osu_small12_large2.xplane.pb")
TRAIN = os.path.join(DATA, "train_t1024_scoped_two_steps.xplane.pb")


def ev(name, a, b, **stats):
    return Event(name, float(a), float(b), stats)


# -- hand-built events ---------------------------------------------------------

def _one_call(t, call, d=0.0, prog=(60, 64), wake=90, cold=0):
    """One traced iteration that starts at host time t; the chip's
    clock reads `d` more than the host's. Returns (host, modules)."""
    host = [
        ev("bench:collective call", t, t + 52),
        ev("ompi:api.Allreduce", t + 1, t + 51, call=call),
        ev("ompi:coll_xla.allreduce", t + 5, t + 49, call=call, nbytes=1024),
        ev("ompi:coll_xla.to_global", t + 10, t + 25, call=call, resident=1),
        ev(P.LAUNCH, t + 27, t + 45, call=call, program="ompi_allreduce",
           cold=cold, nbytes=1024),
        ev("ompi:coll_xla.my_shard", t + 46, t + 48, call=call),
        ev(P.WAIT, t + 53, t + wake),
    ]
    mods = [ev("jit_broadcast_in_dim(1)", t + 20 + d, t + 21 + d),
            ev("jit_ompi_allreduce(2)", t + prog[0] + d, t + prog[1] + d)]
    return host, mods


def test_self_time_is_the_span_less_its_direct_children():
    host, _ = _one_call(1000, call=7)
    spans = P.nest(host[::-1])  # whatever order the file has them in
    by = {s["name"]: s for s in spans}
    assert by["ompi:api.Allreduce"]["self"] == 50 - 44
    assert by["ompi:coll_xla.allreduce"]["self"] == 44 - 15 - 18 - 2
    assert by[P.LAUNCH]["self"] == 18
    assert by["bench:collective call"]["self"] == 52 - 50
    assert spans[by[P.LAUNCH]["parent"]]["name"] == "ompi:coll_xla.allreduce"
    assert by["bench:collective call"]["parent"] is None
    stats = P.span_stats(spans, 0, 5000)
    assert stats["ompi:api.Allreduce"] == {
        "count": 1, "median_us": 0.05, "p95_us": 0.05,
        "self_median_us": 0.006}
    # outside the window: not counted
    assert P.span_stats(spans, 0, 1040).keys() == {
        "ompi:coll_xla.to_global"}


def test_a_cold_launch_is_kept_apart():
    host, _ = _one_call(0, call=1, cold=1)
    stats = P.span_stats(P.nest(host), 0, 100)
    assert P.LAUNCH + " cold" in stats and P.LAUNCH not in stats


def test_bracket_is_the_intersection_over_all_iterations():
    d = -800.0  # the chip's clock reads 800 ns less (PR 23's trace)
    host, mods = [], []
    # iteration k: program starts s_k after the launch span's start
    # and ends e_k before the wake
    for k, (s, e) in enumerate([(40, 30), (33, 26), (50, 12), (36, 40)]):
        h, m = _one_call(1000 * k, call=k, d=d,
                         prog=(27 + s, 90 - e - 0), wake=90)
        host += h
        mods += m
    its = P.iterations(P.nest(host), mods)
    assert len(its) == 4
    lo, hi = P.bracket(its)
    # hi = min(prog start - launch start), lo = max(prog end - wake)
    assert hi == pytest.approx(d + 33)
    assert lo == pytest.approx(d - 12)
    assert lo <= d <= hi
    # one more launch on the chip than the host has spans: no pairing
    mods.append(ev("jit_ompi_allreduce(2)", 9000, 9004))
    assert P.iterations(P.nest(host), mods) == []
    assert P.bracket([]) is None


def test_gap_stays_unattributed_while_the_bracket_is_wider():
    host, _ = _one_call(0, call=1)
    spans = P.nest(host + [ev(tr.WINDOW + "small", 0, 100)])
    ops = [ev("%copy.1", 20, 21), ev("%psum.7", 60, 64)]
    # exact clock: the 39 ns between the two ops fall to the launch
    # span (their middle, 40.5, is inside 27..45), the 20 ns before
    # the first to to_global's parent... the innermost span at t=10
    gaps = P.gaps_by_span(spans, ops, 0, 100, (0.0, 0.0))
    assert gaps[P.LAUNCH] == pytest.approx(39)
    assert gaps["ompi:coll_xla.to_global"] == pytest.approx(20)
    assert gaps[P.WAIT] == pytest.approx(36)
    assert P.UNATTRIBUTED not in gaps
    # a bracket 30 ns wide: only the gaps longer than that are named
    gaps = P.gaps_by_span(spans, ops, 0, 100, (-15.0, 15.0))
    assert gaps[P.UNATTRIBUTED] == pytest.approx(20)
    assert gaps[P.LAUNCH] == pytest.approx(39)
    # no bracket at all (a program without spans): nothing is named
    assert P.gaps_by_span(spans, ops, 0, 100, None) == {
        P.UNATTRIBUTED: pytest.approx(95)}


def test_gaps_move_onto_the_hosts_clock_by_the_brackets_middle():
    host, _ = _one_call(0, call=1)
    spans = P.nest(host)
    # the chip reads 800 less: the op at host time 60..64 is stamped
    # -740..-736
    ops = [ev("%psum.7", -740, -736)]
    gaps = P.gaps_by_span(spans, ops, 0, 100, (-801.0, -799.0))
    assert gaps == {P.LAUNCH: pytest.approx(60), P.WAIT: pytest.approx(36)}


@pytest.mark.parametrize("path, want", [
    ("jit(ompi_train_step)/transpose(jvp(layer_0))/attn_core/dot_general:",
     ["layer_0", "attn_core"]),
    ("jit(ompi_train_step)/jvp(layer_12)/ln/reduce_sum", ["layer_12", "ln"]),
    ("jit(ompi_train_step)/sgd_update/sub", ["sgd_update"]),
    ("jit(ompi_train_step)/jvp(head_loss)/ln/mul", ["head_loss", "ln"]),
    ("jit(step)/jit(main)/dot_general", []),
    ("jit(ompi_allreduce)/allreduce/psum", []),
    (None, []),
])
def test_scopes_of_an_op_path(path, want):
    assert P.scopes_of(path) == want


def test_scope_busy_is_a_union_per_launch_and_a_median_over_them():
    def op(a, b, path=None):
        return ev("%x", a, b, **({"tf_op": path} if path else {}))

    ops = [op(0, 10, "jit(s)/jvp(layer_0)/attn_core/dot"),
           op(5, 15, "jit(s)/transpose(jvp(layer_0))/attn_core/dot"),
           op(15, 18, "jit(s)/jvp(head_loss)/exp"),
           op(18, 20),
           op(100, 130, "jit(s)/jvp(layer_0)/attn_core/dot"),
           op(130, 131)]
    one = P.scope_busy(ops, 0, 50)
    assert one == {"layer_0": 15, "attn_core": 15, "head_loss": 3,
                   "unscoped": 2, "all": 20}
    assert P.scope_busy(ops, 8, 16)["attn_core"] == 7  # clipped
    launches = [ev("jit_s(1)", 0, 50), ev("jit_s(1)", 100, 150)]
    per = P.scope_busy_per_launch(ops, launches)
    assert per["attn_core"] == pytest.approx((15 + 30) / 2)
    assert per["head_loss"] == pytest.approx(1.5)  # absent in one
    assert P.scope_busy_per_launch(ops, []) == {}


def test_analyse_one_window_end_to_end():
    d = -700.0
    host = [ev(tr.WINDOW + "small", -5, 3100)]
    mods, ops = [], []
    for k in range(3):
        h, m = _one_call(1000 * k, call=k, d=d)
        host += h
        mods += m
        ops += [ev("%copy.1", m[0].start_ns, m[0].end_ns),
                ev("%psum.7", m[1].start_ns, m[1].end_ns,
                   tf_op="jit(ompi_allreduce)/allreduce/psum")]
    a = P.analyse({"host": {"python": host, "other": host[:2]},
                   "chips": {"/device:TPU:0": {tr.OPS_LINE: ops,
                                               tr.MODULES_LINE: mods}}})
    lo, hi = a["clock_bracket_ns"]
    assert lo == pytest.approx(d + 64 - 90) and hi == pytest.approx(d + 33)
    assert a["iterations_paired"] == 3
    w = a["windows"]["small"]
    assert w["module_launches"] == {"broadcast_in_dim": 3,
                                    "ompi_allreduce": 3}
    assert w["spans"]["ompi:api.Allreduce"]["count"] == 3
    hp = w["host_path"]
    assert hp["iteration_median_us"] == pytest.approx(90 / 1e3)
    assert hp["parts_us"] == pytest.approx({
        "api_self": 0.006, "slot_self": 0.009, "to_global": 0.015,
        "launch": 0.018, "my_shard": 0.002, "wait": 0.037})
    assert hp["sum_us"] == pytest.approx(0.087)
    assert hp["sum_share"] == pytest.approx(87 / 90)
    assert hp["accounted_share"] == pytest.approx((50 + 37) / 90)
    mid = (lo + hi) / 2
    split = w["wait_split"]
    assert split["launch_end_to_program_start"]["median_us"] == \
        pytest.approx((60 + d - mid - 45) / 1e3)
    assert split["program_end_to_wake"]["median_us"] == \
        pytest.approx((90 - (64 + d - mid)) / 1e3)
    assert split["uncertainty_us"] == pytest.approx((hi - lo) / 2e3)
    assert P.api_span_name(w["spans"]) == "ompi:api.Allreduce"
    assert P.slot_span_name(w["spans"]) == "ompi:coll_xla.allreduce"


def test_a_program_without_spans_gives_every_reader_nothing(monkeypatch):
    """The parent commit: windows and device lines, no `ompi:` span, no
    counter. No reader raises; each returns None."""
    host = [ev(tr.WINDOW + "small", 0, 3000)]
    mods = []
    for k in range(3):
        host += [ev("bench:collective call", 1000 * k, 1000 * k + 50),
                 ev(P.WAIT, 1000 * k + 51, 1000 * k + 90)]
        mods.append(ev("jit__lambda(2)", 1000 * k + 60, 1000 * k + 64))
    a = P.analyse({"host": {"python": host},
                   "chips": {"/device:TPU:0": {
                       tr.OPS_LINE: [ev("%psum.7", m.start_ns, m.end_ns)
                                     for m in mods],
                       tr.MODULES_LINE: mods}}})
    assert a["clock_bracket_ns"] is None
    assert list(a["windows"]["small"]["idle_gaps_us"]) == [P.UNATTRIBUTED]
    monkeypatch.setattr(P, "analysis", lambda: a)
    monkeypatch.setattr(P, "counter", lambda name: None)
    for name in _new_metrics():
        assert _read(name) is None, name


# -- the decoder against jax's own reader -------------------------------------

def test_xplane_decoder_agrees_with_jax_on_a_recorded_trace():
    pytest.importorskip("jax")
    from jax.profiler import ProfileData

    path = os.path.join(DATA, "train_t1024_two_steps.xplane.pb")
    mine = _xplane.planes(path, keep=lambda p: True)
    theirs = {}
    for plane in ProfileData.from_file(path).planes:
        theirs[plane.name] = {
            line.name: [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events] for line in plane.lines}
    assert mine.keys() == theirs.keys()
    n = 0
    for p, lines in theirs.items():
        assert mine[p].keys() == lines.keys()
        for ln, events in lines.items():
            got = [(e.name, e.start_ns, e.end_ns) for e in mine[p][ln]]
            assert len(got) == len(events)
            for g, t in zip(got, events):
                assert g[0] == t[0]
                assert g[1:] == pytest.approx(t[1:], abs=2.0)  # jax drops the ps
            n += len(events)
    assert n > 1000


# -- the new readers on fixtures cut from the chip's traces -------------------

def _new_metrics():
    old = {"init_s", "compile_s", "compiles_in_window", "step_device_ms",
           "step_roofline", "device_idle", "dispatch_us", "coll_device_us",
           "ici_share"}
    return [m["name"] for m in mf.load()["per_layer"]
            if mf.reader_name(m["name"]) not in old]


def _read(name):
    reader = importlib.import_module(
        "benchmark.layer_metrics." + mf.reader_name(name))
    return reader.read({"spans": {}, "counters": {}, "facts": {},
                        "trace": None, "peaks": None, "ranks": 4})


@pytest.fixture
def osu_run(monkeypatch):
    a = P.analyse(P.load(OSU))
    monkeypatch.setattr(P, "analysis", lambda: a)
    return a


@pytest.fixture
def train_run(monkeypatch):
    a = P.analyse(P.load(TRAIN))
    monkeypatch.setattr(P, "analysis", lambda: a)
    return a


def test_osu_span_readers_on_a_trace_cut_from_the_chip(osu_run):
    """The first 12 small and 2 large traced iterations of a real
    four-rank sweep (my chip run PR 24, rank 0)."""
    assert osu_run["iterations_paired"] == 14
    assert osu_run["clock_bracket_ns"] == pytest.approx(
        [-2038323.672, -1526493.75])
    assert _read("api_self_us.osu") == pytest.approx(41.85)
    assert _read("coll_slot_self_us.osu") == pytest.approx(112.155)
    assert _read("to_global_us.osu") == pytest.approx(567.39)
    assert _read("launch_us.osu") == pytest.approx(261.66)
    assert _read("programs_per_call.osu") == 2.0
    small = osu_run["windows"]["small"]
    assert small["module_launches"] == {"broadcast_in_dim": 12,
                                        "ompi_allreduce": 12}
    hp = small["host_path"]
    assert hp["iteration_median_us"] == pytest.approx(1726.065)
    assert hp["sum_us"] == pytest.approx(1560.95)
    assert 0.97 < hp["accounted_share"] < 1.0
    # the bracket (512 us wide here) is narrower than the to_global
    # gaps and wider than the short ones
    gaps = small["idle_gaps_us"]
    assert max(gaps, key=gaps.get) == "ompi:coll_xla.to_global"
    assert gaps[P.UNATTRIBUTED] > 0
    # every span of one call carries its number
    spans = P.nest(next(iter(P.load(OSU)["host"].values())))
    by_call = {}
    for s in spans:
        if s["name"].startswith(P.OMPI):
            by_call.setdefault(s["args"]["call"], []).append(s["name"])
    assert len(by_call) == 14
    assert all(sorted(v) == sorted([
        "ompi:api.Allreduce", "ompi:coll_xla.allreduce",
        "ompi:coll_xla.to_global", P.LAUNCH, "ompi:coll_xla.my_shard"])
        for v in by_call.values())


@pytest.mark.parametrize("metric, counter, ns, want", [
    ("cold_launch_s.osu", "coll_xla_cold_launch_ns", 2478160115,
     2.478160115),
    ("init_import_s.train", "init_import_ns", 3086012400, 3.0860124),
    ("init_distributed_s.osu", "init_distributed_ns", 1106818817, 1.106818817),
    ("init_client_s.osu", "init_client_ns", 24835323430, 24.83532343),
    ("init_fence_s.train", "init_fence_ns", 11148420, 0.01114842),
    ("init_world_s.osu", "init_world_ns", 136871389, 0.136871389),
])
def test_counter_readers_give_seconds_or_nothing(monkeypatch, metric,
                                                 counter, ns, want):
    monkeypatch.setattr(P, "counter",
                        lambda name: ns if name == counter else None)
    assert _read(metric) == pytest.approx(want)
    monkeypatch.setattr(P, "counter", lambda name: None)
    assert _read(metric) is None


def test_counter_reads_the_programs_pvars():
    from ompi_tpu.core import pvar

    assert P.counter("init_no_such_phase_ns") is None
    pvar.record("init_fence_ns", 7)
    assert P.counter("init_fence_ns") >= 7


def test_train_scope_readers_on_a_trace_cut_from_the_chip(train_run):
    """Two whole steps of a real opt30b-train-t1024 trace (my chip run
    PR 24): the ops' `tf_op` paths carry the model's named scopes."""
    win = train_run["windows"]["train"]
    assert win["module_launches"] == {"ompi_train_step": 2}
    assert train_run["clock_bracket_ns"] is None  # no program span in
    # the step: nothing pairs a launch with the host here
    got = {m: _read(m) for m in ("head_loss_ms.train", "attn_core_ms.train",
                                 "sgd_update_ms.train", "unscoped_ms.train")}
    assert got == pytest.approx({
        "head_loss_ms.train": 53.1066, "attn_core_ms.train": 45.9192,
        "sgd_update_ms.train": 3.1547, "unscoped_ms.train": 6.9255},
        rel=1e-3)
    busy = win["scope_busy_us"]
    # the parts tile the step: the layers' parts are the layers, and
    # with head, embedding, update and the unclaimed rest they are all
    layers = sum(busy[f"layer_{i}"] for i in range(3))
    assert busy["mlp"] + busy["attn_proj"] + busy["attn_core"] + busy["ln"] \
        == pytest.approx(layers, rel=2e-3)
    assert layers + busy["head_loss"] + busy["embed"] + busy["sgd_update"] \
        + busy["unscoped"] == pytest.approx(busy["all"], rel=2e-3)
    assert busy["unscoped"] < 0.05 * busy["all"]


def test_scope_readers_give_nothing_for_a_trace_without_scopes(monkeypatch):
    """The parent's step: same ops, no scope in any op path."""
    old = os.path.join(DATA, "train_t1024_two_steps.xplane.pb")
    a = P.analyse(P.load(old))
    monkeypatch.setattr(P, "analysis", lambda: a)
    assert a["windows"]["train"]["module_launches"] == {"step": 1}
    assert set(a["windows"]["train"]["scope_busy_us"]) == {"all", "unscoped"}
    for m in ("head_loss_ms.train", "attn_core_ms.train",
              "sgd_update_ms.train", "unscoped_ms.train"):
        assert _read(m) is None
