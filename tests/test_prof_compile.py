"""The compile ledger (ompi_tpu/prof/compile.py): the rules of the
account on synthetic events and on real jax compiles (nested traces,
a cold run then the same cache directory again, own against foreign),
that a warm launch calls no listener, the `compile.*` spans on both
sinks of the one span source, the table's bound, `prof report`'s
compile section, and that the pvars this replaced are gone."""

import glob
import json
import os
import subprocess
import sys
import textwrap
import time
import types

import pytest

from ompi_tpu import prof
from ompi_tpu.core import pvar
from ompi_tpu.prof import __main__ as prof_cli
from ompi_tpu.prof import compile as cl
from ompi_tpu.trace import export, recorder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
HIT = "/jax/compilation_cache/cache_hits"
RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
COUNTERS = ("compile_trace_ns", "compile_lower_ns", "compile_backend_ns",
            "compile_cache_load_ns", "compile_programs",
            "compile_cache_requests", "compile_cache_hits",
            "compile_foreign_ns", "compile_foreign_programs")


@pytest.fixture
def no_recorder():
    recorder.disable()
    yield
    recorder.disable()


def _compiles(s):
    return {k: s.read(k) for k in COUNTERS if s.read(k)}


def _backend(led, name, t0, t1, cache=None, load_s=0.0):
    """One backend event as jax fires it: the cache's events inside."""
    if cache:
        led.on_event(REQUEST)
    if cache == "hit":
        led.on_event(HIT)
        led.on_duration(RETRIEVAL, load_s)
    led.on_span(BACKEND, t0, t1, fun_name=name)


# -- the rules of the account, on synthetic events --------------------------

def test_nested_trace_events_count_the_outermost_only():
    led, s = cl.Ledger(), pvar.session()
    led.on_scalar(TRACE, 10.0, fun_name="ompi_outer")
    led.on_scalar(TRACE, 10.1, fun_name="sin")
    led.on_span(TRACE, 10.1, 10.2, fun_name="sin")
    led.on_scalar(TRACE, 10.3, fun_name="inner")
    led.on_scalar(TRACE, 10.4, fun_name="matmul")
    led.on_span(TRACE, 10.4, 10.5, fun_name="matmul")
    led.on_span(TRACE, 10.3, 10.6, fun_name="inner")
    led.on_span(TRACE, 10.0, 11.0, fun_name="ompi_outer")
    # and the next one alone counts again
    led.on_scalar(TRACE, 12.0, fun_name="ompi_next")
    led.on_span(TRACE, 12.0, 12.5, fun_name="ompi_next")
    assert [(r["program"], r["runs"]["trace"]) for r in led.table()] == [
        ("ompi_outer", 1), ("ompi_next", 1)]
    assert _compiles(s) == {"compile_trace_ns": 1_500_000_000}


def test_nested_traces_of_a_real_jit_are_counted_once():
    import jax
    import jax.numpy as jnp

    prof.wire_compile_cache()

    @jax.jit
    def inner_fn(x):
        return jnp.sin(x) @ x

    def ompi_nested_probe(x):
        return inner_fn(x) + jnp.cos(x)

    x = jnp.ones((8, 8))  # jax's eager helpers compile here: foreign
    s = pvar.session()
    jax.jit(ompi_nested_probe).lower(x).compile()
    rows = {r["program"]: r for r in prof.compile_table()}
    row = rows["ompi_nested_probe"]
    assert row["own"] and row["runs"]["trace"] == 1
    assert row["runs"]["lower"] == row["runs"]["backend"] == 1
    assert "inner_fn" not in rows
    assert s.read("compile_trace_ns") == row["ns"]["trace"] > 0
    assert s.read("compile_programs") == 1
    assert s.read("compile_foreign_programs") == 0


@pytest.mark.parametrize("cache, want", [
    # a hit: the retrieval's time is the load, the rest the backend's
    ("hit", {"compile_backend_ns": 250_000_000,
             "compile_cache_load_ns": 750_000_000,
             "compile_cache_requests": 1, "compile_cache_hits": 1,
             "compile_programs": 1}),
    # a miss: XLA's compile and the write of the entry
    ("miss", {"compile_backend_ns": 1_000_000_000,
              "compile_cache_requests": 1, "compile_programs": 1}),
    # the persistent cache off: no request made
    (None, {"compile_backend_ns": 1_000_000_000, "compile_programs": 1}),
])
def test_backend_event_splits_by_what_the_cache_answered(cache, want):
    led, s = cl.Ledger(), pvar.session()
    _backend(led, "jit(ompi_train_step)", 5.0, 6.0, cache, load_s=0.75)
    assert _compiles(s) == want
    (row,) = led.table()
    assert (row["hits"], row["misses"]) == (cache == "hit",
                                            cache == "miss")
    assert s.read("prof_compile_cache_hits") == (cache == "hit")
    assert s.read("prof_compile_cache_misses") == (cache == "miss")
    # the answer belonged to that event: the next one asks for itself
    _backend(led, "jit(ompi_train_step)", 7.0, 8.0)
    assert s.read("compile_cache_requests") == want.get(
        "compile_cache_requests", 0)


@pytest.mark.parametrize("fun_name, program, mine", [
    ("ompi_train_step", "ompi_train_step", True),
    ("jit(ompi_train_step)", "ompi_train_step", True),
    ("jit(ompi_allreduce_linear)", "ompi_allreduce_linear", True),
    ("jit(ompi_route_counts)", "ompi_route_counts", True),
    ("jit(convert_element_type)", "convert_element_type", False),
    ("device_init", "device_init", False),
    ("jit(sgd_step)", "sgd_step", False),
    ("jit(my_ompi_step)", "my_ompi_step", False),
])
def test_own_against_foreign_by_name(fun_name, program, mine):
    led, s = cl.Ledger(), pvar.session()
    led.on_scalar(TRACE, 1.0, fun_name=fun_name)
    led.on_span(TRACE, 1.0, 2.0, fun_name=fun_name)
    led.on_span(LOWER, 2.0, 2.5, fun_name=fun_name)
    _backend(led, fun_name, 2.5, 4.5, "miss")
    (row,) = led.table()
    assert (row["program"], row["own"]) == (program, mine)
    assert row["ns"] == {"trace": 10**9, "lower": 5 * 10**8,
                         "backend": 2 * 10**9, "cache_load": 0}
    if mine:
        assert _compiles(s) == {
            "compile_trace_ns": 10**9, "compile_lower_ns": 5 * 10**8,
            "compile_backend_ns": 2 * 10**9, "compile_programs": 1,
            "compile_cache_requests": 1}
    else:  # e.g. the benchmark's weights and its plain reference
        assert _compiles(s) == {"compile_foreign_ns": 35 * 10**8,
                                "compile_foreign_programs": 1}


@pytest.mark.parametrize("attr, name", [
    ("_route_probe", "ompi_route_counts"),
    ("_selection_probe", "ompi_dsa_selection"),
    ("_exit_probe", "ompi_exit_stats"),
])
def test_set_up_probes_are_the_jobs_own(attr, name):
    from ompi_tpu.models import transformer as tfm

    probe = getattr(tfm, attr)
    assert probe.__name__ == name and cl.own(name)
    assert hasattr(probe, "lower")  # still a jitted function


def test_table_is_bounded():
    led = cl.Ledger()
    for i in range(cl.MAX_PROGRAMS + 40):
        led.on_span(LOWER, 1.0, 2.0, fun_name=f"jit(ompi_p{i})")
    rows = led.table()
    assert len(rows) == cl.MAX_PROGRAMS + 1
    assert rows[cl.MAX_PROGRAMS - 1]["program"] == \
        f"ompi_p{cl.MAX_PROGRAMS - 1}"  # the 256th name has its row
    other = rows[-1]  # the 257th and the rest share one
    assert other["program"] == cl.OTHER
    assert other["runs"]["lower"] == 40
    led.on_span(LOWER, 1.0, 2.0, fun_name="jit(ompi_p3)")  # a kept name
    assert led.table()[3]["runs"]["lower"] == 2


# -- a cold run, then the same cache directory again -------------------------

_JOB = """
    import json, os, sys
    sys.path.insert(0, {repo!r})
    import jax, jax.numpy as jnp
    from ompi_tpu import prof
    from ompi_tpu.core import pvar
    prof.wire_compile_cache()

    def ompi_cache_probe(x):
        return jnp.tanh(x @ x).sum()

    def reference(x):  # as the benchmark's: compiled, not the job's
        return jnp.sin(x).sum()

    x = jnp.ones((32, 32))
    step = jax.jit(ompi_cache_probe).lower(x).compile()
    jax.jit(reference)(x)
    print("LEDGER", json.dumps([
        {{k: v for k, v in pvar.snapshot().items() if "compile" in k}},
        prof.compile_table()]))
"""


def _run_job(cache_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=cache_dir,
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    p = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_JOB.format(repo=REPO))],
        capture_output=True, text=True, timeout=180, env=env)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    (line,) = [ln for ln in p.stdout.splitlines()
               if ln.startswith("LEDGER ")]
    counters, table = json.loads(line[len("LEDGER "):])
    return counters, {r["program"]: r for r in table}


def test_cold_run_is_backend_and_the_same_cache_dir_again_is_load(
        tmp_path):
    cold, rows = _run_job(str(tmp_path))
    assert cold["compile_programs"] == cold["compile_cache_requests"] == 1
    assert cold.get("compile_cache_hits", 0) == 0
    assert cold.get("compile_cache_load_ns", 0) == 0
    assert cold["compile_backend_ns"] > 0
    assert (rows["ompi_cache_probe"]["hits"],
            rows["ompi_cache_probe"]["misses"]) == (0, 1)
    assert not rows["reference"]["own"]
    assert cold["compile_foreign_programs"] >= 1

    warm, rows = _run_job(str(tmp_path))
    assert warm["compile_cache_hits"] == warm["compile_cache_requests"] \
        == warm["compile_programs"] == 1  # hit share 1.0
    assert warm["compile_cache_load_ns"] > 0
    row = rows["ompi_cache_probe"]
    assert (row["hits"], row["misses"]) == (1, 0)
    assert row["runs"] == {"trace": 1, "lower": 1, "backend": 1,
                           "cache_load": 1}
    # what is left of the backend event (the cache key) is the rest
    assert warm["compile_backend_ns"] == row["ns"]["backend"]
    assert warm["compile_backend_ns"] < cold["compile_backend_ns"]
    # every program asked the cache, and every one was answered
    assert warm["prof_compile_cache_hits"] >= 2
    assert warm.get("prof_compile_cache_misses", 0) == 0


# -- a warm launch calls no listener ----------------------------------------

@pytest.fixture
def listener_calls():
    """Counts every call jax.monitoring makes to a listener of any of
    the four kinds while the test runs (jax calls all of a kind's
    listeners: ours were called as often as these)."""
    from jax import monitoring as jmon

    calls = []

    def on_event(event, **kw):
        calls.append(event)

    def on_value(event, value, **kw):
        calls.append(event)

    def on_span(event, start, end, **kw):
        calls.append(event)

    jmon.register_event_listener(on_event)
    jmon.register_event_duration_secs_listener(on_value)
    jmon.register_scalar_listener(on_value)
    jmon.register_event_time_span_listener(on_span)
    yield calls
    jmon.unregister_event_listener(on_event)
    jmon.unregister_event_duration_listener(on_value)
    jmon.unregister_scalar_listener(on_value)
    jmon.unregister_event_time_span_listener(on_span)


def _compiled_program():
    import jax
    import jax.numpy as jnp

    def ompi_warm_probe(x):
        return x * 2 + 1

    x = jnp.ones(64, jnp.float32)
    step = jax.jit(ompi_warm_probe).lower(x).compile()
    return lambda: step(x)


def _coll_xla_slot():
    import jax.numpy as jnp

    from ompi_tpu.coll import xla as cx

    comm = types.SimpleNamespace(_coll_xla_ctx=cx._Ctx.local())
    x = jnp.ones(48, jnp.float32)
    return lambda: cx._allreduce_prep(comm, x)()


@pytest.mark.parametrize("make", [_compiled_program, _coll_xla_slot])
def test_a_thousand_warm_launches_call_no_listener(make, listener_calls):
    prof.wire_compile_cache()
    launch = make()
    launch()  # cold: the listeners are called here
    assert listener_calls
    del listener_calls[:]
    s = pvar.session()
    for _ in range(1000):
        out = launch()
    out.block_until_ready()
    assert listener_calls == []
    assert not _compiles(s)


# -- spans: the ring ---------------------------------------------------------

def test_closed_span_lands_on_the_rings_clock(no_recorder):
    assert recorder.closed("backend", "compile", 1, 2, program="p") is None
    rec = recorder.enable(capacity=8)
    m0, w0 = time.monotonic_ns(), time.time_ns()
    recorder.closed("backend", "compile", w0, w0 + 5_000_000,
                    program="ompi_p", cache="hit")
    (sp,) = rec.spans()
    assert (sp.name, sp.subsys, sp.args) == (
        "backend", "compile", {"program": "ompi_p", "cache": "hit"})
    assert sp.t1 - sp.t0 == 5_000_000
    # wall -> monotonic by the offset sampled at enable
    assert abs(sp.t0 - m0) < 50_000_000
    with recorder.api_span("Allreduce"):
        recorder.closed("trace", "compile", w0, w0 + 1, program="ompi_p")
    inner, api = rec.spans()[1:]
    assert inner.args["call"] == api.args["call"]


_RING_JOB = """
import json
import jax.numpy as jnp
from ompi_tpu import mpi
from ompi_tpu.trace import recorder
comm = mpi.Init()
x = jnp.ones(256, jnp.float32)
for _ in range(3):
    comm.Allreduce(x).block_until_ready()
if comm.rank == 0:
    print("SPANS", json.dumps([
        [sp.name, sp.subsys, sp.t0, sp.t1, sp.args or {}]
        for sp in recorder.RECORDER.spans()]), flush=True)
mpi.Finalize()
"""


def test_ring_compile_spans_lie_between_init_and_the_first_warm_launch(
        tmp_path):
    job = tmp_path / "ring_job.py"
    job.write_text(_RING_JOB)
    p = subprocess.run(
        [sys.executable, "-m", "ompi_tpu.runtime.launcher", "-n", "2",
         "--timeout", "120", "--mca", "device_plane", "on",
         "--mca", "trace_enable", "1", str(job)],
        capture_output=True, text=True, cwd=REPO, timeout=240)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    (line,) = [ln for ln in p.stdout.splitlines()
               if ln.startswith("SPANS ")]
    spans = json.loads(line[len("SPANS "):])
    inits = [s for s in spans if s[1] == "init"]
    mine = [s for s in spans if s[1] == "compile"
            and s[4]["program"] == "ompi_allreduce"]
    assert len(inits) == 8
    assert [s[0] for s in mine] == ["trace", "lower", "backend"] or \
        [s[0] for s in mine] == ["trace", "lower", "backend",
                                 "cache_load"]
    assert all(s[4]["cache"] in ("hit", "miss") for s in mine[2:])
    (cold,) = [s for s in spans if (s[0], s[1]) == ("compile", "coll_xla")]
    warm = [s for s in spans if (s[0], s[1]) == ("launch", "coll_xla")
            and s[4]["cold"] == 0]
    slack = 2_000_000  # jax's wall clock against the ring's monotonic
    # one clock: Init's phases, then the program's phases in order,
    # inside the cold launch that caused them, then the warm launches
    assert max(s[3] for s in inits) <= mine[0][2] + slack
    for a, b in zip(mine, mine[1:]):
        assert a[3] <= b[2] + slack
    assert cold[2] - slack <= mine[0][2] and mine[-1][3] <= cold[3] + slack
    assert len(warm) == 2 and mine[-1][3] <= warm[0][2] + slack
    # the API call the compile happened in
    assert {s[4]["call"] for s in mine} == {cold[4]["call"]}


# -- spans: a live profiler session ------------------------------------------

_PROFILED_JOB = """
    import sys
    sys.path.insert(0, {repo!r})
    import jax, jax.numpy as jnp
    from ompi_tpu import prof
    prof.wire_compile_cache()

    def ompi_profiled_probe(x):
        return (x @ x).sum()

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace({out!r}, profiler_options=options)
    jax.jit(ompi_profiled_probe)(jnp.ones((16, 16))).block_until_ready()
    jax.profiler.stop_trace()
"""


def test_profiler_session_gets_the_closing_backend_annotation(tmp_path):
    from jax.profiler import ProfileData

    out = str(tmp_path / "trace")
    p = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(
            _PROFILED_JOB.format(repo=REPO, out=out))],
        capture_output=True, text=True, timeout=180,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache")))
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    (path,) = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("ompi:compile."):
                        stats = dict(e.stats)
                        if stats.get("program") == "ompi_profiled_probe":
                            found[e.name] = stats
    assert set(found) >= {"ompi:compile.trace", "ompi:compile.lower",
                          "ompi:compile.backend"}
    backend = found["ompi:compile.backend"]
    assert backend["cache"] in ("hit", "miss") and backend["dur_ns"] > 0


# -- prof report ---------------------------------------------------------------

def _rank_trace(path, rank, step_backend_s, cache):
    rec = recorder.Recorder(capacity=64, rank=rank)
    t = 1_000_000_000

    def add(phase, program, secs, **args):
        nonlocal t
        rec.record(phase, "compile", t, t + int(secs * 1e9),
                   dict(args, program=program))
        t += int(secs * 1e9)

    add("trace", "ompi_train_step", 1.5)
    add("lower", "ompi_train_step", 0.5)
    add("backend", "ompi_train_step", step_backend_s, cache=cache)
    if cache == "hit":
        add("cache_load", "ompi_train_step", 2.0, cache=cache)
    add("trace", "ompi_allreduce", 0.01)
    add("backend", "ompi_allreduce", 0.02, cache="hit")
    export.write(path, rec)


def test_prof_report_prints_the_compile_section_of_two_ranks(
        tmp_path, capsys):
    paths = [str(tmp_path / f"r{r}.json") for r in (0, 1)]
    _rank_trace(paths[0], 0, 0.25, "hit")
    _rank_trace(paths[1], 1, 30.0, "miss")  # this rank's cache refused
    out = str(tmp_path / "attribution.json")
    assert prof_cli.main(["report", "-o", out] + paths) == 0
    text = capsys.readouterr().out
    assert "compile ledger" in text
    (row,) = [ln for ln in text.splitlines()
              if ln.strip().startswith("ompi_train_step")]
    assert row.split()[1:] == ["1.500", "0.500", "30.000", "2.000",
                               "1", "hits,", "1", "misses"]
    rep = json.load(open(out))["compile"]
    assert [c["program"] for c in rep] == ["ompi_train_step",
                                           "ompi_allreduce"]
    assert rep[0]["phases_s"] == {"trace": 1.5, "lower": 0.5,
                                  "backend": 30.0, "cache_load": 2.0}
    assert (rep[1]["hits"], rep[1]["misses"]) == (2, 0)


# -- what went -------------------------------------------------------------------

@pytest.mark.parametrize("name", ["prof_compile_hits",
                                  "prof_compile_misses",
                                  "prof_compile_ns"])
def test_removed_pvar_is_in_no_registry(name):
    import jax.numpy as jnp

    from ompi_tpu.coll import xla as cx
    from ompi_tpu.prof import ledger

    assert name not in pvar.WELL_KNOWN
    ledger.enable()
    try:  # the sites that fed them, with the profiler they waited for
        comm = types.SimpleNamespace(_coll_xla_ctx=cx._Ctx.local())
        for _ in range(2):
            cx._allreduce_prep(comm, jnp.ones(40, jnp.float32))()
    finally:
        ledger.disable()
    assert name not in pvar.snapshot()
