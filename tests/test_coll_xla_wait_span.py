"""coll/xla's own waits and bucket flushes on the one span source
(trace/recorder.span): `coll_xla.wait` with `program` and the
`launch_call` of the launch it waits for, under the ring and under a
live jax.profiler session; `part_bucket_flush` / `zero_bucket_flush`
and the `pready` instants under a profiler session ALONE (the sink
that shares a clock with the chip); nothing constructed with both
sinks down; no hand-written ring record left in coll/xla.py."""

import glob
import os
import re
import types

import pytest

from ompi_tpu.core import events, pvar
from ompi_tpu.trace import recorder
from tests.harness import run_ranks

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MCA = {"device_plane": "on"}


@pytest.fixture
def no_recorder():
    recorder.disable()
    yield
    recorder.disable()


def _ompi_events(trace_dir):
    """[(name, start_ns, end_ns, stats)] of the `ompi:` events of the
    one thread that made them, in start order."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats)) for e in line.events
                   if e.name.startswith("ompi:")]
            if evs:
                lines.append(sorted(evs, key=lambda e: (e[1], -e[2])))
    (evs,) = lines
    return evs


def _inside(outer, inner):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


class _Session:
    """A jax.profiler session without the Python tracer (as the
    benchmark's) into `dir`, and its `ompi:` events afterwards."""

    def __init__(self, dir) -> None:
        self.dir = str(dir)

    def __enter__(self):
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        return self

    def __exit__(self, *exc):
        import jax

        jax.profiler.stop_trace()
        return False

    def events(self):
        return _ompi_events(self.dir)


# -- the waits, two ranks, both sinks -------------------------------------

def test_waits_name_program_and_launch_call_in_ring_and_session():
    """`Iallreduce` + `wait()`, a wait made inside an API call, a
    persistent `Start` + `wait()` and a partitioned cycle: each leaves
    ONE `coll_xla.wait`, in the ring and in the profiler session, with
    the program it waited for and the `call` of the API call that
    launched it (none where `start()` / `Pready()` launched it outside
    any API call — the launch span has none either)."""
    run_ranks("""
    import glob, os, shutil, tempfile
    import jax, jax.numpy as jnp
    from jax.profiler import ProfileData
    from ompi_tpu.trace import recorder

    x = jnp.full((64,), float(rank + 1), jnp.float32)
    bufs = [jnp.ones(64, jnp.float32) * (rank + 1),
            jnp.ones(32, jnp.float32), jnp.ones(16, jnp.int32)]
    # warm: nothing compiles under the sinks
    comm.Iallreduce(x).wait()
    preq = comm.Allreduce_init(x)
    preq.start(); preq.wait()
    part = comm.Pallreduce_init(bufs)
    part.start()
    for i in range(3):
        part.Pready(i)
    part.wait()

    tmp = tempfile.mkdtemp(prefix="ompi_wait_span_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=options)
    rec = recorder.enable(capacity=4096)
    try:
        r = comm.Iallreduce(x)
        r.wait()
        r.wait()                      # done: no second span
        r2 = comm.Iallreduce(x)
        with recorder.api_span("Waitall"):
            r2.wait()
        preq.start()
        preq.wait()
        part.start()
        for i in (2, 0, 1):
            part.Pready(i)
        part.wait()
    finally:
        recorder.disable()
        jax.profiler.stop_trace()
    np.testing.assert_allclose(np.asarray(r.array), 3.0)
    np.testing.assert_allclose(np.asarray(part.array[0]), 3.0)

    # -- the ring
    spans = rec.spans()
    arg = lambda s, k: (s.args or {}).get(k)
    api = {arg(s, "call"): s.name for s in spans if s.subsys == "api"}
    waits = [s for s in spans
             if (s.name, s.subsys) == ("wait", "coll_xla")]
    assert len(waits) == 4, spans
    w_i, w_in, w_p, w_part = waits
    icalls = sorted(c for c, n in api.items() if n == "Iallreduce")
    assert len(icalls) == 2
    assert arg(w_i, "program") == "ompi_allreduce"
    assert arg(w_i, "launch_call") == icalls[0]
    assert arg(w_i, "call") is None           # a top-level span
    assert arg(w_in, "launch_call") == icalls[1]
    assert api[arg(w_in, "call")] == "Waitall"
    assert arg(w_p, "program") == "ompi_allreduce"
    assert arg(w_p, "launch_call") is None and arg(w_p, "call") is None
    assert arg(w_part, "program") == "ompi_fused_allreduce"
    # launch_call IS the launch span's call, wait by wait
    launches = [s for s in spans
                if (s.name, s.subsys) == ("launch", "coll_xla")]
    assert [arg(s, "call") for s in launches[:3]] == icalls + [None]
    flushes = [s for s in spans if s.name == "part_bucket_flush"]
    assert len(flushes) == 2 and all(s.subsys == "part" for s in flushes)
    assert [arg(s, "partition") for s in spans
            if s.name == "pready"] == [2, 0, 1]

    # -- the profiler session: the same, as ompi:<subsys>.<name>
    (path,) = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    evs = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                evs += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)) for e in line.events
                        if e.name.startswith("ompi:")]
    shutil.rmtree(tmp)
    evs.sort(key=lambda e: (e[1], -e[2]))
    pw = [e for e in evs if e[0] == "ompi:coll_xla.wait"]
    assert [e[3].get("program") for e in pw] == [
        "ompi_allreduce", "ompi_allreduce", "ompi_allreduce",
        "ompi_fused_allreduce"]
    assert [e[3].get("launch_call") for e in pw] == icalls + [None, None]
    (outer,) = [e for e in evs if e[0] == "ompi:api.Waitall"]
    assert outer[1] <= pw[1][1] and pw[1][2] <= outer[2]
    assert pw[1][3]["call"] == outer[3]["call"]
    assert sum(e[0] == "ompi:part.part_bucket_flush" for e in evs) == 2
    assert sum(e[0] == "ompi:part.pready" for e in evs) == 3
    """, 2, mca=MCA, timeout=240)


# -- a profiler session alone: flushes and instants ---------------------------

def _cycle(kind):
    """A two-bucket partitioned request of `kind` ('part': fused
    allreduce, 'zero': fused reduce_scatter) over a one-rank context."""
    import jax
    import jax.numpy as jnp

    from ompi_tpu import op as op_mod
    from ompi_tpu.coll import xla as cx

    ctx = cx._Ctx.local()
    bufs = [jnp.ones(64, jnp.float32), jnp.ones(64, jnp.float32),
            jnp.ones(64, jnp.int32), jnp.ones(64, jnp.int32)]
    leaves, treedef = jax.tree.flatten(bufs)
    if kind == "part":
        req = cx.PartitionedAllreduceRequest(ctx, leaves, treedef,
                                             op_mod.SUM, None)
    else:
        comm = types.SimpleNamespace(_coll_xla_ctx=ctx, rank=0, size=1)
        req = cx.PartitionedReduceScatterRequest(
            ctx, comm, leaves, treedef, op_mod.SUM, None)
    return req


def _run_cycle(req):
    req.start()
    for i in (1, 0, 2, 3):
        req.Pready(i)
    req.wait()


@pytest.mark.parametrize("kind, flush, program", [
    ("part", "part_bucket_flush", "ompi_fused_allreduce"),
    ("zero", "zero_bucket_flush", "ompi_zero_rs"),
])
def test_session_alone_shows_flushes_around_launches(no_recorder, tmp_path,
                                                     kind, flush, program):
    """No ring: the profiler session still gets `pready` ...
    `ompi:<part|zero>.<flush>` ⊃ `ompi:coll_xla.launch` ...
    `ompi:coll_xla.wait`, with the arguments the ring's spans have."""
    req = _cycle(kind)
    _run_cycle(req)  # warm
    assert recorder.RECORDER is None
    with _Session(tmp_path) as session:
        _run_cycle(req)
    evs = session.events()
    pre = [e for e in evs if e[0] == f"ompi:{kind}.pready"]
    assert [e[3]["partition"] for e in pre] == [1, 0, 2, 3]
    fl = [e for e in evs if e[0] == f"ompi:{kind}.{flush}"]
    assert len(fl) == 2
    # the f32 bucket went when leaf 0 came, with the i32 leaves still
    # pending (overlap); the i32 bucket with the last Pready
    assert [(e[3]["bucket"], e[3]["trigger_partition"], e[3]["overlap"],
             e[3]["nbytes"]) for e in fl] == [
        (0, 0, 1, 2 * 64 * 4), (1, 3, 0, 2 * 64 * 4)]
    launches = [e for e in evs if e[0] == "ompi:coll_xla.launch"]
    assert len(launches) == 2
    for f, launch in zip(fl, launches):
        assert _inside(f, launch)
        assert launch[3]["program"] == program and launch[3]["cold"] == 0
    (wait,) = [e for e in evs if e[0] == "ompi:coll_xla.wait"]
    assert wait[3]["program"] == program
    assert wait[1] >= fl[-1][2] and pre[-1][2] <= fl[-1][1]


def test_ring_alone_still_holds_what_it_held(no_recorder):
    """Name, subsystem and arguments of the ring's flush spans and
    instants are what the hand-written records gave, the instants
    still have no duration, and the flush feeds the histogram."""
    from ompi_tpu.trace import export

    req = _cycle("zero")
    _run_cycle(req)  # warm
    s = pvar.session()
    rec = recorder.enable(capacity=256)
    try:
        _run_cycle(req)
    finally:
        recorder.disable()
    spans = rec.spans()
    fl = [sp for sp in spans if sp.name == "zero_bucket_flush"]
    assert [(sp.subsys, sp.args) for sp in fl] == [
        ("zero", {"bucket": 0, "trigger_partition": 0, "overlap": True,
                  "nbytes": 512}),
        ("zero", {"bucket": 1, "trigger_partition": 3, "overlap": False,
                  "nbytes": 512})]
    pre = [sp for sp in spans if sp.name == "pready"]
    assert [(sp.subsys, sp.args, sp.t1 - sp.t0) for sp in pre] == [
        ("zero", {"partition": i}, 0) for i in (1, 0, 2, 3)]
    hist = export.histograms(s.snapshot())
    assert sum(hist.get("zero_bucket_flush", {}).values()) == 2
    (wait,) = [sp for sp in spans if sp.name == "wait"]
    assert wait.args == {"program": "ompi_zero_rs"}


# -- both sinks down ----------------------------------------------------------

def test_sinks_down_waits_and_flushes_construct_nothing(monkeypatch,
                                                        no_recorder):
    """The hot-path contract at the new sites: 1,000 waits and 1,000
    bucket flushes with no sink up build no span, no ring record, no
    TraceAnnotation — and remember no launch."""
    import jax.numpy as jnp

    from ompi_tpu.coll import xla as cx

    assert not recorder.active()

    def boom(*a, **k):
        raise AssertionError("span constructed while no sink is up")

    req = _cycle("part")
    _run_cycle(req)  # warm, before the traps
    monkeypatch.setattr(recorder, "Span", boom)
    monkeypatch.setattr(recorder, "_Span", boom)
    monkeypatch.setattr(recorder, "_annotation", boom)
    monkeypatch.setattr(recorder, "instant", boom)
    s = pvar.session()
    comm = types.SimpleNamespace(_coll_xla_ctx=cx._Ctx.local())
    launcher = cx._allreduce_prep(comm, jnp.ones(16, jnp.float32))
    persistent = cx.PersistentDeviceRequest(launcher)
    for _ in range(500):
        persistent.start()
        persistent.wait()
        assert persistent._inner._launched is None
        r = cx.DeviceRequest(*cx._launched(launcher))
        r.wait()
    for _ in range(500):
        _run_cycle(req)  # two flushes and one wait a cycle
    assert req._launched is None
    assert s.read("part_bucket_flushes") == 1000
    assert s.read("coll_xla_launches") == 2000


# -- the source ---------------------------------------------------------------

def test_no_hand_written_ring_record_left_in_coll_xla():
    """coll/xla.py reads `_trace.RECORDER` only to guard `hist`, and
    never blocks on a device array but in the one wait helper."""
    with open(os.path.join(REPO, "ompi_tpu", "coll", "xla.py")) as f:
        src = f.read()
    reads = [m.start() for m in re.finditer(r"_trace\.RECORDER", src)]
    assert len(reads) == 1
    after = src[reads[0]:reads[0] + 120]
    assert re.match(r"_trace\.RECORDER is not None:\s+_trace\.hist\(",
                    after), after
    assert "rec.record(" not in src and "rec.instant(" not in src
    blocks = re.findall(r"jax\.block_until_ready\(", src)
    assert len(blocks) == 2  # `_wait`: sink down, sink up


def test_trace_span_event_type_is_gone():
    """The MPI-4 event `trace_span` had no subscriber (no tool, report
    or test): the ring's `record` emits nothing."""
    from ompi_tpu import mpit

    names = [mpit.event_get_info(i)["name"]
             for i in range(mpit.event_get_num())]
    assert "trace_span" not in names and "pml_message_matched" in names
    assert not hasattr(recorder, "TRACE_SPAN")
    with pytest.raises(Exception):
        events.handle_alloc("trace_span", buffer_size=4)


# -- instant ------------------------------------------------------------------

def test_instant_feeds_the_ring_a_span_without_duration(no_recorder):
    assert recorder.instant("pready", "part", partition=1) is None
    rec = recorder.enable(capacity=16)
    try:
        recorder.instant("pready", "part", partition=3)
        with recorder.api_span("Pready"):
            recorder.instant("pready", "zero", partition=4)
    finally:
        recorder.disable()
    a, b, api = rec.spans()
    assert (a.name, a.subsys, a.args, a.t1 - a.t0) == \
        ("pready", "part", {"partition": 3}, 0)
    assert b.args == {"partition": 4, "call": api.args["call"]}
    assert b.t0 == b.t1 and api.t0 <= b.t0 <= api.t1


def test_launched_remembers_the_last_launch_only_while_a_sink_is_up(
        no_recorder):
    import jax.numpy as jnp

    from ompi_tpu.coll import xla as cx

    comm = types.SimpleNamespace(_coll_xla_ctx=cx._Ctx.local())
    launcher = cx._allreduce_prep(comm, jnp.ones(8, jnp.float32))
    launcher()  # cold
    out, note = cx._launched(launcher)
    assert note is None and out.shape == (8,)
    recorder.enable(capacity=16)
    try:
        with recorder.api_span("Iallreduce"):
            _, note = cx._launched(launcher)
        # a launcher that launches no device program (a staged
        # fallback) leaves nothing of an earlier launch behind
        _, stale = cx._launched(lambda: None)
    finally:
        recorder.disable()
    assert note["program"] == "ompi_allreduce" and note["call"] > 0
    assert stale is None
