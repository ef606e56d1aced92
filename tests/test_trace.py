"""trace/ subsystem tests: ring bounding + drop accounting, log2
histogram binning, Pready -> flush span attribution, Chrome export
shape, cross-rank merge, the events-plane concurrent drop accounting
the recorder builds on — and the one span source under its three
states: no sink (constructs nothing), the ring up, a jax.profiler
session live (a real 4-rank CPU .xplane.pb); a program's cold launch;
the phases of mpi.Init()."""

import glob
import json
import os
import subprocess
import sys
import tempfile
import threading
import types

import pytest

from ompi_tpu.core import events, pvar
from ompi_tpu.trace import export, merge, recorder
from ompi_tpu.trace import __main__ as trace_cli
from tests.harness import run_ranks

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def no_recorder():
    """Guarantee the global recorder is off before and after."""
    recorder.disable()
    yield
    recorder.disable()


# -- ring buffer + drop accounting ---------------------------------------

def test_ring_buffer_bounds_and_trace_dropped(no_recorder):
    rec = recorder.Recorder(capacity=8, rank=0)
    s = pvar.session()
    for i in range(20):
        t = recorder.now()
        rec.record(f"s{i}", "test", t, t + 10)
    spans = rec.spans()
    assert len(spans) == 8
    # oldest overwritten: only the last capacity spans survive
    assert [sp.name for sp in spans] == [f"s{i}" for i in range(12, 20)]
    assert s.read("trace_dropped") == 12


def test_ring_thread_safety_exact_accounting(no_recorder):
    rec = recorder.Recorder(capacity=16, rank=0)
    s = pvar.session()
    n_threads, per = 4, 100
    start = threading.Barrier(n_threads)

    def emitter(k):
        start.wait()
        for i in range(per):
            t = recorder.now()
            rec.record(f"t{k}_{i}", "test", t, t)

    ts = [threading.Thread(target=emitter, args=(k,))
          for k in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert len(rec.spans()) == 16
    assert s.read("trace_dropped") == n_threads * per - 16


def _local_comm():
    from ompi_tpu.coll import xla as cx

    return types.SimpleNamespace(_coll_xla_ctx=cx._Ctx.local())


def test_disabled_guard_constructs_nothing(monkeypatch, no_recorder):
    """No sink up (the default): the one span source hands back ONE
    shared no-op and builds no span object, ring record or
    TraceAnnotation anywhere on the coll/xla hot path — the guard
    contract the fused pvar regression tests depend on."""
    import jax.numpy as jnp

    from ompi_tpu.coll import xla as cx

    assert recorder.RECORDER is None and not recorder.active()

    def boom(*a, **k):
        raise AssertionError("span constructed while no sink is up")

    monkeypatch.setattr(recorder, "Span", boom)
    monkeypatch.setattr(recorder, "_Span", boom)
    monkeypatch.setattr(recorder, "_annotation", boom)
    assert recorder.span("launch", "coll_xla", cold=0) is recorder.OFF
    assert recorder.api_span("Allreduce") is recorder.OFF
    with recorder.span("x", "y") as sp:
        sp.set(k=1)  # arguments learned inside cost nothing either
    comm = _local_comm()
    s = pvar.session()
    launcher = cx._allreduce_prep(comm, jnp.ones(16, jnp.float32))
    launcher()  # cold: timed into the pvars, still no span object
    launcher()
    assert s.read("coll_xla_launches") >= 2  # the path really ran
    assert s.read("coll_xla_cold_launches") == 1


# -- the one span source: ring up ----------------------------------------

def test_ring_records_the_device_path_names_once_cold(no_recorder):
    """Ring up: to_global, launch and my_shard under one slot's
    prep, with the program's stable name; `cold=1` exactly once per
    key, inside a `compile` span."""
    import jax.numpy as jnp

    from ompi_tpu.coll import xla as cx

    comm = _local_comm()
    s = pvar.session()
    rec = recorder.enable(capacity=256)
    try:
        x = jnp.ones(32, jnp.float32)
        for _ in range(3):
            cx._allreduce_prep(comm, x)()
        cx._allreduce_prep(comm, jnp.ones(8, jnp.float32))()  # new key
    finally:
        recorder.disable()
    # the span stays, it just gets short: no device program behind it
    assert s.read("coll_xla_global_view_copies") == 0
    spans = [sp for sp in rec.spans() if sp.subsys == "coll_xla"]
    names = [sp.name for sp in spans]
    assert names.count("to_global") == 4
    assert names.count("my_shard") == 4
    assert names.count("launch") == 4
    assert names.count("compile") == 2  # one per key
    launches = [sp for sp in spans if sp.name == "launch"]
    assert [sp.args["cold"] for sp in launches] == [1, 0, 0, 1]
    assert {sp.args["program"] for sp in launches} == {"ompi_allreduce"}
    assert launches[0].args["nbytes"] == 32 * 4
    assert all(sp.args["resident"] == 1 for sp in spans
               if sp.name == "to_global")
    # the removed instants stay removed: nothing per warm call but
    # the spans of the work
    assert "cache_hit" not in names and "plan_cache_hit" not in names


def test_api_span_gives_every_span_of_the_call_one_id(no_recorder):
    """`api_span` opens a call; spans inside carry its number, the
    next call gets another, and a nested API call its own."""
    rec = recorder.enable(capacity=64)
    try:
        with recorder.api_span("Allreduce"):
            with recorder.span("allreduce", "coll_xla", nbytes=4):
                with recorder.span("launch", "coll_xla"):
                    pass
            with recorder.api_span("bcast"):  # an API call inside one
                with recorder.span("launch", "coll_xla"):
                    pass
            with recorder.span("my_shard", "coll_xla"):
                pass
        with recorder.api_span("Allreduce"):
            pass
        with recorder.span("orphan", "coll_xla"):  # outside any call
            pass
    finally:
        recorder.disable()
    by = {}
    for sp in rec.spans():
        by.setdefault(sp.name, []).append((sp.args or {}).get("call"))
    outer, second = by["Allreduce"]
    assert outer is not None and second not in (None, outer)
    assert by["allreduce"] == [outer] and by["my_shard"] == [outer]
    inner = by["bcast"][0]
    assert inner not in (outer, second)
    assert sorted(by["launch"]) == sorted([outer, inner])
    assert by["orphan"] == [None]


def test_span_records_the_error_that_escaped(no_recorder):
    rec = recorder.enable(capacity=8)
    try:
        with pytest.raises(KeyError):
            with recorder.api_span("Recv"):
                raise KeyError("x")
    finally:
        recorder.disable()
    (sp,) = rec.spans()
    assert (sp.name, sp.subsys, sp.args["error"]) == \
        ("Recv", "api", "KeyError")


def test_cold_launch_not_build_is_the_compile(no_recorder):
    """`_Ctx.compiled` only wraps (`build()` returns a lazy jax.jit):
    the compile ledger's counters and the cold-launch pvars grow on
    the key's FIRST launch, not on `build()`, and never again; the
    ledger's four phases lie inside the cold launch's wall time."""
    import jax.numpy as jnp

    from ompi_tpu import prof
    from ompi_tpu.coll import xla as cx

    def ledger_ns():
        return sum(s.read("compile_%s_ns" % ph) for ph in
                   ("trace", "lower", "backend", "cache_load"))

    prof.wire_compile_cache()
    ctx = cx._Ctx.local()
    x = jnp.ones(24, jnp.float32)
    key = cx._key(x, "allreduce", "MPI_SUM", None)
    s = pvar.session()
    fn = ctx.compiled(key, lambda: ctx.smap(
        lambda a: a[0] * 2, out_varying=True))
    assert ctx.programs[fn] == "ompi_allreduce"
    assert s.read("coll_xla_cache_misses") == 1
    assert s.read("compile_programs") == 0  # nothing compiled yet
    assert ledger_ns() == 0
    assert s.read("coll_xla_cold_launches") == 0
    g = ctx.to_global(x)
    # the view is the operand under the global shape (n = 1
    # here), and the body above still saw its (1, 24) block
    assert g.shape == (24,)
    assert (g.addressable_data(0).unsafe_buffer_pointer()
            == x.unsafe_buffer_pointer())
    assert ctx.launch(fn, g).shape == (24,)
    cold_ns = s.read("coll_xla_cold_launch_ns")
    assert s.read("coll_xla_cold_launches") == 1 and cold_ns > 0
    assert s.read("compile_programs") == 1
    compiled_ns = ledger_ns()
    assert 0 < compiled_ns <= cold_ns
    assert ctx.compiled(key, None) is fn  # warm: build not called
    ctx.launch(fn, g)
    assert s.read("coll_xla_cold_launches") == 1
    assert s.read("coll_xla_cold_launch_ns") == cold_ns
    assert s.read("compile_programs") == 1
    assert ledger_ns() == compiled_ns


@pytest.mark.parametrize("key, name", [
    (((16,), "float32", "allreduce", "MPI_SUM", None), "ompi_allreduce"),
    (((16,), "float32", "allreduce", "MPI_SUM", "linear"),
     "ompi_allreduce_linear"),
    (((4, 4), "int32", "bcast", 2), "ompi_bcast"),
    (("fused_allreduce", ((8,),), "MPI_SUM", "ring", False),
     "ompi_fused_allreduce_ring"),
    (("barrier",), "ompi_barrier"),
])
def test_program_name_is_the_keys_kind(key, name):
    from ompi_tpu.coll import xla as cx

    assert cx.program_name(key) == name


def test_evicted_program_is_forgotten(no_recorder):
    """LRU eviction drops the name and the cold mark with the cache
    entry; a rebuilt key is cold again."""
    import jax.numpy as jnp

    from ompi_tpu.coll import xla as cx
    from ompi_tpu.core import cvar

    comm = _local_comm()
    ctx = comm._coll_xla_ctx
    cvar.set("coll_xla_cache_max", 1)
    s = pvar.session()
    try:
        cx._allreduce_prep(comm, jnp.ones(4, jnp.float32))()
        cx._allreduce_prep(comm, jnp.ones(5, jnp.float32))()
        assert len(ctx.fns) == 1 and len(ctx.programs) == 1
        assert not ctx._cold
        cx._allreduce_prep(comm, jnp.ones(4, jnp.float32))()
        assert s.read("coll_xla_cold_launches") == 3
    finally:
        cvar.set("coll_xla_cache_max", 0)


# -- the one span source: a jax.profiler session live --------------------

def _host_events(path):
    """[(name, start_ns, end_ns, stats)] of the ompi:/bench: events in
    a profiler trace, by thread line."""
    from jax.profiler import ProfileData

    lines = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats)) for e in line.events
                   if e.name.startswith(("ompi:", "bench:"))]
            if evs:
                lines.append(evs)
    return lines


def test_profiler_session_carries_the_nested_program_spans():
    """`--trace 1` switches nothing on in the program: any live
    jax.profiler session gets `ompi:api.Allreduce` > `ompi:coll_xla.
    allreduce` > {to_global, launch, my_shard}, one `call` id per API
    call, inside the benchmark's own `bench:collective call`, in a
    real 4-rank CPU .xplane.pb."""
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", "osu-allreduce-4rank", "--seed", str(2**31 + 5),
         "--seconds", "1", "--trace", "1", "--rehearsal", "1"],
        capture_output=True, text=True, timeout=600, cwd=REPO)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    found = sorted(glob.glob(os.path.join(
        REPO, "chiprun_out", "benchmark",
        "rehearsal-osu-allreduce-4rank", "trace", "plugins", "profile",
        "*", "*.xplane.pb")))
    assert found
    (events,) = _host_events(found[-1])  # one thread made them all

    def inside(outer, inner):
        return outer[1] <= inner[1] and inner[2] <= outer[2]

    calls = [e for e in events if e[0] == "bench:collective call"]
    apis = [e for e in events if e[0] == "ompi:api.Allreduce"]
    assert len(calls) > 10 and len(apis) == len(calls)
    seen = set()
    for call, api in zip(calls, apis):
        assert inside(call, api)
        cid = api[3]["call"]
        assert cid not in seen
        seen.add(cid)
        mine = [e for e in events if e[0].startswith("ompi:coll_xla.")
                and e[3].get("call") == cid]
        assert [e[0] for e in mine] == [
            "ompi:coll_xla.allreduce", "ompi:coll_xla.to_global",
            "ompi:coll_xla.launch", "ompi:coll_xla.my_shard"]
        slot, to_global, launch, my_shard = mine
        assert inside(api, slot)
        assert all(inside(slot, c) for c in (to_global, launch,
                                              my_shard))
        assert to_global[2] <= launch[1] <= launch[2] <= my_shard[1]
        assert launch[3]["program"] == "ompi_allreduce"
        assert launch[3]["cold"] == 0 and to_global[3]["resident"] == 1
    # the host-plane API calls between the passes are there too
    assert any(e[0] == "ompi:api.Barrier" for e in events)


# -- mpi.Init(): phases that tile it --------------------------------------

PHASES = ("import", "rte", "accelerator", "distributed", "client",
          "fence", "pml", "world")


def test_init_phase_pvars_tile_mpi_init():
    """Every `init_<phase>_ns` is non-zero after `mpi.Init()` of a
    device-plane job, and together they come to within 10% of the
    wall from the package's import to Init's return."""
    with tempfile.NamedTemporaryFile("w", suffix=".py",
                                     delete=False) as fh:
        fh.write(
            "import json, time\n"
            "t0 = time.monotonic_ns()\n"
            "from ompi_tpu import mpi\n"
            "comm = mpi.Init()\n"
            "wall = time.monotonic_ns() - t0\n"
            "from ompi_tpu.core import pvar\n"
            f"ph = {{p: pvar.read('init_%s_ns' % p) for p in {PHASES!r}}}\n"
            "print('PHASES', comm.rank, json.dumps([wall, ph]),"
            " flush=True)\n"
            "mpi.Finalize()\n")
    try:
        r = subprocess.run(
            [sys.executable, "-m", "ompi_tpu.runtime.launcher", "-n",
             "2", "--timeout", "120", "--mca", "device_plane", "on",
             fh.name],
            capture_output=True, text=True, cwd=REPO, timeout=180)
    finally:
        os.unlink(fh.name)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    lines = [ln for ln in r.stdout.splitlines()
             if ln.startswith("PHASES")]
    assert len(lines) == 2, r.stdout[-2000:]
    for ln in lines:
        wall, ph = json.loads(ln.split(" ", 2)[2])
        assert all(v > 0 for v in ph.values()), ph
        assert abs(sum(ph.values()) - wall) <= 0.10 * wall, (wall, ph)


# -- log2 histogram ------------------------------------------------------

def test_histogram_binning(no_recorder):
    s = pvar.session()
    recorder.hist("t_binop", 1000, 5000)
    # bit_length bins: 1000 -> 10, 5000 -> 13
    assert s.read("trace_hist_t_binop_sz10_lat13") == 1
    recorder.hist("t_binop", 0, 0)
    assert s.read("trace_hist_t_binop_sz0_lat0") == 1
    h = export.histograms(s.snapshot())["t_binop"]
    assert h[(10, 13)] == 1 and h[(0, 0)] == 1


def test_histogram_percentiles(no_recorder):
    for _ in range(10):
        recorder.hist("t_pctop", 64, 100)     # lat bin 7
    recorder.hist("t_pctop", 64, 100000)      # lat bin 17
    pc = export.percentiles("t_pctop", (0.5, 0.99))
    assert pc is not None
    assert pc[0] == 3.0 * 2 ** 5     # midpoint of bin 7 = 96 ns
    assert pc[1] == 3.0 * 2 ** 15    # midpoint of bin 17
    assert export.percentiles("t_no_such_op") is None


# -- Pready -> flush attribution ----------------------------------------

def test_pready_flush_span_attribution(no_recorder):
    """Flush spans carry the Pready that released the bucket and
    whether the dispatch overlapped pending partitions; the flush
    latency lands in the part_bucket_flush histogram."""
    import jax
    import jax.numpy as jnp

    from ompi_tpu import op as op_mod
    from ompi_tpu.coll import xla as cx

    ctx = cx._Ctx.local()
    # two dtype-segregated buckets: f32 leaves {0,1}, i32 leaves {2,3}
    bufs = [jnp.ones(64, jnp.float32), jnp.ones(64, jnp.float32),
            jnp.ones(64, jnp.int32), jnp.ones(64, jnp.int32)]
    leaves, treedef = jax.tree.flatten(bufs)
    preq = cx.PartitionedAllreduceRequest(ctx, leaves, treedef,
                                          op_mod.SUM, None)
    rec = recorder.enable(capacity=1024)
    s = pvar.session()
    try:
        preq.start()
        # f32 bucket completes FIRST (out of order: 1 then 0), while
        # the i32 leaves are still pending -> overlap flush
        preq.Pready(1)
        preq.Pready(0)
        preq.Pready(2)
        preq.Pready(3)
        preq.wait()
    finally:
        recorder.disable()
    flushes = [sp for sp in rec.spans()
               if sp.name == "part_bucket_flush"]
    assert len(flushes) == 2, rec.spans()
    by_trigger = {sp.args["trigger_partition"]: sp for sp in flushes}
    assert set(by_trigger) == {0, 3}, by_trigger
    assert by_trigger[0].args["overlap"] is True
    assert by_trigger[3].args["overlap"] is False
    assert all(sp.args["nbytes"] == 2 * 64 * 4 for sp in flushes)
    assert all(sp.subsys == "part" for sp in flushes)
    # the Pready markers are on the timeline too
    preadys = [sp.args["partition"] for sp in rec.spans()
               if sp.name == "pready"]
    assert preadys == [1, 0, 2, 3]
    # and each flush fed the latency histogram
    hist = export.histograms(s.snapshot())
    assert sum(hist.get("part_bucket_flush", {}).values()) == 2
    # launch spans from the coll_xla layer under the flushes
    assert sum(1 for sp in rec.spans()
               if sp.name == "launch" and sp.subsys == "coll_xla") == 2


# -- Chrome export + merge ----------------------------------------------

def _fake_recorder(rank, t_base=1_000_000):
    rec = recorder.Recorder(capacity=64, rank=rank)
    rec.record("alpha", "api", t_base, t_base + 5_000)
    rec.record("beta", "pml", t_base + 1_000, t_base + 2_000)
    rec.record("gamma", "api", t_base + 6_000, t_base + 9_000)
    return rec


def test_export_chrome_shape(no_recorder):
    doc = export.to_chrome(_fake_recorder(0))
    evs = doc["traceEvents"]
    assert isinstance(evs, list)
    spans = [e for e in evs if e["ph"] == "X"]
    metas = [e for e in evs if e["ph"] == "M"]
    assert len(spans) == 3
    assert {e["name"] for e in metas} == {"process_name",
                                          "thread_name"}
    assert all(e["pid"] == 0 for e in spans)
    # per-tid timestamps are monotone
    by_tid = {}
    for e in spans:
        by_tid.setdefault(e["tid"], []).append(e["ts"])
    for ts in by_tid.values():
        assert ts == sorted(ts)
    # ts/dur are microseconds
    alpha = next(e for e in spans if e["name"] == "alpha")
    assert alpha["dur"] == 5.0
    assert doc["metadata"]["rank"] == 0


def test_export_requires_a_recorder(no_recorder):
    with pytest.raises(RuntimeError):
        export.to_chrome()


def test_merge_two_ranks_distinct_pids(tmp_path, no_recorder):
    p0 = str(tmp_path / "r0.json")
    p1 = str(tmp_path / "r1.json")
    export.write(p0, _fake_recorder(0))
    export.write(p1, _fake_recorder(1, t_base=1_500_000))
    doc = merge.merge([p0, p1])
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert {e["pid"] for e in spans} == {0, 1}
    assert doc["metadata"]["ranks"] == [0, 1]
    # metadata events lead, spans are globally ts-sorted
    ph = [e["ph"] for e in doc["traceEvents"]]
    assert ph == sorted(ph, key=lambda p: 0 if p == "M" else 1)
    ts = [e["ts"] for e in spans]
    assert ts == sorted(ts)


def test_merge_pid_collision_bumps(tmp_path, no_recorder):
    p0 = str(tmp_path / "a.json")
    p1 = str(tmp_path / "b.json")
    export.write(p0, _fake_recorder(0))
    export.write(p1, _fake_recorder(0))
    doc = merge.merge([p0, p1])
    assert doc["metadata"]["ranks"] == [0, 1]  # second file bumped


def test_merge_cli(tmp_path, capsys, no_recorder):
    p0 = str(tmp_path / "r0.json")
    p1 = str(tmp_path / "r1.json")
    recorder.hist("t_cliop", 64, 100)
    export.write(p0, _fake_recorder(0))
    export.write(p1, _fake_recorder(1))
    out = str(tmp_path / "merged.json")
    assert trace_cli.main(["merge", "-o", out, p0, p1]) == 0
    doc = json.load(open(out))
    assert {e["pid"] for e in doc["traceEvents"]} == {0, 1}
    assert trace_cli.main(["report", p0]) == 0
    text = capsys.readouterr().out
    assert "api" in text and "hist t_cliop" in text


# -- events plane: concurrent drop accounting (satellite) ----------------

def test_event_drops_concurrent_emitters_exact():
    events.register_type("t_trace_drops", "test type", ("i",))
    fired = []
    h = events.handle_alloc("t_trace_drops", buffer_size=4)
    h.set_dropped_handler(lambda n: fired.append(n))
    try:
        n_threads, per = 4, 50
        start = threading.Barrier(n_threads)

        def emitter():
            start.wait()
            for i in range(per):
                events.emit("t_trace_drops", i=i)

        ts = [threading.Thread(target=emitter)
              for _ in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        # overflow from >= 2 concurrent emitters counts EXACTLY
        assert h.dropped == n_threads * per - 4, h.dropped
        # handler fired once for the whole dropping episode
        assert len(fired) == 1, fired
        # draining re-arms the transition
        assert h.read() is not None
        events.emit("t_trace_drops", i=-1)   # refills the free slot
        assert h.dropped == n_threads * per - 4
        events.emit("t_trace_drops", i=-2)   # overflows again
        assert h.dropped == n_threads * per - 3
        assert len(fired) == 2, fired
    finally:
        h.free()


def test_event_dropped_handler_single_thread_transitions():
    events.register_type("t_trace_drops2", "test type", ("i",))
    fired = []
    h = events.handle_alloc("t_trace_drops2", buffer_size=2)
    h.set_dropped_handler(lambda n: fired.append(n))
    try:
        for i in range(6):
            events.emit("t_trace_drops2", i=i)
        assert h.dropped == 4
        assert fired == [1], fired  # once, at the transition
    finally:
        h.free()


# -- end to end: init-time enable + cross-rank clock sync ---------------

def test_trace_enabled_two_ranks_end_to_end():
    """cvar trace_enable turns the recorder on at instance init,
    clock offsets sync through the store, per-rank exports merge into
    one timeline with distinct pids and api+pml spans."""
    run_ranks("""
        import json
        from ompi_tpu.trace import export, merge, recorder
        rec = recorder.RECORDER
        assert rec is not None, "trace_enable should enable at init"
        assert rec.rank == rank
        data = np.ones(64, np.float32)
        if rank == 0:
            comm.Send(data, dest=1, tag=3)
        else:
            comm.Recv(data, source=0, tag=3)
        comm.Barrier()
        path = f"/tmp/ompi_tpu_trace_e2e_r{rank}.json"
        export.write(path, rec)
        comm.Barrier()
        if rank == 0:
            paths = [f"/tmp/ompi_tpu_trace_e2e_r{r}.json"
                     for r in range(size)]
            doc = merge.merge(paths)
            spans = [e for e in doc["traceEvents"]
                     if e.get("ph") == "X"]
            assert {e["pid"] for e in spans} == {0, 1}
            bases = [json.load(open(p))["metadata"]["clock_base_ns"]
                     for p in paths]
            assert bases[0] == bases[1], bases  # synced to rank 0
            cats = {e["cat"] for e in spans}
            assert "api" in cats and "pml" in cats, cats
        comm.Barrier()
    """, 2, mca={"trace_enable": "1"}, timeout=120)
