"""prof/ subsystem tests: phase ledger nesting/reentrancy +
cross-thread current_phase, the zero-cost disabled guard over every
instrumented site, transfer byte/bandwidth accounting on the CPU
staging path (chunked + plain), compile + compile-cache pvars,
watchdog phase attribution, sampler bandwidth gauge, and the
attribution CLI round-trip (local merge + 2-rank store-synced run)."""

import json
import threading
import time
import types

import numpy as np
import pytest

from ompi_tpu.core import pvar
from ompi_tpu.prof import __main__ as prof_cli
from ompi_tpu.prof import ledger
from ompi_tpu.trace import export, recorder
from tests.harness import run_ranks


@pytest.fixture
def no_prof():
    """Guarantee profiler AND recorder are off before and after."""
    ledger.disable()
    recorder.disable()
    yield
    ledger.disable()
    recorder.disable()


# -- phase ledger --------------------------------------------------------

def test_phase_nesting_reentrancy_pvars_and_spans(no_prof):
    ledger.enable(rank=0)
    recorder.enable(rank=0)
    s = pvar.session()
    assert ledger.current_phase() is None
    with ledger.phase("staging"):
        assert ledger.current_phase() == "staging"
        with ledger.phase("compile"):          # nesting
            assert ledger.current_phase() == "compile"
            time.sleep(0.002)
        assert ledger.current_phase() == "staging"
    assert ledger.current_phase() is None
    with ledger.phase("staging"):              # reentrancy
        pass
    ph = ledger.phase_seconds()
    # a nested phase counts in itself AND its parent
    assert ph["staging"] >= ph["compile"] > 0
    assert ledger.PROFILER.phase_counts() == {"staging": 2,
                                              "compile": 1}
    assert s.read("prof_phase_staging_ns") > 0
    assert s.read("prof_phase_compile_ns") > 0
    spans = [(sp.name, sp.subsys) for sp in recorder.RECORDER.spans()]
    assert spans.count(("staging", "prof")) == 2
    assert spans.count(("compile", "prof")) == 1


def test_current_phase_cross_thread(no_prof):
    """The watchdog/sampler threads ask "what is this RANK doing" —
    with no phase of their own they must read the main thread's."""
    ledger.enable()
    seen = []
    with ledger.phase("train"):
        t = threading.Thread(
            target=lambda: seen.append(ledger.current_phase()))
        t.start()
        t.join()
    assert seen == ["train"]

    def worker():
        with ledger.phase("io"):               # own phase wins
            seen.append(ledger.current_phase())

    with ledger.phase("train"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    assert seen == ["train", "io"]


def test_disabled_guard_constructs_nothing(monkeypatch, no_prof):
    """Default-off profiling must not touch ledger machinery on any
    instrumented site — the one-branch guard contract: phase() hands
    out the shared no-op, and the accelerator/coll hot paths never
    read a clock or build a span for the profiler."""
    import jax.numpy as jnp

    from ompi_tpu.accelerator import tpu as tpu_mod
    from ompi_tpu.coll import xla as cx

    assert ledger.PROFILER is None

    def boom(*a, **k):
        raise AssertionError("prof machinery touched while disabled")

    monkeypatch.setattr(ledger, "now", boom)
    monkeypatch.setattr(ledger, "_PhaseOpen", boom)
    monkeypatch.setattr(ledger.Profiler, "xfer", boom)
    monkeypatch.setattr(ledger.Profiler, "xfer_chunk", boom)

    assert ledger.phase("staging") is ledger._NOP
    with ledger.phase("staging"):
        pass
    acc = tpu_mod.TpuAccelerator()
    # plain + chunked H2D, D2H readback — every accelerator copy site
    small = acc.to_host(acc.to_device(np.ones(1024, np.float32)))
    assert small.nbytes == 4096
    big = np.ones((9 << 20) // 4, np.float32)
    assert acc.to_host(acc.to_device(big)).nbytes == big.nbytes
    # coll/xla staging + compile sites
    ctx = cx._Ctx.local()
    comm = types.SimpleNamespace(_coll_xla_ctx=ctx)
    s = pvar.session()
    launch = cx._allreduce_prep(comm, jnp.ones(16, jnp.float32))
    launch()
    launch()
    assert s.read("coll_xla_launches") >= 2    # the path really ran


# -- transfer accounting -------------------------------------------------

def test_transfer_accounting_chunked_h2d_and_d2h(no_prof):
    from ompi_tpu.accelerator import tpu as tpu_mod
    from ompi_tpu.telemetry import openmetrics

    ledger.enable(rank=0)
    acc = tpu_mod.TpuAccelerator()
    acc.to_host(acc.to_device(np.ones(4, np.float32)))  # warm backend
    recorder.enable(rank=0)  # after warm-up: spans below are exact
    s = pvar.session()
    host = np.ones((9 << 20) // 4, np.float32)  # 9 MiB: chunked path
    back = acc.to_host(acc.to_device(host))
    assert back.nbytes == host.nbytes
    # byte accounting is exact — chunk spans must not double-count
    assert s.read("prof_xfer_h2d_bytes") == host.nbytes
    assert s.read("prof_xfer_d2h_bytes") == host.nbytes
    assert s.read("prof_xfer_h2d_ns") > 0
    assert s.read("prof_xfer_d2h_ns") > 0
    assert pvar.read("prof_xfer_h2d_bw_mbps") > 0  # peak watermark
    spans = recorder.RECORDER.spans()
    h2d = [sp for sp in spans
           if sp.subsys == "xfer" and sp.name == "h2d"]
    chunks = [sp for sp in spans if sp.name == "h2d_chunk"]
    d2h = [sp for sp in spans
           if sp.subsys == "xfer" and sp.name == "d2h"]
    assert len(h2d) == 1 and h2d[0].args["bytes"] == host.nbytes
    assert h2d[0].args["chunks"] == len(chunks) == 2
    assert sum(sp.args["bytes"] for sp in chunks) == host.nbytes
    assert d2h[-1].args == {"bytes": host.nbytes, "site": "to_host"}
    assert ledger.PROFILER.rolling_bw_bps("h2d") > 0
    # the log2 size/latency histogram reaches the OpenMetrics page as
    # a real histogram family
    text = openmetrics.render(pvar.snapshot(), {"rank": "0"})
    for d in ("h2d", "d2h"):
        fam = openmetrics.PREFIX + "trace_hist_xfer_" + d
        assert f"# TYPE {fam} histogram" in text
        assert fam + "_bucket" in text


def test_sampler_publishes_rolling_bandwidth_gauge(no_prof):
    from ompi_tpu.telemetry import openmetrics
    from ompi_tpu.telemetry.sampler import Sampler

    p = ledger.enable()
    p.xfer("h2d", 1 << 20, 0, 1_000_000)       # 1 MiB in 1 ms
    smp = Sampler(rank=0, jobid="jp", size=1, interval=3600,
                  port=0, path="", rollup=False)
    text = smp.sample()
    metric = openmetrics.PREFIX + "prof_xfer_h2d_rolling_bps"
    assert f"# TYPE {metric} gauge" in text
    parsed = openmetrics.parse(text)
    val = parsed["prof_xfer_h2d_rolling_bps"]['{job="jp",rank="0"}']
    assert val == int((1 << 20) * 1e9 / 1_000_000)
    # no d2h samples yet -> no gauge fabricated
    assert "prof_xfer_d2h_rolling_bps" not in parsed


# -- compile observability -----------------------------------------------

def test_ctx_compile_pvars_miss_then_hit(no_prof):
    """A coll/xla program's first launch is in the compile ledger
    (always on: no `prof_enable`) as the job's own; the same slot
    again compiles nothing."""
    import jax.numpy as jnp

    from ompi_tpu import prof
    from ompi_tpu.coll import xla as cx

    prof.wire_compile_cache()
    ctx = cx._Ctx.local()
    comm = types.SimpleNamespace(_coll_xla_ctx=ctx)
    s = pvar.session()
    launch = cx._allreduce_prep(comm, jnp.ones(16, jnp.float32))
    launch()
    assert s.read("compile_programs") >= 1
    assert s.read("compile_trace_ns") > 0
    assert s.read("compile_lower_ns") > 0
    assert s.read("compile_backend_ns") > 0
    assert "ompi_allreduce" in [r["program"]
                                for r in prof.compile_table() if r["own"]]
    s2 = pvar.session()
    relaunch = cx._allreduce_prep(comm, jnp.ones(16, jnp.float32))
    relaunch()
    assert s2.read("coll_xla_cache_hits") >= 1
    assert not {k: v for k, v in s2.snapshot().items()
                if k.startswith("compile_") and v}


# compile-cache placement and hit/miss accounting: tests/test_bringup.py


# -- watchdog phase attribution ------------------------------------------

def test_watchdog_dump_carries_current_phase(tmp_path, no_prof):
    """A rank stuck in staging reports phase=staging in its hang dump
    instead of being misattributed to the collective it never ran."""
    from ompi_tpu.telemetry import flight
    from ompi_tpu.telemetry.watchdog import Watchdog

    ledger.enable()
    fl = flight.FlightRecorder()
    fl.exit(fl.enter("warmup"))
    fl.enter("allreduce_dev", comm_cid=1, nbytes=64)
    wd = Watchdog(rank=0, jobid="jp", world=range(2), client=None,
                  flight_rec=fl, dead_fn=lambda: {}, period=3600,
                  timeout=0.0, action="dump", dump_dir=str(tmp_path))
    with ledger.phase("staging"):
        v = wd.sweep()
    assert v is not None and v["stragglers"] == [1]
    doc = json.load(open(wd._dumped[(2, "hang")]))
    assert doc["phase"] == "staging"


# -- pvar plane ----------------------------------------------------------

def test_prof_pvars_are_well_known():
    for name in ("prof_phase_staging_ns", "prof_phase_compile_ns",
                 "prof_phase_train_ns", "prof_phase_teardown_ns",
                 "prof_xfer_h2d_bytes", "prof_xfer_h2d_ns",
                 "prof_xfer_d2h_bytes", "prof_xfer_d2h_ns",
                 "compile_trace_ns", "compile_lower_ns",
                 "compile_backend_ns", "compile_cache_load_ns",
                 "compile_programs", "compile_cache_requests",
                 "compile_cache_hits", "compile_foreign_ns",
                 "compile_foreign_programs", "prof_compile_cache_hits",
                 "prof_compile_cache_misses"):
        assert name in pvar.WELL_KNOWN, name


# -- attribution CLI -----------------------------------------------------

def _prof_recorder(rank, t_base=1_000_000):
    """A rank trace with prof + xfer + ordinary spans; staging is the
    worst-rank phase on rank 1 (40 ms vs 30 ms)."""
    rec = recorder.Recorder(capacity=64, rank=rank)
    stag = 40_000_000 if rank else 30_000_000
    rec.record("staging", "prof", t_base, t_base + stag)
    rec.record("h2d", "xfer", t_base + 1_000, t_base + 2_001_000,
               {"bytes": 1 << 20, "site": "to_device", "chunks": 1})
    rec.record("train", "prof", t_base + stag,
               t_base + stag + 10_000_000)
    rec.record("launch", "coll_xla", t_base + stag + 500,
               t_base + stag + 600)
    return rec


def test_attribution_cli_roundtrip(tmp_path, capsys, no_prof):
    p0 = str(tmp_path / "r0.json")
    p1 = str(tmp_path / "r1.json")
    export.write(p0, _prof_recorder(0))
    export.write(p1, _prof_recorder(1))
    out = str(tmp_path / "attr.json")
    assert prof_cli.main(
        ["report", "-o", out, "--top", "5", p0, p1]) == 0
    text = capsys.readouterr().out
    assert "phase ledger" in text and "transfers h2d" in text
    rep = json.load(open(out))
    assert rep["schema"] == prof_cli.SCHEMA
    assert rep["ranks"] == [0, 1]
    # worst-rank ordering: staging (0.04 s on rank 1) ranks first
    assert rep["phases"][0]["phase"] == "staging"
    assert rep["phases"][0]["max_s"] == pytest.approx(0.04)
    assert rep["phases"][0]["per_rank_s"] == {"0": 0.03, "1": 0.04}
    assert rep["phases"][1]["phase"] == "train"
    assert rep["transfers"]["h2d"]["bytes"] == 2 << 20
    assert rep["transfers"]["h2d"]["spans"] == 2
    assert rep["transfers"]["h2d"]["avg_gbps"] is not None
    # prof spans never list themselves as consumers
    assert rep["top"] and all(c["subsys"] != "prof"
                              for c in rep["top"])


def test_attribution_cli_missing_input(tmp_path, capsys, no_prof):
    assert prof_cli.main(
        ["report", str(tmp_path / "nope.json")]) == 1
    err = capsys.readouterr().err
    assert err.startswith("prof report:") and err.count("\n") == 1


def test_attribution_cli_corrupt_input(tmp_path, capsys, no_prof):
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert prof_cli.main(["report", str(bad)]) == 1
    assert "corrupt" in capsys.readouterr().err


# -- end to end: init-time enable + 2-rank merged attribution ------------

def test_prof_enabled_two_ranks_end_to_end():
    """cvar prof_enable turns the ledger on at instance init; phase +
    transfer spans ride the trace recorder; the CLI merges both ranks
    (store-synced clocks) and attributes the wall to staging."""
    run_ranks("""
        import json, time
        from ompi_tpu.accelerator import tpu as tpu_mod
        from ompi_tpu.prof import ledger
        from ompi_tpu.prof import __main__ as prof_cli
        from ompi_tpu.trace import export, recorder
        assert ledger.PROFILER is not None, "prof_enable at init"
        assert ledger.PROFILER.rank == rank
        acc = tpu_mod.TpuAccelerator()
        with ledger.phase("staging"):
            dev = acc.to_device(np.ones(1 << 18, np.float32))
            time.sleep(0.15)
        with ledger.phase("train"):
            time.sleep(0.02)
        comm.Barrier()
        path = f"/tmp/ompi_tpu_prof_e2e_r{rank}.json"
        export.write(path, recorder.RECORDER)
        comm.Barrier()
        if rank == 0:
            paths = [f"/tmp/ompi_tpu_prof_e2e_r{r}.json"
                     for r in range(size)]
            out = "/tmp/ompi_tpu_prof_e2e_attr.json"
            assert prof_cli.main(["report", "-o", out] + paths) == 0
            rep = json.load(open(out))
            assert rep["ranks"] == [0, 1]
            assert rep["phases"][0]["phase"] == "staging"
            assert rep["phases"][0]["max_s"] >= 0.15
            assert "train" in {p["phase"] for p in rep["phases"]}
            assert rep["transfers"]["h2d"]["bytes"] >= 2 * (1 << 20)
        comm.Barrier()
    """, 2, mca={"prof_enable": "1", "trace_enable": "1"},
        timeout=120)
