"""osc/pallas — device-resident one-sided plane.

Every data-moving case proves BIT-identity against the host AM window
over the same op sequence (the contract that lets CPU interpret-mode
CI stand in for TPU hardware, exactly how coll/pallas is tested): the
pallas window's kernel applies and colored fence rounds must land the
same uint32 patterns the host window's memcpy path lands. The
component is opt-in (``osc_pallas on``); every test stacks it
explicitly, and the erroneous-call matrix pins the epoch discipline
the host window never enforced.
"""

import pytest

from tests.harness import run_ranks

# One shared MCA for every osc_pallas pool: monitoring/telemetry/trace
# ride along on ALL bodies (they only observe — no semantic effect on
# the RMA paths) so the observability tests reuse the same rank pools
# as the bit-identity matrix instead of spawning their own. Pool
# spawns dominate this file's wall time on the 1-core CI box.
MCA = {"device_plane": "on", "osc_pallas": "on",
       "monitoring_level": "2", "telemetry_enable": "1",
       "trace_enable": "1"}

# shared body prologue: a pallas window and a host shadow window over
# the SAME per-rank contents, element-addressed (disp_unit=itemsize)
_WINS = """
    import jax.numpy as jnp
    from ompi_tpu import osc
    from ompi_tpu.core import pvar
    from ompi_tpu.osc.pallas import PallasWindow
    rng = np.random.default_rng(40 + rank)
    base = rng.standard_normal(32).astype(np.float32)
    wd = osc.win_create(comm, jnp.asarray(base), disp_unit=4)
    assert isinstance(wd, PallasWindow), type(wd).__name__
    wh = osc.Window(comm, base.copy(), disp_unit=4)

    def bitcheck():
        got = np.asarray(wd.array)
        ref = wh.base
        assert got.view(np.uint32).tolist() \\
            == ref.view(np.uint32).tolist(), (rank, got, ref)
"""


def test_selected_and_counted():
    """win_create under the cvar returns the pallas backend and seeds
    the well-known pvars."""
    run_ranks(_WINS + """
    assert pvar.read("osc_pallas_windows") >= 1
    assert not isinstance(wh, PallasWindow)  # host buffer -> host win
    wd.Free(); wh.Free()
    """, 2, mca=MCA)


@pytest.mark.parametrize("n", [2, 3, 4])
def test_fence_put_bit_identity(n):
    """Fence-epoch puts over colored rounds == host AM puts, bit for
    bit, on pow2 and odd meshes."""
    run_ranks(_WINS + """
    s = pvar.session()
    plds = [rng.standard_normal(4).astype(np.float32)
            for _ in range(3)]
    wd.Fence()
    for k, p in enumerate(plds):
        wd.Put(jnp.asarray(p), (rank + 1 + k) % size, disp=5 * k)
    wd.Fence()
    for k, p in enumerate(plds):
        wh.Put(p, (rank + 1 + k) % size, disp=5 * k)
    wh.Fence()
    bitcheck()
    assert s.read("osc_pallas_put") == 3
    assert s.read("osc_pallas_rounds") >= 1
    assert s.read("osc_pallas_bytes") == 3 * 16
    assert s.read("osc_pallas_am_ops") == 0  # pure device path
    wd.Free(); wh.Free()
    """, n, mca=MCA)


@pytest.mark.parametrize("n", [2, 3, 4])
def test_fence_accumulate_bit_identity(n):
    """Elementwise accumulates (sum/min/max/prod) batched into the
    fence program match the host fold bitwise — including two
    same-origin ops to one location (FIFO order preserved by round
    coloring)."""
    run_ranks(_WINS + """
    from ompi_tpu import op as op_mod
    ops = [op_mod.SUM, op_mod.MIN, op_mod.MAX, op_mod.PROD]
    plds = [rng.standard_normal(3).astype(np.float32)
            for _ in range(4)]
    wd.Fence()
    for k, (o, p) in enumerate(zip(ops, plds)):
        wd.Accumulate(jnp.asarray(p), (rank + 1) % size, disp=4 * k,
                      op=o)
    # same-origin ordered pair onto one location
    wd.Accumulate(jnp.asarray(plds[0]), (rank + 1) % size, disp=20,
                  op=op_mod.SUM)
    wd.Accumulate(jnp.asarray(plds[1]), (rank + 1) % size, disp=20,
                  op=op_mod.PROD)
    wd.Fence()
    for k, (o, p) in enumerate(zip(ops, plds)):
        wh.Accumulate(p, (rank + 1) % size, disp=4 * k, op=o)
    wh.Accumulate(plds[0], (rank + 1) % size, disp=20, op=op_mod.SUM)
    wh.Accumulate(plds[1], (rank + 1) % size, disp=20, op=op_mod.PROD)
    wh.Fence()
    bitcheck()
    wd.Free(); wh.Free()
    """, n, mca=MCA)


@pytest.mark.parametrize("n", [2, 3, 4])
def test_strided_halo_bit_identity(n):
    """Put_strided (halo columns: element stride = row width) inside
    a fence epoch == the host shmem_iput transport, bitwise."""
    run_ranks(_WINS + """
    col = rng.standard_normal(4).astype(np.float32)  # 4x8 grid column
    wd.Fence()
    wd.Put_strided(jnp.asarray(col), (rank + 1) % size, disp=7,
                   stride=8)
    wd.Fence()
    wh.Put_strided(col, (rank + 1) % size, disp=7, stride=8)
    wh.Fence()
    bitcheck()
    comm.barrier()  # a peer's lock-epoch put must not land mid-check
    # strided AM path under a lock epoch, same bit contract
    t = (rank + 1) % size
    wd.Lock(t); wd.Put_strided(jnp.asarray(col * 2), t, 0, 8)
    wd.Unlock(t)
    wh.Lock(t); wh.Put_strided(col * 2, t, 0, 8); wh.Unlock(t)
    comm.barrier()
    bitcheck()
    wd.Free(); wh.Free()
    """, n, mca=MCA)


@pytest.mark.parametrize("n", [2, 3])
def test_get_epoch_and_strided_get(n):
    """Get_epoch rides the colored rounds (data target->origin) and
    matches a host Get of the same slice; Get_strided reads kernel
    slices through the AM plane."""
    run_ranks(_WINS + """
    peer = (rank + 1) % size
    wd.Fence()
    h = wd.Get_epoch(6, peer, disp=3)
    hs = wd.Get_epoch(3, peer, disp=1, stride=9)
    wd.Fence()
    ref = np.zeros(6, np.float32)
    wh.Fence()
    wh.Get(ref, peer, disp=3)
    refs = np.zeros(3, np.float32)
    wh.Get_strided(refs, peer, disp=1, stride=9)
    wh.Fence()
    assert np.asarray(h.array).view(np.uint32).tolist() \\
        == ref.view(np.uint32).tolist()
    assert np.asarray(hs.array).view(np.uint32).tolist() \\
        == refs.view(np.uint32).tolist()
    # AM-plane strided get on the device window agrees too
    mine = np.zeros(3, np.float32)
    wd.Get_strided(mine, peer, disp=1, stride=9)
    assert mine.view(np.uint32).tolist() \\
        == refs.view(np.uint32).tolist()
    wd.Free(); wh.Free()
    """, n, mca=MCA)


def test_embedding_scatter_update_bit_identity():
    """The recommender primitive: rows of a sharded table fetched
    from owners and gradient rows accumulated back — all four ranks,
    device vs host, bitwise."""
    run_ranks("""
    import jax.numpy as jnp
    from ompi_tpu import osc
    from ompi_tpu.osc.pallas import PallasWindow
    DIM = 4
    rows = (np.arange(8 * DIM, dtype=np.float32).reshape(8, DIM)
            + 100 * rank)
    wd = osc.win_create(comm, jnp.asarray(rows), disp_unit=4)
    assert isinstance(wd, PallasWindow)
    wh = osc.Window(comm, rows.copy(), disp_unit=4)
    rng = np.random.default_rng(7 + rank)
    # each rank updates one distinct row on every owner
    grads = {t: rng.standard_normal(DIM).astype(np.float32)
             for t in range(size)}
    for w, dev in ((wd, True), (wh, False)):
        w.Fence()
        for t, g in grads.items():
            w.Accumulate(jnp.asarray(g) if dev else g, t,
                         disp=rank * DIM)
        w.Fence()
    got = np.asarray(wd.array).reshape(-1)
    assert got.view(np.uint32).tolist() \\
        == wh.base.reshape(-1).view(np.uint32).tolist()
    # lookup: fetch my row back from the next owner
    peer = (rank + 1) % size
    h = wd.Get_epoch(DIM, peer, disp=rank * DIM)
    wd.Fence()
    ref = np.zeros(DIM, np.float32)
    wh.Get(ref, peer, disp=rank * DIM)
    assert np.asarray(h.array).view(np.uint32).tolist() \\
        == ref.view(np.uint32).tolist()
    wd.Free(); wh.Free()
    """, 4, mca=MCA)


@pytest.mark.parametrize("n", [2, 3, 4])
def test_pscw_bit_identity(n):
    """Post/Start/Complete/Wait: rank 0 exposes, the others Put into
    distinct slots through the AM plane with kernel target applies —
    same bits as the host PSCW epoch."""
    run_ranks(_WINS + """
    others = [r for r in range(size) if r != 0]
    for w, dev in ((wd, True), (wh, False)):
        p = np.full(2, 1.5 + rank, np.float32)
        if rank == 0:
            w.Post(others)
            w.Wait()
        else:
            w.Start([0])
            w.Put(jnp.asarray(p) if dev else p, 0, disp=2 * rank)
            w.Complete()
    comm.barrier()
    bitcheck()
    wd.Free(); wh.Free()
    """, n, mca=MCA)


def test_lock_accumulate_atomicity():
    """Passive target: every rank adds into one counter on rank 0
    under Lock — the per-window mutex is the Accumulate atomicity
    discipline; total and bits match the host window."""
    run_ranks(_WINS + """
    for w, dev in ((wd, True), (wh, False)):
        one = np.full(1, 1.0, np.float32)
        w.Lock(0, osc.LOCK_SHARED)
        w.Accumulate(jnp.asarray(one) if dev else one, 0, disp=0)
        w.Unlock(0)
    comm.barrier()
    bitcheck()
    wd.Free(); wh.Free()
    """, 3, mca=MCA)


def test_rmw_get_accumulate_fetch_op_cas():
    """The atomic RMW surface on a device window: Get_accumulate
    returns the pre-op slice, Fetch_and_op and Compare_and_swap
    behave exactly like the host window's service-loop versions."""
    run_ranks(_WINS + """
    from ompi_tpu import op as op_mod
    val = np.full(2, 2.0, np.float32)
    for w, dev in ((wd, True), (wh, False)):
        old = np.zeros(2, np.float32)
        w.Lock(rank)  # self passive epoch covers the RMW ops
        w.Get_accumulate(jnp.asarray(val) if dev else val, old,
                         rank, disp=4)
        one, prev = np.ones(1, np.float32), np.zeros(1, np.float32)
        w.Fetch_and_op(one, prev, rank, disp=4)
        got = np.zeros(1, np.float32)
        cur = np.array(prev[0] + 0.0, np.float32).reshape(1)
        w.Compare_and_swap(np.full(1, 9.0, np.float32), cur, got,
                           rank, disp=4)
        w.Unlock(rank)
    comm.barrier()
    bitcheck()
    # NO_OP Get_accumulate reads without modifying
    snap = np.asarray(wd.array).copy()
    res = np.zeros(2, np.float32)
    wd.Lock(rank)
    wd.Get_accumulate(val, res, rank, disp=4, op=op_mod.NO_OP)
    wd.Unlock(rank)
    assert np.array_equal(np.asarray(wd.array), snap)
    wd.Free(); wh.Free()
    """, 2, mca=MCA)


def test_creation_fallthrough_unsupported_dtype():
    """int16 device buffers are outside the kernel support matrix:
    win_create records the fallthrough and serves a HOST window that
    still works."""
    run_ranks("""
    import jax.numpy as jnp
    from ompi_tpu import osc
    from ompi_tpu.core import pvar
    from ompi_tpu.osc.pallas import PallasWindow
    s = pvar.session()
    win = osc.win_create(comm, jnp.zeros(8, jnp.int16), disp_unit=2)
    assert not isinstance(win, PallasWindow)
    assert s.read("osc_pallas_fallthrough") >= 1
    win.Fence()
    win.Put(np.full(2, 3, np.int16), (rank + 1) % size, disp=0)
    win.Fence()
    assert win.base[0] == 3
    win.Free()
    """, 2, mca=MCA)


def test_off_by_default_keeps_staging_semantics():
    """Without the cvar, a device-buffer win_create keeps the
    documented host-staging window — existing behavior unchanged."""
    run_ranks("""
    import jax.numpy as jnp
    from ompi_tpu import osc
    from ompi_tpu.osc.pallas import PallasWindow
    win = osc.win_create(comm, jnp.zeros(4, jnp.float32))
    assert not isinstance(win, PallasWindow)
    win.Free()
    """, 2, mca={"device_plane": "on"})


def test_op_fallthrough_nonelementwise_accumulate():
    """A valid but non-elementwise op (BAND) falls through to the
    host-assisted AM path: counted, warned once, and the result still
    matches the host window bitwise."""
    run_ranks("""
    import jax.numpy as jnp
    from ompi_tpu import op as op_mod, osc
    from ompi_tpu.core import pvar
    from ompi_tpu.osc.pallas import PallasWindow
    s = pvar.session()
    base = np.arange(8, dtype=np.int32) + 10 * rank
    wd = osc.win_create(comm, jnp.asarray(base), disp_unit=4)
    assert isinstance(wd, PallasWindow)
    wh = osc.Window(comm, base.copy(), disp_unit=4)
    mask = np.full(4, 6, np.int32)
    for w, dev in ((wd, True), (wh, False)):
        w.Fence()
        w.Accumulate(jnp.asarray(mask) if dev else mask,
                     (rank + 1) % size, disp=2, op=op_mod.BAND)
        w.Fence()
    assert np.asarray(wd.array).tolist() == wh.base.tolist()
    assert s.read("osc_pallas_fallthrough") >= 1
    assert s.read("osc_pallas_am_ops") >= 1
    wd.Free(); wh.Free()
    """, 2, mca=MCA)


def test_err_put_outside_epoch():
    run_ranks("""
    import jax.numpy as jnp
    from ompi_tpu import errors, osc
    win = osc.win_create_pallas(comm, jnp.zeros(4, jnp.float32))
    for attempt in range(2):  # uncached: raises EVERY call
        try:
            win.Put(jnp.ones(1, jnp.float32), 0)
            raise AssertionError("Put outside epoch did not raise")
        except errors.MPIError as e:
            assert e.error_class == errors.ERR_RMA_SYNC, e.error_class
    win.Free()
    """, 2, mca=MCA)


def test_err_unlock_without_lock():
    run_ranks("""
    import jax.numpy as jnp
    from ompi_tpu import errors, osc
    win = osc.win_create_pallas(comm, jnp.zeros(4, jnp.float32))
    for attempt in range(2):
        try:
            win.Unlock((rank + 1) % size)
            raise AssertionError("Unlock without Lock did not raise")
        except errors.MPIError as e:
            assert e.error_class == errors.ERR_RMA_SYNC, e.error_class
    win.Free()
    """, 2, mca=MCA)


def test_err_complete_without_start():
    run_ranks("""
    import jax.numpy as jnp
    from ompi_tpu import errors, osc
    win = osc.win_create_pallas(comm, jnp.zeros(4, jnp.float32))
    for attempt in range(2):
        try:
            win.Complete()
            raise AssertionError("Complete without Start did not raise")
        except errors.MPIError as e:
            assert e.error_class == errors.ERR_RMA_SYNC, e.error_class
    win.Free()
    """, 2, mca=MCA)


def test_err_accumulate_dtype_mismatch():
    run_ranks("""
    import jax.numpy as jnp
    from ompi_tpu import errors, osc
    win = osc.win_create_pallas(comm, jnp.zeros(4, jnp.float32))
    win.Fence()
    for attempt in range(2):
        try:
            win.Accumulate(np.ones(2, np.float64), 0, disp=0)
            raise AssertionError("dtype-mismatched acc did not raise")
        except errors.MPIError as e:
            assert e.error_class == errors.ERR_ARG, e.error_class
    win.Fence()
    win.Free()
    """, 2, mca=MCA)


def test_err_rput_outside_passive_epoch():
    """Request-based RMA is passive-target only on this backend."""
    run_ranks("""
    import jax.numpy as jnp
    from ompi_tpu import errors, osc
    win = osc.win_create_pallas(comm, jnp.zeros(4, jnp.float32))
    win.Fence()  # an ACTIVE epoch is not enough for Rput/Rget
    for meth, args in (("Rput", (jnp.ones(1, jnp.float32), 0)),
                       ("Rget", (np.ones(1, np.float32), 0))):
        try:
            getattr(win, meth)(*args)
            raise AssertionError(f"{meth} outside Lock did not raise")
        except errors.MPIError as e:
            assert e.error_class == errors.ERR_RMA_SYNC, e.error_class
    win.Fence()
    win.Free()
    """, 2, mca=MCA)


def test_monitoring_link_attribution_torus():
    """Level-2 monitoring on the 2x2 torus: fence-flush RMA bytes
    walk the CartTopo routes into per-link pvars, and the osc context
    table carries the wire totals."""
    run_ranks("""
    import jax.numpy as jnp
    from ompi_tpu import osc
    from ompi_tpu.core import pvar
    from ompi_tpu.monitoring import matrix
    from ompi_tpu.osc.pallas import PallasWindow
    tm = matrix.TRAFFIC
    assert tm is not None and tm.level == 2 and tm.linkmap is not None
    win = osc.win_create(comm, jnp.zeros(16, jnp.float32),
                         disp_unit=4)
    assert isinstance(win, PallasWindow)
    win.Fence()
    win.Put(jnp.full(8, 1.0 + rank, jnp.float32), (rank + 1) % size,
            disp=0)
    win.Fence()
    cell = tm.tables["osc"].get((rank + 1) % size)
    assert cell is not None and cell[1] >= 32.0, tm.tables["osc"]
    links = {n: v for n, v in pvar.snapshot().items()
             if n.startswith("monitoring_link_bytes_d")}
    assert links and any(v > 0 for v in links.values()), links
    win.Free()
    """, 4, mca=MCA)


def test_flight_slots_and_epoch_spans():
    """Telemetry integration: a fence leaves an osc_pallas epoch span
    in the trace recorder, and the flight-recorder slot strings name
    window and peer (what a watchdog hang dump prints)."""
    run_ranks("""
    import jax.numpy as jnp
    from ompi_tpu import osc
    from ompi_tpu.telemetry import flight
    from ompi_tpu.trace import recorder as trace
    win = osc.win_create_pallas(comm, jnp.zeros(8, jnp.float32))
    win.Fence()
    win.Put(jnp.ones(2, jnp.float32), (rank + 1) % size, disp=0)
    win.Fence()
    rec = trace.RECORDER
    assert rec is not None
    spans = [s for s in rec.spans() if s.subsys == "osc_pallas"]
    assert any(s.args.get("op") == "fence" for s in spans), spans
    fl = flight.FLIGHT
    assert fl is not None
    win.Lock((rank + 1) % size, osc.LOCK_SHARED)
    win.Unlock((rank + 1) % size)
    spans = [s for s in rec.spans() if s.subsys == "osc_pallas"]
    assert any(s.args.get("op") == "passive" for s in spans), spans
    win.Free()
    """, 2, mca=MCA)


def test_device_epoch_fallback_counted():
    """Satellite: the device_epoch window now counts + warns its host
    reroutes instead of silently raising — non-fusable accumulate and
    every passive-target verb."""
    run_ranks("""
    import jax.numpy as jnp
    from ompi_tpu import errors, op as op_mod, osc
    from ompi_tpu.core import pvar
    s = pvar.session()
    win = osc.win_create_device(comm, jnp.zeros(8, jnp.float32))
    win.Fence()
    try:
        win.Accumulate(jnp.ones(2, jnp.float32), 0, op=op_mod.BAND)
        raise AssertionError("non-fusable acc did not raise")
    except errors.MPIError as e:
        assert e.error_class == errors.ERR_OP
    assert s.read("osc_device_fallbacks") == 1
    for verb, args in (("Lock", (0,)), ("Unlock", (0,)),
                       ("Flush", (0,)), ("Post", ([0],)),
                       ("Start", ([0],))):
        try:
            getattr(win, verb)(*args)
            raise AssertionError(f"{verb} on device-epoch window")
        except errors.MPIError as e:
            assert e.error_class == errors.ERR_RMA_SYNC
    assert s.read("osc_device_fallbacks") == 6
    win.Fence()
    win.Free()
    """, 2, mca={"device_plane": "on"})
