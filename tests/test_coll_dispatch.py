"""coll/dispatch — the one seam every blocking device slot is launched
and observed through.

One parametrised case per blocking ``*_dev`` slot of the CollXla table,
on one shared 2-rank pool with monitoring, tune, telemetry and the
trace ring all up: one call gives exactly one flight entry named after
the slot that launched (gone after the return, and gone after a
launcher that raises), one traffic record, one tune sample under the
provider that served, and the slot's span with its operand bytes. The
rooted threshold is 0, so ``reduce_dev`` and ``gather_dev`` launch
their own schedules; ``reduce_scatter_dev`` and ``gatherv_dev`` serve
through another slot and are observed as that slot, once.
"""

import types

import pytest

from tests.harness import run_ranks

MCA = {"device_plane": "on", "monitoring_level": "1",
       "tune_observe": "1", "telemetry_enable": "1",
       "trace_enable": "1", "coll_xla_rooted_threshold_bytes": "0"}

# operands every case picks from (8 rows, divisible by the 2 ranks)
_OPERANDS = """
    import jax.numpy as jnp
    from ompi_tpu import op as op_mod
    from ompi_tpu.zero import layout as zl
    x = jnp.arange(8, dtype=jnp.float32) + rank
    y = jnp.arange(4, dtype=jnp.int32) + rank
    counts = (3, 5)
    state = zl.ShardedState.from_full(comm, [x, y])
    c = comm
"""

#: slot -> (the op that launches and is observed, args after comm,
#: the span's nbytes); ``c`` is the comm the slot is called on
SLOTS = {
    "allreduce_dev": ("allreduce", "(x,)", "x.nbytes"),
    "allreduce_multi_dev":
        ("allreduce_multi", "([x, y],)", "x.nbytes + y.nbytes"),
    "reduce_scatter_multi_dev":
        ("reduce_scatter_multi", "([x, y],)", "x.nbytes + y.nbytes"),
    "allgather_multi_dev":
        ("allgather_multi", "(state,)", "x.nbytes + y.nbytes"),
    "allgather_multi_bucket_dev":
        ("allgather_multi_bucket", "(state, 0)", "x.nbytes + y.nbytes"),
    "reduce_dev": ("reduce", "(x, op_mod.SUM, 1)", "x.nbytes"),
    "bcast_dev": ("bcast", "(x, 1)", "x.nbytes"),
    "allgather_dev": ("allgather", "(x,)", "x.nbytes"),
    "gather_dev": ("gather", "(x, 1)", "x.nbytes"),
    "alltoall_dev": ("alltoall", "(x,)", "x.nbytes"),
    "reduce_scatter_block_dev":
        ("reduce_scatter_block", "(x,)", "x.nbytes"),
    "scatter_dev":
        ("scatter", "(x if rank == 1 else None, 1, x[:4])",
         "x.nbytes if rank == 1 else 0"),
    "scan_dev": ("scan", "(x,)", "x.nbytes"),
    "exscan_dev": ("exscan", "(x,)", "x.nbytes"),
    "barrier_dev": ("barrier", "()", "0"),
    "allgatherv_dev":
        ("allgatherv", "(x[:counts[rank]], counts)",
         "4 * counts[rank]"),
    "gatherv_dev":
        ("allgatherv", "(x[:counts[rank]], counts, 1)",
         "4 * counts[rank]"),
    "alltoallv_dev":
        ("alltoallv", "(x, counts, (counts[rank],) * 2, 5)",
         "x.nbytes"),
    "scatterv_dev":
        ("scatterv", "(x if rank == 1 else None, counts, 1, x[:1])",
         "x.nbytes if rank == 1 else 0"),
    "reduce_scatter_dev": ("allreduce", "(x, counts)", "x.nbytes"),
    "neighbor_allgather_dev": ("neighbor_allgather", "(x,)", "x.nbytes"),
    "neighbor_alltoall_dev":
        ("neighbor_alltoall", "(x.reshape(2, 4),)", "x.nbytes"),
}


@pytest.mark.parametrize("slot", sorted(SLOTS))
def test_slot_is_observed_once(slot):
    op, args, nbytes = SLOTS[slot]
    cart = "c = comm.Create_cart([2], periods=[True])" \
        if slot.startswith("neighbor") else ""
    run_ranks(_OPERANDS + f"""
    {cart}
    from ompi_tpu.coll import xla as cx
    from ompi_tpu.core import pvar
    from ompi_tpu.telemetry import flight
    from ompi_tpu.trace import recorder as trace
    SLOT, OP, args, nb = "{slot}", "{op}", {args}, {nbytes}
    fl, rec = flight.FLIGHT, trace.RECORDER
    assert fl is not None and rec is not None
    assert c.coll.providers[SLOT] == "xla", c.coll.providers[SLOT]
    fn = getattr(c.coll, SLOT)
    open_now = lambda: [e for e in fl.snapshot() if "op" in e]
    entered = []
    fl.enter = lambda name, *a: (entered.append(name),
                                 type(fl).enter(fl, name, *a))[1]
    try:
        c.Barrier()
        rec.clear()
        del entered[:]
        s = pvar.session()
        fn(c, *args)
        assert entered == [OP + "_dev"], entered
        assert open_now() == [], open_now()
        assert s.read("telemetry_flight_ops") == 1
        assert s.read("monitoring_coll_launches") == 1
        assert s.read("tune_samples") == 1
        assert s.read("tune_obs_" + OP + "_xla") == 1
        assert s.read("coll_xla_device") == 1
        own = [sp for sp in rec.spans()
               if sp.subsys == "coll_xla" and sp.name == SLOT[:-4]]
        assert len(own) == 1, [(sp.subsys, sp.name) for sp in rec.spans()]
        assert own[0].args["nbytes"] == nb, (own[0].args, nb)

        # a launcher that raises leaves nothing in flight either
        def boom(*a):
            raise RuntimeError("the launch failed")

        ctx = cx._ctx(c)
        ctx.launch = boom
        del entered[:]
        try:
            fn(c, *args)
        except RuntimeError:
            pass
        else:
            raise AssertionError("the launcher did not run")
        finally:
            del ctx.launch
        assert entered == [OP + "_dev"], entered
        assert open_now() == [], open_now()
        c.Barrier()
    finally:
        del fl.enter
    """, 2, mca=MCA)


def test_planes_down_constructs_nothing(monkeypatch):
    """The default, and every benchmark run: with monitoring, tune
    and the flight recorder down, a slot's call through the seam
    constructs none of them and calls none of their methods."""
    import jax.numpy as jnp

    from ompi_tpu.coll import dispatch, xla as cx
    from ompi_tpu.core import pvar
    from ompi_tpu.monitoring import matrix
    from ompi_tpu.telemetry import flight
    from ompi_tpu.tune import observe

    def boom(*a, **k):
        raise AssertionError("an observation plane was touched")

    for mod, guard, cls, hooks in (
            (matrix, "TRAFFIC", matrix.TrafficMatrix, ("coll",)),
            (observe, "OBSERVER", observe.Observer, ("timed", "sample")),
            (flight, "FLIGHT", flight.FlightRecorder, ("enter", "exit"))):
        monkeypatch.setattr(mod, guard, None)
        for name in ("__init__",) + hooks:
            monkeypatch.setattr(cls, name, boom)
    monkeypatch.setattr(dispatch, "nbytes_of", boom)
    # two "ranks" over one device: the psum is an identity, the host
    # path is the real one
    comm = types.SimpleNamespace(_coll_xla_ctx=cx._Ctx.local(),
                                 size=2, rank=0, cid=0)
    s = pvar.session()
    x = jnp.ones(16, jnp.float32)
    assert cx.allreduce_dev(comm, x).tolist() == x.tolist()
    assert s.read("coll_xla_device") == 1
    assert s.read("coll_xla_launches") == 1
