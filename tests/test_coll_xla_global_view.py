"""coll/xla — the global view of a resident buffer.

``_Ctx.to_global`` wraps the caller's buffer itself into the global
array a compiled collective takes (shape ``(n * s0, *rest)``, sharded
on dimension 0) and ``_Ctx.smap``'s program puts the rank axis back in
front of each block, inside the traced program. No eager ``x[None]``:
no second device program per call, no copy of the operand. The always-
on pvar ``coll_xla_global_view_copies`` counts the calls that still
dispatched one — 0-d operands only, which have no dimension to shard.

What is pinned here: every slot gives numpy's answer on every operand
shape that breaks naive arithmetic ((1,), (0, k), 2-D, 3-D, 0-d), on
the flat mesh, the 2-level ICI x DCN mesh and coll/hier's grid; the
view aliases the operand and the operand survives; and no compiled
program donates an argument, which is what makes the aliasing sound.
"""

import pytest

from tests.harness import run_ranks

MCA = {"device_plane": "on"}
MODES = {
    "flat": MCA,
    "xla_hier2": {**MCA, "coll_xla_hier": "2"},
    "coll_hier_2x2": {**MCA, "coll_hier": "on",
                      "coll_hier_split": "2x2"},
}
N = 4
SHAPES = [(2,), (1,), (256,), (4, 8), (2, 3, 5), (0, 4), ()]
SLOTS = ["Allreduce", "Bcast", "Allgather", "Alltoall",
         "Reduce_scatter_block", "Scan"]


def _admits(shape, slot) -> bool:
    """Alltoall and Reduce_scatter_block split dimension 0 over the
    ranks; the other slots take any shape. XLA's reduce_scatter
    refuses a scatter dimension of 0 (StableHLO's verifier, before
    and after this view), so (0, k) stops short of that slot."""
    if slot == "Reduce_scatter_block" and shape and not shape[0]:
        return False
    if slot == "Scan" and not shape:
        # a per-rank 0-d RESULT has no dimension for the P(AXIS) out
        # spec (shard_map refuses it, before and after this view)
        return False
    if slot in ("Alltoall", "Reduce_scatter_block"):
        return bool(shape) and shape[0] % N == 0
    return True


#: rank body shared by the cases: inputs are small integers in
#: float32, so every fold order gives the same bits and numpy's
#: answer is exact
_CASE = """
import jax
import jax.numpy as jnp
from ompi_tpu.core import pvar
shape, slots = {shape!r}, {slots!r}
hs = [np.random.default_rng(40 + r).integers(-8, 9, shape)
      .astype(np.float32) for r in range(size)]
total = hs[0].copy()
for h in hs[1:]:
    total = total + h
k = shape[0] // size if shape else 0
want = {{
    "Allreduce": lambda: total,
    "Bcast": lambda: hs[1],
    "Allgather": lambda: np.stack(hs),
    "Alltoall": lambda: np.concatenate(
        [h[rank * k:(rank + 1) * k] for h in hs]),
    "Reduce_scatter_block": lambda: total[rank * k:(rank + 1) * k],
    "Scan": lambda: np.sum(np.stack(hs[:rank + 1]), axis=0),
}}
x = jnp.asarray(hs[rank])
assert x.shape == shape
for slot in slots:
    s = pvar.session()
    call = getattr(comm, slot)
    out = call(x, root=1) if slot == "Bcast" else call(x)
    exp = np.asarray(want[slot](), np.float32)
    assert isinstance(out, jax.Array), (slot, type(out))
    assert out.shape == exp.shape, (slot, out.shape, exp.shape)
    assert out.dtype == x.dtype, (slot, out.dtype)
    np.testing.assert_array_equal(np.asarray(out), exp, err_msg=slot)
    copies = s.read("coll_xla_global_view_copies")
    assert copies == (0 if shape else 1), (slot, copies)
    assert s.read("coll_accelerator_staged") == 0, slot
    assert s.read("coll_xla_launches") == 1, slot
    # the view shared the operand's buffer: the operand is intact
    np.testing.assert_array_equal(np.asarray(x), hs[rank])
"""


@pytest.mark.parametrize(
    "shape, slot",
    [(sh, sl) for sh in SHAPES for sl in SLOTS if _admits(sh, sl)],
    ids=lambda v: v if isinstance(v, str) else "x".join(map(str, v))
    or "scalar")
def test_slot_matches_numpy_without_a_view_copy(shape, slot):
    """Every blocking slot on every operand shape: numpy's shape,
    dtype and values, ONE launch, and no eager program behind the
    global view unless the operand is 0-d."""
    run_ranks(_CASE.format(shape=shape, slots=[slot]), N, mca=MCA)


@pytest.mark.parametrize("shape", [(2,), (4, 8), (2, 3, 5), ()],
                         ids=["2", "4x8", "2x3x5", "scalar"])
@pytest.mark.parametrize("mode", ["xla_hier2", "coll_hier_2x2"])
def test_two_level_meshes_take_the_same_view(mode, shape):
    """``in_sharding2d`` (coll_xla_hier=2) and coll/hier's
    ``plan.sharding`` shard dimension 0 over BOTH mesh axes: global
    dim 0 is n_dcn * n_ici * s0 and each body still sees its
    (1, *shape) block."""
    slots = [s for s in SLOTS if _admits(shape, s)]
    run_ranks(_CASE.format(shape=shape, slots=slots), N,
              mca=MODES[mode])


@pytest.mark.parametrize("mode", list(MODES))
def test_allreduce_multi_mixed_rank_leaves(mode):
    """One eager expand per gradient leaf was the fused path's cost:
    a pytree of 1-D, 2-D, 3-D, (1,) and 0-d leaves in two dtypes now
    pays one only for the 0-d leaf, and still launches one program
    per dtype bucket."""
    run_ranks("""
    import jax.numpy as jnp
    from ompi_tpu.core import pvar
    shapes = [(5,), (2, 3), (1,), (), (2, 3, 5)]
    rng = np.random.default_rng(9)
    base = [rng.integers(-8, 9, sh) for sh in shapes]
    tree = {"f": [jnp.asarray((b * (rank + 1)).astype(np.float32))
                  for b in base],
            "i": jnp.asarray(np.arange(6).reshape(2, 3) + rank,
                             jnp.int32)}
    comm.Allreduce_multi(tree)  # warm: plan + executables
    s = pvar.session()
    out = comm.Allreduce_multi(tree)
    scale = sum(range(1, size + 1))
    for o, b, sh in zip(out["f"], base, shapes):
        assert o.shape == sh and o.dtype == jnp.float32, (o.shape, sh)
        np.testing.assert_array_equal(
            np.asarray(o), (b * scale).astype(np.float32))
    assert out["i"].dtype == jnp.int32
    np.testing.assert_array_equal(
        np.asarray(out["i"]),
        size * np.arange(6).reshape(2, 3) + sum(range(size)))
    assert s.read("coll_xla_global_view_copies") == 1  # the 0-d leaf
    assert s.read("coll_xla_launches") == 2  # one per dtype bucket
    assert s.read("coll_accelerator_staged") == 0
    # persistent form: views bound once, operands intact after two
    # cycles
    req = comm.Allreduce_multi_init(tree)
    for cycle in range(2):
        req.start()
        req.wait()
        np.testing.assert_array_equal(
            np.asarray(req.array["f"][1]),
            (base[1] * scale).astype(np.float32))
    for leaf, b in zip(tree["f"], base):
        np.testing.assert_array_equal(
            np.asarray(leaf), (b * (rank + 1)).astype(np.float32))
    """, N, mca=MODES[mode])


@pytest.mark.parametrize("shape", [s for s in SHAPES if s],
                         ids=lambda s: "x".join(map(str, s)))
def test_view_is_the_operands_buffer(shape):
    """``to_global`` of an operand with a dimension to shard is the
    caller's buffer under another shape: same device pointer, global
    shape (n * s0, *rest), under the comm's sharding and under a
    caller's (coll/hier's plan, the 2-level mesh). A 0-d operand is
    the one copy, and says so."""
    run_ranks("""
    import jax.numpy as jnp
    from ompi_tpu.coll import hier, xla as cx
    from ompi_tpu.core import pvar
    shape = %r
    x = jnp.asarray(np.arange(int(np.prod(shape)), dtype=np.float32)
                    .reshape(shape) + rank)
    ctx = cx._ctx(comm)
    plan = hier._plan(comm)
    assert ctx.mesh2d is not None and plan is not None
    s = pvar.session()
    for sharding in (None, ctx.in_sharding2d, plan.sharding):
        g = ctx.to_global(x, sharding)
        assert g.shape == (size * shape[0],) + shape[1:], g.shape
        assert g.dtype == x.dtype
        mine = g.addressable_data(0)
        assert mine.shape == shape
        if x.size:
            assert (mine.unsafe_buffer_pointer()
                    == x.unsafe_buffer_pointer())
    assert s.read("coll_xla_global_view_copies") == 0
    assert s.read("coll_xla_device_put_skipped") == 3
    # numpy in: staged once, then viewed the same way
    g = ctx.to_global(np.asarray(x))
    assert g.shape == (size * shape[0],) + shape[1:]
    assert s.read("coll_xla_global_view_copies") == 0
    # 0-d: nothing to shard; one eager expand, counted
    g0 = ctx.to_global(jnp.float32(rank))
    assert isinstance(g0, cx._Scalar) and g0.view.shape == (size,)
    assert s.read("coll_xla_global_view_copies") == 1
    """ % (shape,), N,
              mca={**MODES["coll_hier_2x2"], "coll_xla_hier": "2"})


def test_operand_survives_blocking_and_persistent_use():
    """The view aliases the operand; jax arrays are immutable and
    nothing donates, so the operand reads back unchanged after a
    blocking collective, after a persistent request's two
    Start()/Wait() cycles, and after the RESULT is deleted. A view
    does not outlive its operand: Start() after the user deleted the
    bound operand raises instead of reading freed memory."""
    run_ranks("""
    import jax.numpy as jnp
    h = np.arange(12, dtype=np.float32).reshape(3, 4) * (rank + 1)
    x = jnp.asarray(h)
    scale = sum(range(1, size + 1))
    want = np.arange(12, dtype=np.float32).reshape(3, 4) * scale
    r = comm.Allreduce(x)
    np.testing.assert_array_equal(np.asarray(r), want)
    r.delete()
    np.testing.assert_array_equal(np.asarray(x), h)
    reqs = [comm.Allreduce_init(x), comm.Bcast_init(x, 1),
            comm.Allgather_init(x)]
    for cycle in range(2):
        for req in reqs:
            req.start()
            req.wait()
        np.testing.assert_array_equal(np.asarray(reqs[0].array), want)
        np.testing.assert_array_equal(
            np.asarray(reqs[1].array),
            np.arange(12, dtype=np.float32).reshape(3, 4) * 2)
        assert reqs[2].array.shape == (size, 3, 4)
        np.testing.assert_array_equal(np.asarray(x), h)
    y = jnp.asarray(h) + 1
    req = comm.Allreduce_init(y)
    y.delete()
    try:
        req.start()
        req.wait()
    except Exception as e:
        assert "deleted" in str(e), e
    else:
        raise AssertionError("Start() ran on a deleted operand")
    """, 3, mca=MCA)


def test_no_compiled_program_donates_an_argument():
    """The invariant the aliasing rests on: after a run of every
    slot — blocking, rooted, v-variants, fused, ZeRO buckets,
    persistent — no program in ``ctx.fns`` donates an input (a
    donated view would hand the CALLER's buffer to XLA to
    overwrite)."""
    run_ranks("""
    import jax
    import jax.numpy as jnp
    from ompi_tpu import op as op_mod
    from ompi_tpu.coll import xla as cx
    seen = {}
    launch = cx._Ctx.launch
    def spy(self, fn, *args):
        seen[fn] = args
        return launch(self, fn, *args)
    cx._Ctx.launch = spy
    try:
        x = jnp.arange(2 * size * 3, dtype=jnp.float32).reshape(
            2 * size, 3) + rank
        comm.Allreduce(x)
        comm.Allreduce(x, deterministic="linear")
        comm.Allreduce(x, op=op_mod.MAX)
        comm.Bcast(x, root=1)
        comm.Allgather(x)
        comm.Alltoall(x)
        comm.Reduce_scatter_block(x)
        comm.Scan(x)
        comm.Exscan(x)
        comm.Allreduce(jnp.float32(rank))
        comm.coll.reduce_dev(comm, x, op_mod.SUM, 0)
        comm.coll.gather_dev(comm, x, 0)
        comm.coll.scatter_dev(comm, x if rank == 0 else None, 0)
        comm.Barrier()
        comm.coll.barrier_dev(comm)
        tree = [x, jnp.ones(5, jnp.int32), jnp.float32(1.0)]
        comm.Allreduce_multi(tree)
        st = comm.Reduce_scatter_multi(tree)
        comm.Allgather_multi(st)
        req = comm.Allreduce_init(x)
        req.start()
        req.wait()
    finally:
        cx._Ctx.launch = launch
    ctx = comm._coll_xla_ctx
    assert len(ctx.fns) >= 14, sorted(ctx.programs.values())
    for fn in ctx.fns.values():
        assert fn in seen, ctx.programs[fn]
        info = jax.tree.leaves(fn.lower(*seen[fn]).args_info)
        assert info and not any(a.donated for a in info), \\
            ctx.programs[fn]
    """, 3, mca={**MCA, "coll_xla_rooted_threshold_bytes": "0"})
