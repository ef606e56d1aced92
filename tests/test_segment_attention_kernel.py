"""Two-way segment attention's kernels (ops/segment_attention.py behind
`ops/attention.blockwise_mha(segments=)` and the rule `segment_tile`)
against the full-softmax reference `att.mha(segments=)`, here on the CPU
with the kernels in interpret mode at toy sizes and 128-row tiles.

Tolerances. With float32 operands under `highest` both sides keep
float32 scores, statistics and sums and differ by the order of the sums
and by where the division by the row sum happens: 2e-5 of the largest
entry (measured 1e-6). With bfloat16 operands the kernels round the
UNNORMALISED probabilities to bfloat16 before the PV product and dS
before its two products, the reference the normalised ones and lets XLA
round the cotangent: two roundings of the same numbers, 1% of the norm
(measured 0.3-0.7%). A tile visited that should not be, ids compared for
the wrong pair, a tile taken for interior that is not, or a row left out
misses these by orders of magnitude.
"""

import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402

from ompi_tpu.core import pvar  # noqa: E402
from ompi_tpu.ops import attention as att  # noqa: E402
from ompi_tpu.ops import segment_attention as sg  # noqa: E402

TILE = 128

#: name -> the ids of a packed row of 384 (three tiles of 128)
PACKINGS = {
    "edges_on_tile_edges": np.repeat([0, 1], [128, 256]),
    "edges_inside_tiles": np.repeat([0, 1, 2], [100, 156, 128]),
    "one_image_fills_the_row": np.zeros(384, np.int64),
    # the table is a superset for ids that are not sorted: still exact
    "ids_that_decrease": np.repeat([2, 0, 1], [100, 28, 256]),
    # rows 192.. (id 2) meet key tile 0 (ids 0, 1) first: all masked
    "first_tile_wholly_masked_for_some_rows": np.repeat([0, 1, 2],
                                                        [64, 128, 192]),
    "many_small_images": np.arange(384) // 24,
    "ids_that_alternate": np.arange(384) % 2,
}

#: (D, Dv, H, heads a step forward, backward)
WIDTHS = {
    "72_72_h4_g2_g1": (72, 72, 4, 2, 1),
    "24_8_h3_g3_g1": (24, 8, 3, 3, 1),
    "128_128_h2_g1_g2": (128, 128, 2, 1, 2),
}


def _operands(t, h, d, dv, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    make = lambda key, *s: jax.random.normal(  # noqa: E731
        key, s, jnp.float32).astype(dtype)
    return (make(ks[0], 1, t, h, d), make(ks[1], 1, t, h, d),
            make(ks[2], 1, t, h, dv),
            jax.random.normal(ks[3], (1, t, h, dv), jnp.float32))


def _value_and_grads(fn, q, k, v, g):
    def loss(q, k, v):
        o = fn(q, k, v)
        return jnp.sum(o.astype(jnp.float32) * g), o
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                      has_aux=True))(q, k, v)


def _gap(got, want):
    got, want = (np.asarray(x, np.float64) for x in (got, want))
    return np.linalg.norm(got - want) / np.linalg.norm(want)


def _both(q, k, v, g, ids, tiles):
    ids = jnp.asarray(ids, jnp.int32)[None]
    (_, o), grads = _value_and_grads(
        lambda *a: att.blockwise_mha(*a, tiles, interpret=True, segments=ids),
        q, k, v, g)
    with jax.default_matmul_precision("highest"):
        (_, o_w), grads_w = _value_and_grads(
            lambda *a: att.mha(*a, causal=False, segments=ids), q, k, v, g)
    return (o, *grads), (o_w, *grads_w)


@pytest.mark.parametrize("packing", sorted(PACKINGS))
@pytest.mark.parametrize("widths", sorted(WIDTHS))
def test_output_and_gradients_equal_the_reference(widths, packing):
    d, dv, h, group, group_bwd = WIDTHS[widths]
    ids = PACKINGS[packing]
    q, k, v, g = _operands(len(ids), h, d, dv, jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, want = _both(q, k, v, g, ids,
                          sg.Tiles(TILE, TILE, group, group_bwd))
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.abs(b).max()))


@pytest.mark.parametrize("rows,keys", [(256, 128), (128, 256), (256, 256)])
def test_tiles_that_are_not_square_or_longer_than_a_piece(rows, keys,
                                                          monkeypatch):
    """Rows and keys of a pair need not be equal, and a tile longer
    than `_PIECE` is scored piece by piece (128 here, 512 on the chip)."""
    monkeypatch.setattr(sg, "_PIECE", 128)
    ids = np.repeat([0, 1, 2], [200, 56, 256])
    q, k, v, g = _operands(512, 2, 72, 72, jnp.float32, seed=1)
    with jax.default_matmul_precision("highest"):
        got, want = _both(q, k, v, g, ids, sg.Tiles(rows, keys, 2, 1))
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.abs(b).max()))


@pytest.mark.parametrize("packing", ["edges_inside_tiles",
                                     "one_image_fills_the_row",
                                     "first_tile_wholly_masked_for_some_rows"])
def test_bfloat16_operands_round_as_the_library_s_kernels_do(packing):
    ids = PACKINGS[packing]
    q, k, v, g = _operands(len(ids), 4, 72, 72, jnp.bfloat16, seed=2)
    got, want = _both(q, k, v, g, ids, sg.Tiles(TILE, TILE, 4, 2))
    for a, b in zip(got, want):
        assert a.dtype == b.dtype == jnp.bfloat16
        assert _gap(a, b) < 1e-2


def test_the_two_kernels_alone_are_the_dense_formulas():
    """`forward` and `backward` called by hand ([H, T, .] operands, the
    tables from the ids) against the softmax written out: the
    log-sum-exp too, which `blockwise_mha` does not show."""
    ids = jnp.asarray(PACKINGS["edges_inside_tiles"], jnp.int32)
    t, h, d = len(ids), 2, 72
    q, k, v, g = (a[0].transpose(1, 0, 2)
                  for a in _operands(t, h, d, d, jnp.float32, seed=3))
    tiles = sg.Tiles(TILE, TILE, 2, 1)
    visit, plain = (att.segment_tiles(ids, TILE),
                    att.segment_interior(ids, TILE))
    same = ids[:, None] == ids[None, :]

    def dense(q, k, v):
        s = jnp.where(same[None], jnp.einsum("hqd,hkd->hqk", q, k), -jnp.inf)
        return (jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, -1), v),
                jax.nn.logsumexp(s, -1))

    with jax.default_matmul_precision("highest"):
        o, lse = sg.forward(q, k, v, ids, sg.pair_table(visit, plain), tiles,
                            interpret=True)
        want_o, want_lse = dense(q, k, v)
        assert o.shape == (h, t, d) and o.dtype == q.dtype
        assert lse.shape == (h, 1, t) and lse.dtype == jnp.float32
        assert _gap(o, want_o) < 2e-5
        np.testing.assert_allclose(lse[:, 0], want_lse, rtol=1e-5, atol=1e-5)
        di = (g * o).sum(-1)[:, None, :]
        got = sg.backward(q, k, v, g, lse, di, ids,
                          sg.pair_table(visit, plain, by_key=True), tiles,
                          interpret=True)
        want = jax.grad(lambda *a: (dense(*a)[0] * g).sum(),
                        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape and _gap(a, b) < 2e-5


@pytest.mark.parametrize("group,group_bwd", [(3, 1), (1, 3), (8, 8)])
def test_head_groups_that_do_not_divide_raise(group, group_bwd):
    q, k, v, g = _operands(256, 4, 72, 72, jnp.float32)
    with pytest.raises(ValueError, match="do not divide"):
        _both(q, k, v, g, np.zeros(256, np.int64),
              sg.Tiles(TILE, TILE, group, group_bwd))


def test_tiles_that_do_not_divide_raise():
    q = jnp.zeros((2, 256, 72), jnp.float32)
    table = (jnp.zeros(6, jnp.int32),) * 3
    with pytest.raises(ValueError, match="do not divide"):
        sg.forward(q, q, q, jnp.zeros(256, jnp.int32), table,
                   sg.Tiles(96, 128, 2, 2), interpret=True)
    with pytest.raises(ValueError, match="do not divide"):
        sg.backward(q, q, q, q, jnp.zeros((2, 1, 256)), jnp.zeros((2, 1, 256)),
                    jnp.zeros(256, jnp.int32), table, sg.Tiles(128, 96, 2, 2),
                    interpret=True)


# -- the pairs --------------------------------------------------------------------

CELL_GRIDS = [(96, 64), (64, 48), (48, 40), (36, 32)]


def _cell_ids():
    return jnp.asarray(np.repeat(np.arange(4), [r * c for r, c in CELL_GRIDS]),
                       jnp.int32)


def test_pair_table_of_the_cell_s_four_grids_by_hand():
    """12,288 patches in tiles of 1,024: the images end at 6,144 and
    9,216 — tile edges — and at 11,136, inside tile 10. Image 0 fills
    tiles 0-5, image 1 tiles 6-8, image 2 tile 9 and most of 10, image
    3 the rest of 10 and 11: 36 + 9 + 4 + 4 - 1 = 52 pairs visited of
    144, and every pair but those with tile 10 is interior: 47."""
    ids = _cell_ids()
    visit, plain = att.segment_tiles(ids, 1024), att.segment_interior(ids,
                                                                      1024)
    assert visit.shape == (12, 12)
    assert int(visit.sum()) == 52 and int((visit & plain).sum()) == 47
    row_of, key_of, flags = (np.asarray(a) for a in sg.pair_table(visit,
                                                                  plain))
    assert row_of.shape == key_of.shape == flags.shape == (144,)
    seen = flags & sg.VISITED != 0
    assert seen[:52].all() and not flags[52:].any()
    # a query tile's pairs after one another, in key order
    want = [(i, j) for i in range(12) for j in range(12)
            if np.asarray(visit)[i, j]]
    assert list(zip(row_of[:52], key_of[:52])) == want
    # past the count: the last visited pair again, so nothing is fetched
    assert (row_of[52:] == 11).all() and (key_of[52:] == 11).all()
    assert [j for i, j in want if i == 10] == [9, 10, 11]
    edges = {(i, j) for (i, j), f in zip(want, flags) if f & sg.EDGE}
    assert edges == {(9, 10), (10, 9), (10, 10), (10, 11), (11, 10)}
    firsts = [p for p, f in zip(want, flags) if f & sg.FIRST]
    lasts = [p for p, f in zip(want, flags) if f & sg.LAST]
    assert firsts == [(i, min(j for a, j in want if a == i))
                      for i in range(12)]
    assert lasts == [(i, max(j for a, j in want if a == i))
                     for i in range(12)]


def test_pair_table_by_key_is_the_transposed_walk():
    ids = _cell_ids()
    visit, plain = att.segment_tiles(ids, 1024, 512), att.segment_interior(
        ids, 1024, 512)
    assert visit.shape == (12, 24)
    row_of, key_of, flags = (np.asarray(a) for a in sg.pair_table(
        visit, plain, by_key=True))
    n = int(visit.sum())
    want = [(i, j) for j in range(24) for i in range(12)
            if np.asarray(visit)[i, j]]
    assert list(zip(row_of[:n], key_of[:n])) == want
    assert not flags[n:].any() and (flags[:n] & sg.VISITED).all()
    # FIRST / LAST are a KEY tile's here
    assert [p for p, f in zip(want, flags) if f & sg.FIRST] == [
        (min(i for i, b in want if b == j), j) for j in range(24)]
    assert [p for p, f in zip(want, flags) if f & sg.LAST] == [
        (max(i for i, b in want if b == j), j) for j in range(24)]


def test_every_pair_visited_leaves_no_spare_step():
    ids = jnp.zeros(512, jnp.int32)
    visit, plain = att.segment_tiles(ids, 128), att.segment_interior(ids, 128)
    assert bool(visit.all()) and bool(plain.all())
    row_of, key_of, flags = (np.asarray(a) for a in sg.pair_table(visit,
                                                                  plain))
    assert (flags & sg.VISITED).all() and not (flags & sg.EDGE).any()
    assert row_of.tolist() == [i for i in range(4) for _ in range(4)]
    assert key_of.tolist() == list(range(4)) * 4


def test_interior_is_one_id_on_both_sides_and_the_same():
    ids = jnp.asarray(np.repeat([0, 1, 1, 2], [4, 4, 2, 6]))  # tiles of 4
    assert att.segment_interior(ids, 4).tolist() == [
        [True, False, False, False], [False, True, False, False],
        [False, False, False, False], [False, False, False, True]]
    # ids that come back: tiles 0 and 2 hold the same single id
    ids = jnp.asarray(np.repeat([5, 3, 5], [4, 4, 4]))
    assert att.segment_interior(ids, 4).tolist() == [
        [True, False, True], [False, True, False], [True, False, True]]
    assert att.segment_tiles(ids, 4).tolist() == [
        [True, False, True], [False, True, False], [True, False, True]]


# -- the rule -----------------------------------------------------------------

@pytest.mark.parametrize("t,heads,d", [
    # kimivl-train-t4096's tower: 12,288 patches, 16 heads of 72
    (12288, 16, 72), (4096, 16, 72), (1536, 12, 64), (768, 3, 128),
    (32768, 16, 128)])
def test_rule_gives_tiles_that_divide_and_fit(t, heads, d):
    tiles = att.segment_tile("tpu", t, t, heads, d, d)
    assert tiles is not None
    assert tiles.rows == tiles.keys == next(b for b in att._SEG_TILES
                                            if t % b == 0)
    assert not (heads % tiles.heads or heads % tiles.heads_bwd)
    # the backward's dq: float32 scratch, and the block twice
    assert tiles.heads_bwd * t * att.lanes(d) * 8 <= att._SEG_DQ_BYTES


@pytest.mark.parametrize("why,args,kwargs", [
    ("the CPU", ("cpu", 1024, 1024, 16, 72, 72), {}),
    ("a GPU", ("gpu", 1024, 1024, 16, 72, 72), {}),
    ("the causal mask besides", ("tpu", 1024, 1024, 16, 72, 72),
     {"causal": True}),
    ("no head", ("tpu", 1024, 1024, 16, 0, 72), {}),
    ("no tile divides 384", ("tpu", 384, 384, 16, 72, 72), {}),
    ("no tile divides 1000", ("tpu", 1000, 1000, 16, 72, 72), {}),
    ("fewer queries than keys", ("tpu", 512, 1024, 16, 72, 72), {}),
    ("a query block at an offset", ("tpu", 1024, 1024, 16, 72, 72),
     {"q_offset": 1024}),
    ("an offset that is not a Python int", ("tpu", 1024, 1024, 16, 72, 72),
     {"k_offset": np.int32(0)}),
    ("a row whose dq outgrows VMEM for one head",
     ("tpu", 131072, 131072, 16, 72, 72), {}),
])
def test_rule_refuses(why, args, kwargs):
    assert att.segment_tile(*args, **kwargs) is None, why


def test_the_cpu_takes_the_reference_and_counts_it(pvar_clean):
    q, k, v, _ = _operands(256, 2, 72, 72, jnp.bfloat16)
    ids = jnp.asarray(PACKINGS["edges_inside_tiles"][:256], jnp.int32)[None]
    f = jax.jit(lambda q, k, v: att.attention(q, k, v, causal=False,
                                              segments=ids))
    text = f.lower(q, k, v).as_text()
    assert "tpu_custom_call" not in text and "pallas" not in text
    assert pvar.read("attn_segment_layers") == 1
    assert pvar.read("attn_reference_layers") == 1
    assert pvar.read("attn_segment_kernel_layers") == 0
    assert pvar.read("attn_blockwise_layers") == 0
    np.testing.assert_array_equal(
        np.asarray(f(q, k, v), np.float32),
        np.asarray(att.mha(q, k, v, causal=False, segments=ids), np.float32))


def test_the_rule_s_yes_takes_the_kernels_and_counts_it(monkeypatch,
                                                        pvar_clean):
    """The rule as on a TPU with 128-row tiles, the kernels in interpret
    mode: `attention` counts the layer under all three names."""
    rule = att.segment_tile
    monkeypatch.setattr(att, "_SEG_TILES", (TILE,))
    monkeypatch.setattr(att, "segment_tile",
                        lambda backend, *a, **k: rule("tpu", *a, **k))
    monkeypatch.setattr(att, "blockwise_mha", functools.partial(
        att.blockwise_mha, interpret=True))
    q, k, v, _ = _operands(256, 2, 72, 72, jnp.float32)
    ids = jnp.asarray(PACKINGS["edges_inside_tiles"][:256], jnp.int32)[None]
    with jax.default_matmul_precision("highest"):
        got = att.attention(q, k, v, causal=False, scale=1.0, segments=ids)
        want = att.mha(q, k, v, causal=False, scale=1.0, segments=ids)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert pvar.read("attn_segment_layers") == 1
    assert pvar.read("attn_segment_kernel_layers") == 1
    assert pvar.read("attn_blockwise_layers") == 1
    assert pvar.read("attn_reference_layers") == 0

