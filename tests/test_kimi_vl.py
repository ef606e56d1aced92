"""The fifth published model of models/transformer.py at toy widths on
the CPU: a native-resolution vision tower (models/vision.py: packed
images, block-diagonal two-way attention, 2-D RoPE, a bicubically
resized position table, a 2 x 2 merge, a projector) feeding a
latent-attention decoder without a query latent — the program against
hand-written cases and against the float32 reference
(benchmark/reference/kimivl_decoder.py)."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from benchmark.reference import kimivl_decoder as ref  # noqa: E402
from ompi_tpu.core import pvar  # noqa: E402
from ompi_tpu.models import remat  # noqa: E402
from ompi_tpu.models import transformer as tfm  # noqa: E402
from ompi_tpu.models import vision  # noqa: E402
from ompi_tpu.ops import attention as att  # noqa: E402

AX = tfm.Axes()
VC = vision.VisionConfig(d_model=32, n_layers=2, n_heads=2, d_ff=48,
                         patch_dim=12, pos_grid=(8, 8), merge=2)
GRIDS = ((4, 6), (2, 4), (4, 2))
TEXT, T = 18, 64  # 6 + 2 + 2 merged rows + 3 x 18 text positions


def config(**kw):
    base = dict(
        vocab=64, d_model=32, n_layers=2, n_heads=2, d_ff=64, max_seq=64,
        first_dense=1, moe_d_ff=16, n_experts=8, top_k=2,
        norm_topk_prob=True, router_score="sigmoid", router_bias=True,
        routed_scale=2.446, n_shared_experts=2, mlp_act="silu",
        mlp_gated=True, norm="rmsnorm", pos="rope", rope_theta=8e5,
        tie_head=False, attn="mla", q_lora_rank=0, kv_lora_rank=8,
        qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8, rope_interleave=True,
        dtype=jnp.float32, vision=VC)
    base.update(kw)
    return tfm.Config(**base)


SPEC = ref.Spec(n_heads=2, qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8,
                top_k=2, images=GRIDS, text_run=TEXT, vit_heads=2)


def places(grids=GRIDS, text=TEXT):
    out, at = [], 0
    for r, c in grids:
        out.append(np.arange(at, at + r * c // 4))
        at += r * c // 4 + text
    return out


def make_batch(seed=0, grids=GRIDS, where=None, seq=T):
    rng = np.random.default_rng(seed)
    where = places(grids) if where is None else where
    is_image = np.zeros(seq, bool)
    is_image[np.concatenate(where).astype(int)] = True
    tokens = rng.integers(0, 64, (1, seq)).astype(np.int32)
    labels = np.roll(tokens, -1, 1)
    labels[0, np.roll(is_image, -1)] = -1
    labels[0, -1] = -1
    patches = rng.standard_normal(
        (sum(r * c for r, c in grids), VC.patch_dim)).astype(np.float32)
    batch = dict(vision.pack(grids, where, VC), tokens=tokens,
                 patches=patches)
    return {k: jnp.asarray(v) for k, v in batch.items()}, jnp.asarray(labels)


@pytest.fixture(scope="module")
def params():
    return jax.tree.map(jnp.asarray, tfm.init_params(
        np.random.default_rng(0), config()))


def rows_of(params, batch):
    return vision.tower(params["vision"], batch, VC, jnp.float32)


# -- hand-written cases -----------------------------------------------------------

def test_rope2d_turns_pairs_by_column_then_row():
    vc = vision.VisionConfig(d_model=16, n_heads=2)  # heads of 8: 2 freqs
    pos = jnp.asarray([[3, 5]], jnp.int32)  # row 3, column 5
    ang = np.asarray(vision.rope2d_angles(pos, vc))[0]
    f = [1.0, 10000.0 ** (-4 / 8)]
    np.testing.assert_allclose(ang, [5 * f[0], 3 * f[0], 5 * f[1], 3 * f[1]],
                               rtol=1e-6)
    x = jnp.arange(8, dtype=jnp.float32).reshape(1, 1, 8) + 1
    got = np.asarray(vision.rope2d(x, jnp.asarray(ang)[None]))[0, 0]
    for p in range(4):
        a, b = 2 * p + 1, 2 * p + 2
        c, s = np.cos(ang[p]), np.sin(ang[p])
        np.testing.assert_allclose(got[2 * p:2 * p + 2],
                                   [a * c - b * s, b * c + a * s], rtol=1e-5)


def test_rope2d_is_the_reference_s():
    rng = np.random.default_rng(1)
    r, c = 4, 6
    x = jnp.asarray(rng.standard_normal((r * c, 2, 16)), jnp.float32)
    pos = jnp.asarray(np.stack(np.divmod(np.arange(r * c), c), 1), jnp.int32)
    vc = vision.VisionConfig(d_model=32, n_heads=2)
    mine = vision.rope2d(x, vision.rope2d_angles(pos, vc))
    theirs = ref.rope_2d(jnp.moveaxis(x, 1, 0), r, c, 1e4)
    np.testing.assert_allclose(mine, jnp.moveaxis(theirs, 0, 1), atol=1e-5)


@pytest.mark.parametrize("rows,cols", [(8, 8), (4, 6), (16, 12), (2, 2)])
def test_bicubic_table(rows, cols):
    """The taps resize the table as the plain separable resize does; a
    grid of the table's own size reads the table itself, a constant
    table stays constant."""
    rng = np.random.default_rng(2)
    table = jnp.asarray(rng.standard_normal((8, 8, 5)), jnp.float32)
    index, weight = vision.bicubic_taps(rows, cols, (8, 8))
    got = vision.position_rows(table, jnp.asarray(index),
                               jnp.asarray(weight))
    with jax.default_matmul_precision("highest"):
        want = ref.position_rows(table, rows, cols)
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(weight.sum(1), 1.0, atol=1e-6)
    if (rows, cols) == (8, 8):
        np.testing.assert_allclose(got, table.reshape(64, 5), atol=1e-6)


def test_bicubic_taps_by_hand():
    """2 samples from 4 (scale 2): output 0 sits at source 0.5, its
    taps -1, 0, 1, 2 weigh (-3/32, 19/32, 19/32, -3/32) for a = -0.75
    and the tap past the edge folds onto the edge's."""
    index, weight = vision._axis_taps(2, 4)
    assert index.tolist() == [[0, 0, 1, 2], [1, 2, 3, 3]]
    np.testing.assert_allclose(
        weight, [[-3 / 32, 19 / 32, 19 / 32, -3 / 32]] * 2, atol=1e-12)


def test_merge_takes_each_neighbourhood_in_raster_order():
    packing = vision.pack([(4, 6), (2, 2)], [np.arange(6), np.arange(6, 7)],
                          VC)
    assert packing["merge_index"].tolist() == [
        [0, 1, 6, 7], [2, 3, 8, 9], [4, 5, 10, 11],
        [12, 13, 18, 19], [14, 15, 20, 21], [16, 17, 22, 23],
        [24, 25, 26, 27]]
    assert packing["image_ids"].tolist() == [0] * 24 + [1] * 4
    assert packing["patch_pos"][7].tolist() == [1, 1]
    assert packing["patch_pos"][26].tolist() == [1, 0]
    with pytest.raises(ValueError, match="does not merge"):
        vision.pack([(3, 4)], [np.arange(3)], VC)


def test_segment_tiles_by_hand():
    ids = jnp.asarray(np.repeat([0, 1, 2], [6, 3, 7]))  # tiles of 4
    assert att.segment_tiles(ids, 4).tolist() == [
        [True, True, False, False], [True, True, True, False],
        [False, True, True, True], [False, False, True, True]]


# -- the tower ------------------------------------------------------------------------

def test_tower_on_the_packed_row_is_the_tower_image_by_image(params):
    batch, _ = make_batch()
    packed = np.asarray(rows_of(params, batch))
    at, row = 0, 0
    for r, c in GRIDS:
        alone = dict(vision.pack([(r, c)], [np.arange(r * c // 4)], VC),
                     patches=batch["patches"][at:at + r * c])
        got = rows_of(params, {k: jnp.asarray(v) for k, v in alone.items()})
        np.testing.assert_allclose(got, packed[row:row + r * c // 4],
                                   atol=2e-5)
        at, row = at + r * c, row + r * c // 4


def test_moving_an_image_in_the_row_moves_its_rows_and_nothing_else(params):
    batch, _ = make_batch()
    rows = np.asarray(rows_of(params, batch))
    order = (2, 0, 1)
    cut = np.cumsum([0] + [r * c for r, c in GRIDS])
    moved = dict(vision.pack([GRIDS[i] for i in order],
                             [places()[i] for i in order], VC),
                 patches=np.concatenate(
                     [np.asarray(batch["patches"][cut[i]:cut[i + 1]])
                      for i in order]))
    got = np.asarray(rows_of(params, {k: jnp.asarray(v)
                                      for k, v in moved.items()}))
    mcut = np.cumsum([0] + [r * c // 4 for r, c in GRIDS])
    want = np.concatenate([rows[mcut[i]:mcut[i + 1]] for i in order])
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_attention_leaks_nothing_across_images(params):
    batch, _ = make_batch()
    rows = np.asarray(rows_of(params, batch))
    first = GRIDS[0][0] * GRIDS[0][1]
    other = dict(batch, patches=batch["patches"].at[:first].add(1.0))
    got = np.asarray(rows_of(params, other))
    n0 = first // 4
    assert not np.array_equal(got[:n0], rows[:n0])
    assert np.array_equal(got[n0:], rows[n0:])  # bit for bit


def test_mha_with_segments_is_attention_image_by_image():
    rng = np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 12, 2, 8)), jnp.float32)
               for _ in range(3))
    ids = jnp.asarray([[0] * 5 + [1] * 7])
    got = att.mha(q, k, v, causal=False, segments=ids)
    for a, b in ((0, 5), (5, 12)):
        want = att.mha(q[:, a:b], k[:, a:b], v[:, a:b], causal=False)
        np.testing.assert_allclose(got[:, a:b], want, atol=1e-6)


@pytest.mark.parametrize("d,dv,masks", [
    (72, 72, "segments"), (192, 128, "causal"), (24, 8, "segments"),
    (128, 128, "segments")])
def test_blockwise_kernels_pad_any_width_and_equal_mha(d, dv, masks):
    """Interpret mode: heads of any width (padded to the lanes), q and
    v of different widths, the segment mask as data — output and
    gradients are att.mha's."""
    rng = np.random.default_rng(4)
    t, h = 256, 2
    q, k = (jnp.asarray(rng.standard_normal((1, t, h, d)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.standard_normal((1, t, h, dv)), jnp.float32)
    ids = None if masks == "causal" else jnp.asarray(
        np.repeat([0, 1, 2], [100, 28, 128])[None])

    def loss(fn, *a):
        return (fn(*a) ** 2).sum()

    blockwise = lambda *a: att.blockwise_mha(  # noqa: E731
        *a, 128, interpret=True, segments=ids)
    plain = lambda *a: att.mha(*a, causal=ids is None,  # noqa: E731
                               segments=ids)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(blockwise(q, k, v), plain(q, k, v),
                                   atol=2e-5)
        got = jax.grad(lambda *a: loss(blockwise, *a), (0, 1, 2))(q, k, v)
        want = jax.grad(lambda *a: loss(plain, *a), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-4 * float(jnp.abs(b).max()))


@pytest.mark.parametrize("causal,segmented,want", [
    (True, False, "library"), (False, True, "segment"), (False, False, None),
    (True, True, None)])
def test_rule_takes_one_mask_or_the_other(causal, segmented, want):
    """One rule per mask: the library's kernels under the causal mask,
    the repo's own under a segment mask, the reference under neither
    or both."""
    def taken(backend):  # as `att.attention` asks them
        if segmented:
            return att.segment_tile(backend, 4096, 4096, 16, 72, 72,
                                    causal=causal) and "segment"
        return att.blockwise_tile(backend, 4096, 4096, 72, causal) and \
            "library"

    assert taken("tpu") == want
    assert taken("cpu") is None
    assert att.blockwise_tile("tpu", 4096, 4096, 72, True) == 1024
    assert att.segment_tile("tpu", 4096, 4096, 16, 72, 72)[:2] == (1024, 1024)


# -- the decoder ---------------------------------------------------------------------

def test_plain_q_latent_attention_is_mha_at_192_over_128_shape():
    """q_lora_rank 0: q is one product of x; q and v differ in width
    and the layer goes through the model's one entry all the same."""
    cfg = config(vision=None, n_layers=1, first_dense=1)
    lp = jax.tree.map(jnp.asarray, tfm.init_params(
        np.random.default_rng(5), cfg))["layers"][0]
    assert "wq" in lp and "wq_a" not in lp and "q_a_norm" not in lp
    rng = np.random.default_rng(6)
    h = jnp.asarray(rng.standard_normal((1, 16, 32)), jnp.float32)
    x = tfm._norm(h, lp["ln1"], cfg)
    q, k, v, c_q = tfm._mla_project(lp, x, cfg, jnp.arange(16))
    assert c_q is None and q.shape[-1] == 12 and v.shape[-1] == 8
    want = h + att.mha(q, k, v, causal=True).reshape(1, 16, -1) @ lp["wo"]
    before = {n: pvar.read(n) for n in ("attn_mla_plain_q_layers",
                                        "attn_reference_layers")}
    got = h + tfm._mla_attention(lp, x, cfg, None, None)
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert all(pvar.read(n) == v + 1 for n, v in before.items())


def test_indexer_without_a_query_latent_is_refused():
    cfg = config(vision=None, index_heads=2, index_dim=8, index_topk=4)
    with pytest.raises(NotImplementedError, match="query latent"):
        params = tfm.init_params(np.random.default_rng(0), cfg)
        tok = jnp.zeros((1, 16), jnp.int32)
        tfm.forward_local(jax.tree.map(jnp.asarray, params), tok, cfg, AX)


@pytest.mark.parametrize("axis", ["tp", "sp", "pp"])
def test_tower_under_an_axis_is_refused(axis):
    with pytest.raises(NotImplementedError, match="vision tower"):
        tfm._check_supported(config(), tfm.Axes(**{axis: "x"}), False, 0)


def test_batch_and_config_must_agree(params):
    batch, labels = make_batch()
    with pytest.raises(ValueError, match="vision tower"):
        tfm.loss_local(params, batch["tokens"], labels, config(), AX)


# -- the step against the float32 reference ---------------------------------------

def test_loss_and_gradients_are_the_reference_s(params):
    batch, labels = make_batch()
    cfg = config()
    (nll, count), grads = jax.value_and_grad(
        lambda p: tfm.loss_local(p, batch, labels, cfg, AX),
        has_aux=True)(params)
    want, ref_grads = jax.value_and_grad(
        lambda p: ref.loss(p, batch, labels, SPEC))(params)
    assert int(count) == 3 * TEXT
    np.testing.assert_allclose(nll / count, want, rtol=2e-5)
    flat = jax.tree_util.tree_leaves_with_path(ref_grads)
    mine = jax.tree.leaves(grads)
    for (path, r), g in zip(flat, mine):
        scale = float(jnp.abs(r).max()) + 1e-9
        np.testing.assert_allclose(
            g / count, r, atol=2e-4 * scale,
            err_msg=jax.tree_util.keystr(path))


def test_vision_rows_are_the_reference_s(params):
    batch, _ = make_batch()
    np.testing.assert_allclose(rows_of(params, batch),
                               ref.vision_rows(params, batch, SPEC),
                               atol=2e-5)


def test_gradient_reaches_every_tower_leaf(params):
    batch, labels = make_batch()
    grads = jax.grad(lambda p: tfm.loss_local(
        p, batch, labels, config(), AX)[0])(params)
    for path, g in jax.tree_util.tree_leaves_with_path(grads["vision"]):
        assert float(jnp.abs(g).max()) > 0, jax.tree_util.keystr(path)


def test_tower_gets_no_gradient_where_no_image_is_placed(params):
    batch, labels = make_batch()
    none = dict(batch, merge_index=jnp.zeros((0, 4), jnp.int32),
                image_positions=jnp.zeros((0,), jnp.int32))
    grads = jax.grad(lambda p: tfm.loss_local(
        p, none, labels, config(), AX)[0])(params)
    assert all(float(jnp.abs(g).max()) == 0
               for g in jax.tree.leaves(grads["vision"]))
    assert float(jnp.abs(grads["embed"]).max()) > 0


def test_loss_ignores_image_positions(params):
    batch, labels = make_batch()
    cfg = config()
    where = np.concatenate(places())
    nll, count = tfm.loss_local(params, batch, labels, cfg, AX)
    assert int(count) == 3 * TEXT and np.all(np.asarray(labels)[0, -1] == -1)
    # the id at an image position is never read
    other = dict(batch, tokens=batch["tokens"].at[0, where].set(7))
    assert float(tfm.loss_local(params, other, labels, cfg, AX)[0]) \
        == float(nll)
    # a label at an image position's predecessor would be: it is -1
    lab = np.asarray(labels)[0]
    assert np.all(lab[where - 1][where > 0] == -1)
    # and the embedding rows under the images get no gradient
    marked = dict(batch, tokens=jnp.where(
        batch["tokens"] == 63, 0, batch["tokens"]).at[0, where].set(63))
    g = jax.grad(lambda p: tfm.loss_local(p, marked, labels, cfg, AX)[0])(
        params)["embed"]
    assert float(jnp.abs(g[63]).max()) == 0 < float(jnp.abs(g[:63]).max())


def test_another_mix_of_grids_compiles_nothing(params):
    """Packing is data: the same P and the same number of image
    positions with other grids runs the executable it has."""
    step = jax.jit(tfm.make_train_step(config(remat=True), AX,
                                       tfm.param_specs(config(), AX)))
    batch, labels = make_batch()
    step(params, batch, labels)
    assert step._cache_size() == 1
    grids = ((2, 4), (4, 4), (4, 4))  # 8 + 16 + 16 = 40 patches again
    where = [np.arange(0, 2), np.arange(20, 24), np.arange(42, 46)]
    other, other_labels = make_batch(1, grids, where)
    _, loss = step(params, other, other_labels)
    assert step._cache_size() == 1 and np.isfinite(float(loss))
    spec = SPEC._replace(images=grids, text_run=0)
    want = ref.loss(params, dict(other, tokens=jnp.concatenate(
        [other["tokens"][:, np.concatenate(where)],
         jnp.delete(other["tokens"], np.concatenate(where), axis=1)], 1)),
        jnp.full((1, T), -1).at[0, -1].set(3), spec)
    assert np.isfinite(float(want))  # the reference lays images first


def test_step_lowered_for_the_tpu_holds_both_kinds_of_kernel(monkeypatch,
                                                             pvar_clean):
    """The rules as on a TPU (128-row tiles) and the step lowered for
    it, here without one: the tower's attention is the repo's own
    kernels and no `splash_mha_*segmented*`, the decoder's causal
    attention the library's, as before there was a second rule."""
    rules = att.blockwise_tile, att.segment_tile
    monkeypatch.setattr(att, "_TILES", (128,))
    monkeypatch.setattr(att, "_SEG_TILES", (128,))
    monkeypatch.setattr(att, "blockwise_tile",
                        lambda backend, *a, **k: rules[0]("tpu", *a, **k))
    monkeypatch.setattr(att, "segment_tile",
                        lambda backend, *a, **k: rules[1]("tpu", *a, **k))
    grids = ((16, 12), (8, 16), (4, 16))  # 384 patches: edges at 192, 320
    where = [np.arange(0, 48), np.arange(98, 130), np.arange(180, 196)]
    cfg = config(max_seq=256, remat=True, dtype=jnp.bfloat16)
    batch, labels = make_batch(0, grids, where, seq=256)
    shapes = jax.eval_shape(lambda: tfm.init_params(
        np.random.default_rng(0), cfg))
    text = jax.jit(tfm.make_train_step(
        cfg, AX, tfm.param_specs(cfg, AX))).trace(
            shapes, batch, labels).lower(
                lowering_platforms=("tpu",)).as_text()
    assert "splash_mha_fwd" in text and "splash_mha_dkv" in text
    assert "seg_fwd" in text and "seg_bwd" in text
    assert not re.search(r"splash_mha_\w*segmented", text)
    layers = VC.n_layers + cfg.n_layers
    assert pvar.read("attn_blockwise_layers") == layers
    assert pvar.read("attn_segment_layers") == VC.n_layers
    assert pvar.read("attn_segment_kernel_layers") == VC.n_layers
    assert pvar.read("attn_reference_layers") == 0


# -- recomputation: the tower's blocks are a layer kind of the rule -------------------

def test_recomputed_step_is_the_plain_step_and_counts_its_blocks(params,
                                                                 pvar_clean):
    batch, labels = make_batch()
    out = {}
    for remat in (False, True):
        cfg = config(remat=remat)
        out[remat] = jax.value_and_grad(lambda p: tfm.loss_local(
            p, batch, labels, cfg, AX)[0])(params)
    assert pvar.read("remat_whole_applications") == VC.n_layers + 2
    assert pvar.read("attn_segment_layers") == 2 * VC.n_layers
    assert pvar.read("vision_patches") == 2 * 40
    assert pvar.read("vision_image_positions") == 2 * 10
    np.testing.assert_allclose(out[True][0], out[False][0], rtol=1e-6)
    for a, b in zip(jax.tree.leaves(out[True][1]),
                    jax.tree.leaves(out[False][1])):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(
            jnp.abs(b).max() + 1e-9))


def test_rule_reckons_the_tower_s_applications():
    cfg = config(remat=True)
    kinds = tfm._application_kinds(cfg)
    assert kinds == [tfm.VIT] * 2 + [False, True]
    assert tfm._application_kinds(config(vision=None)) == [False, True]
    with_tower = remat.whole_step_peak(*tfm.step_costs(cfg, 1, T, 0, 40))
    without = remat.whole_step_peak(*tfm.step_costs(
        config(vision=None, remat=True), 1, T, 0))
    assert with_tower >= without + VC.n_layers * 40 * VC.d_model * 4
    sizes = vision.application(VC, 40, 4).sizes  # float32 activations
    assert sizes[att.QKV] == 3 * 40 * VC.n_heads * 128 * 4  # lanes of 128
    order = remat.remat_order(tfm.step_costs(cfg, 1, T, patches=40)[0])
    held = dict(order)
    alone = dict(remat.remat_order(tfm.step_costs(
        config(vision=None, remat=True), 1, T)[0]))
    for name, size in sizes.items():
        assert held[name] == alone.get(name, 0) + VC.n_layers * size
    costs = tfm.step_costs(cfg, 1, T, 10 ** 6, 40)
    assert remat.remat_keep(*costs, None) == ()
    assert remat.remat_keep(*costs, 10 ** 12) == tuple(n for n, _ in order)


def test_kept_names_change_nothing_but_what_is_recomputed(params,
                                                          monkeypatch):
    batch, labels = make_batch()
    cfg = config(remat=True)
    fn = lambda p: tfm.loss_local(p, batch, labels, cfg, AX)[0]  # noqa: E731
    whole = jax.value_and_grad(fn)(params)
    monkeypatch.setattr(remat, "_memory_limit", lambda: 10 ** 12)
    before = pvar.read("remat_kept_applications")
    kept = jax.value_and_grad(fn)(params)
    assert pvar.read("remat_kept_applications") == before + VC.n_layers + 2
    np.testing.assert_allclose(kept[0], whole[0], rtol=1e-6)
    for a, b in zip(jax.tree.leaves(kept[1]), jax.tree.leaves(whole[1])):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(
            jnp.abs(b).max() + 1e-9))


def test_probe_reads_the_packing(pvar_clean):
    batch, _ = make_batch()
    stats = vision.vision_stats(batch)
    assert stats["patches"] == 40 and stats["images"] == 3
    assert stats["diag_pairs"] == 24 ** 2 + 8 ** 2 + 8 ** 2
    assert stats["diag_share"] == stats["diag_pairs"] / 1600
    assert pvar.read("vision_diag_pairs") == stats["diag_pairs"]
    assert pvar.read("vision_row_pairs") == 1600


def test_route_probe_takes_the_dict(params):
    batch, _ = make_batch()
    counts = tfm.route_counts(params, batch, config())
    assert counts.shape == (1, 8) and int(counts.sum()) == T * 2


def test_specs_and_init_agree_on_the_tree():
    cfg = config()
    params = tfm.init_params(np.random.default_rng(0), cfg)
    specs = tfm.param_specs(cfg, AX)
    assert jax.tree.structure(params) == jax.tree.structure(
        specs, is_leaf=lambda s: not isinstance(s, (dict, list)))
