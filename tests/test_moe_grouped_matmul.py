"""The grouped matmul of the drop-free expert path (ops/moe.py:
`grouped_matmul`, the rule `grouped_tiles`; the Pallas kernels in
ops/grouped_matmul.py) against `lax.ragged_dot`, here on the CPU with
the kernels in interpret mode at toy sizes and 128-row tiles.

Tolerances. With float32 operands both sides accumulate in float32 and
differ by the order of the sums: 1e-5 of the result's norm (measured
3e-7). With bfloat16 operands both round one float32 sum to bfloat16:
they differ where the two sums fall on either side of a rounding
boundary, by one bfloat16 step there — 1% of the norm at most (measured
0.1-0.2%). A wrong group edge, a tile visited for the wrong group or a
missing row misses these by orders of magnitude.
"""

import functools
import json
import os
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from ompi_tpu.core import pvar  # noqa: E402
from ompi_tpu.models import transformer as tfm  # noqa: E402
from ompi_tpu.ops import attention as att  # noqa: E402
from ompi_tpu.ops import moe  # noqa: E402

M, K, N, E = 512, 256, 128, 8

#: rows a group, [E]; they sum to M unless the case is about the rest
GROUPS = {
    "uniform": [64] * 8,
    "skewed_8x": [256, 32, 40, 32, 48, 32, 40, 32],
    "empty_groups": [0, 200, 0, 0, 212, 100, 0, 0],
    "all_in_one": [0, 0, 0, 512, 0, 0, 0, 0],
    "edges_mid_tile": [1, 127, 129, 60, 3, 100, 91, 1],
    "rows_past_the_last_group": [100, 0, 150, 30, 0, 0, 70, 33],
}
#: edges worked a whole tile at a time, the weights' gradient in one
#: block; and 128 rows at a time, that gradient in four blocks
TILES = {
    "whole": moe.GroupedTiles(fwd=(128, 128, N), drows=(128, 128, K),
                              dw=(128, 128, K, N)),
    "blocked": moe.GroupedTiles(fwd=(256, 128, 128), drows=(512, 128, 128),
                                dw=(256, 128, 128, 128)),
}


def _operands(dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (M, K), jnp.float32).astype(dtype),
            jax.random.normal(ks[1], (E, K, N), jnp.float32).astype(dtype),
            jax.random.normal(ks[2], (M, N), jnp.float32))


def _value_and_grads(product, rows, w, counts, g):
    def loss(rows, w):
        return jnp.sum(product(rows, w, counts).astype(jnp.float32) * g)
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(rows, w)


def _f32(x):
    return np.asarray(x.astype(jnp.float32))


def _gap(got, want):
    return np.linalg.norm(_f32(got) - _f32(want)) / np.linalg.norm(_f32(want))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("tiles", sorted(TILES))
@pytest.mark.parametrize("groups", sorted(GROUPS))
def test_product_and_both_gradients_equal_ragged_dot(groups, tiles, dtype):
    rows, w, g = _operands(dtype)
    counts = jnp.asarray(GROUPS[groups], jnp.int32)
    kernels = moe._grouped_kernels(TILES[tiles], True)
    limit = 1e-2 if dtype == jnp.bfloat16 else 1e-5
    out = jax.jit(kernels)(rows, w, counts)
    want = lax.ragged_dot(rows, w, counts)
    assert out.shape == want.shape and out.dtype == want.dtype
    assert _gap(out, want) < limit
    # rows of no group are zero, whatever the tile held before
    np.testing.assert_array_equal(_f32(out)[sum(GROUPS[groups]):], 0.0)
    _, (d_rows, d_w) = _value_and_grads(kernels, rows, w, counts, g)
    _, (r_rows, r_w) = _value_and_grads(lax.ragged_dot, rows, w, counts, g)
    assert d_rows.dtype == rows.dtype and d_w.dtype == w.dtype
    assert _gap(d_rows, r_rows) < limit
    assert _gap(d_w, r_w) < limit
    # an expert without rows has a gradient of exactly zero
    empty = np.array(GROUPS[groups]) == 0
    np.testing.assert_array_equal(_f32(d_w)[empty], 0.0)
    assert (np.abs(_f32(d_w)[~empty]).max((1, 2)) > 0).all()


@pytest.mark.parametrize("width,run", [(256, 128), (384, 256), (640, 512)])
@pytest.mark.parametrize("kernel", ["gmm", "gmm_nt", "tgmm"])
def test_a_whole_tiles_product_in_runs_of_columns_is_the_product(kernel,
                                                                 width, run):
    """The kernels compute a whole tile's product in runs of columns
    (a loop: shorter code); a width the run does not divide takes the
    widest run of whole lanes that does."""
    from ompi_tpu.ops import grouped_matmul as gk

    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    counts = jnp.asarray(GROUPS["edges_mid_tile"], jnp.int32)
    rows = jax.random.normal(ks[0], (M, 128), jnp.float32)
    if kernel == "tgmm":
        cols = jax.random.normal(ks[1], (M, width), jnp.float32)
        got = gk.tgmm(rows, cols, counts, (128, 128, 128, width), run=run,
                      interpret=True)
        want = jax.vjp(lambda w: lax.ragged_dot(rows, w, counts),
                       jnp.zeros((E, 128, width)))[1](cols)[0]
    else:
        nt = kernel == "gmm_nt"
        w = jax.random.normal(ks[1], (E, width, 128) if nt
                              else (E, 128, width), jnp.float32)
        got = gk.gmm(rows, w, counts, (128, 128, width), transpose_rhs=nt,
                     run=run, interpret=True)
        want = lax.ragged_dot(rows, w.swapaxes(1, 2) if nt else w, counts)
    assert _gap(got, want) < 1e-5


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_an_empty_experts_gradient_is_zero_not_what_memory_held(dtype):
    """Twice through the same compiled backward with other data: the
    second call's empty experts must not show the first call's."""
    kernels = moe._grouped_kernels(TILES["whole"], True)
    first = jnp.asarray(GROUPS["uniform"], jnp.int32)
    second = jnp.asarray(GROUPS["empty_groups"], jnp.int32)
    rows, w, g = _operands(dtype, seed=1)
    _value_and_grads(kernels, rows, w, first, g)
    _, (_, d_w) = _value_and_grads(kernels, rows, w, second, g)
    empty = np.array(GROUPS["empty_groups"]) == 0
    assert empty.sum() == 5
    np.testing.assert_array_equal(_f32(d_w)[empty], 0.0)


# -- the rule -----------------------------------------------------------------

@pytest.mark.parametrize("m,k,n,dtype", [
    (32768, 2048, 1024, jnp.bfloat16),   # olmoe-train-t4096, w1 / w3
    (32768, 1024, 2048, jnp.bfloat16),   # ... and w2
    (32768, 2048, 1024, jnp.float32),
    (1024, 128, 128, jnp.bfloat16),
    (4096, 384, 640, jnp.bfloat16),
])
def test_rule_gives_tiles_that_divide_the_three_products(m, k, n, dtype):
    tiles = moe.grouped_tiles("tpu", m, k, n, dtype)
    (tm, sub, tn), (tm_r, sub_r, tn_r), (tm_w, sub_w, tk_w, tn_w) = tiles
    assert not (m % tm or tm % sub or n % tn)          # [m, k] x [E, k, n]
    assert not (m % tm_r or tm_r % sub_r or k % tn_r)  # [m, n] x [E, k, n]^T
    assert not (m % tm_w or tm_w % sub_w or k % tk_w   # [m, k]^T x [m, n]
                or n % tn_w)
    assert all(t % 128 == 0 for ts in tiles for t in ts)
    # the answer for w1's shape is the answer for w2's
    assert moe.grouped_tiles("tpu", m, n, k, dtype) is not None


@pytest.mark.parametrize("why,args", [
    ("the CPU", ("cpu", 32768, 2048, 1024, jnp.bfloat16)),
    ("a GPU", ("gpu", 32768, 2048, 1024, jnp.bfloat16)),
    ("K of 2000", ("tpu", 32768, 2000, 1024, jnp.bfloat16)),
    ("K of 64", ("tpu", 32768, 64, 1024, jnp.bfloat16)),
    ("N of 1000", ("tpu", 32768, 2048, 1000, jnp.bfloat16)),
    ("N of 32, as the toy models", ("tpu", 512, 256, 32, jnp.bfloat16)),
    ("rows no row tile divides", ("tpu", 32768 + 128, 2048, 1024,
                                  jnp.bfloat16)),
    ("fewer rows than a row tile", ("tpu", 64, 2048, 1024, jnp.bfloat16)),
    ("int8 operands", ("tpu", 32768, 2048, 1024, jnp.int8)),
    ("int32 operands", ("tpu", 32768, 2048, 1024, jnp.int32)),
    ("float16 operands", ("tpu", 32768, 2048, 1024, jnp.float16)),
    ("operands of two types", ("tpu", 32768, 2048, 1024, None)),
    ("a matrix whose whole-K block outgrows VMEM",
     ("tpu", 32768, 7168, 28672, jnp.bfloat16)),
])
def test_rule_refuses(why, args):
    assert moe.grouped_tiles(*args) is None, why


def test_the_rule_never_sees_the_group_sizes():
    """Traced counts, a traced product: the choice is made of static
    shapes alone."""
    rows, w, _ = _operands(jnp.bfloat16)
    f = jax.jit(lambda rows, w, counts: moe.grouped_matmul(rows, w, counts))
    out = f(rows, w, jnp.asarray(GROUPS["uniform"], jnp.int32))
    assert out.shape == (M, N)


# -- the model with the kernels put in by hand --------------------------------

@pytest.fixture
def kernels_on_cpu(monkeypatch):
    """The rule as on a TPU with 128-row tiles, the kernels in interpret
    mode: everything else is the program's own path."""
    rule = moe.grouped_tiles
    monkeypatch.setattr(moe, "_TM", 128)
    monkeypatch.setattr(moe, "grouped_tiles",
                        lambda backend, *a: rule("tpu", *a))
    monkeypatch.setattr(moe, "grouped_matmul", functools.partial(
        moe.grouped_matmul, interpret=True))


OLMOE = dict(vocab=128, d_model=128, n_layers=2, n_heads=1, d_ff=128,
             max_seq=128, moe_every=1, n_experts=8, top_k=2, mlp_act="silu",
             mlp_gated=True, norm="rmsnorm", pos="rope", qk_norm=True,
             tie_head=False, router_aux_weight=0.01, router_z_weight=0.001)
UNGATED = dict(OLMOE, mlp_act="relu", mlp_gated=False)
MODELS = {"gated_silu": OLMOE, "ungated_relu": UNGATED}
AX = tfm.Axes()


def _toy(model, dtype, b=2, t=128):
    cfg = tfm.Config(dtype=dtype, **MODELS[model])
    params = tfm.init_params(np.random.default_rng(0), cfg)
    tok = np.random.default_rng(1).integers(0, cfg.vocab, (b, t))
    tok = jnp.asarray(tok, jnp.int32)
    return cfg, params, tok, jnp.roll(tok, -1, axis=1)


def _step(cfg):
    return jax.jit(tfm.make_train_step(cfg, AX, tfm.param_specs(cfg, AX),
                                       lr=1.0))


def _grad_norms(params, new_params):
    """Per leaf, the norm of one plain SGD step at lr 1: the
    gradient's."""
    return np.array([np.linalg.norm(np.asarray(a, np.float64)
                                    - np.asarray(b, np.float64))
                     for a, b in zip(jax.tree.leaves(params),
                                     jax.tree.leaves(new_params))])


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("model", sorted(MODELS))
def test_train_step_with_the_kernels_equals_the_ragged_dot_step(
        model, dtype, request, pvar_clean):
    """Loss and every leaf's gradient norm. In float32 the two steps
    differ by the order of float32 sums (measured: loss 1e-7, norms
    2e-6); in bfloat16 each is a different rounding of the same step (a
    toy leaf's norm moves by up to 1.5%, measured; top-2 of 8 re-routes
    a token or two)."""
    cfg, params, tok, lab = _toy(model, dtype)
    new_w, loss_w = _step(cfg)(params, tok, lab)
    assert pvar.read("moe_ragged_dot_layers") == cfg.n_layers
    assert pvar.read("moe_grouped_kernel_layers") == 0
    request.getfixturevalue("kernels_on_cpu")
    new_g, loss_g = _step(cfg)(params, tok, lab)
    # one count per traced layer, and every layer took the kernels
    assert pvar.read("moe_grouped_kernel_layers") == cfg.n_layers
    assert pvar.read("moe_ragged_dot_layers") == cfg.n_layers
    bf16 = dtype == jnp.bfloat16
    assert abs(float(loss_g) - float(loss_w)) <= (
        2e-3 if bf16 else 1e-5) * abs(float(loss_w))
    n_g, n_w = _grad_norms(params, new_g), _grad_norms(params, new_w)
    assert (n_w > 0).all()
    assert (np.abs(n_g - n_w) <= (5e-2 if bf16 else 1e-4) * n_w).all(), \
        np.abs(n_g - n_w) / n_w


@pytest.mark.parametrize("model", sorted(MODELS))
def test_the_cpu_step_is_the_step_that_calls_ragged_dot(model, monkeypatch,
                                                        pvar_clean):
    """On the CPU the entry adds nothing to the program: the lowered
    step is, as text, the one whose experts call lax.ragged_dot
    themselves, as they did before there was an entry."""
    cfg, params, tok, lab = _toy(model, jnp.bfloat16)
    through_entry = _step(cfg).lower(params, tok, lab).as_text()
    assert pvar.read("moe_ragged_dot_layers") == cfg.n_layers
    assert pvar.read("moe_grouped_kernel_layers") == 0
    monkeypatch.setattr(moe, "grouped_matmul", lax.ragged_dot)
    direct = _step(cfg).lower(params, tok, lab).as_text()
    assert through_entry == direct
    assert "tpu_custom_call" not in through_entry
    assert "pallas" not in through_entry


# -- the kernels at the cell's widths, compiled for a described chip ----------

CELL_M, CELL_E, CELL_D, CELL_F = 32768, 64, 2048, 1024


# -- the rows of a chip that holds a share of the experts -------------------

#: tokens, assignments a token, experts the router scores, experts held
#: (numbers 0 and 1), the rows the layer is bounded at
BT, BK, BE, BHELD, BOUND = 128, 2, 16, 2, 128
#: held assignments of the BT * BK = 256 a batch makes
HELD = {"under": 40, "at": BOUND, "zero": 0, "just_over": BOUND + 1,
        "far_over": 230}


def _forced_route(x, wg, held: int):
    """A route whose first `held` assignments (in token order) go to
    the held experts 0 / 1 and every other one to experts 2 .. 15 — a
    token's two experts always differ — with weights that are the
    router's own: the softmax of the chosen experts' logits."""
    flat = np.arange(BT * BK)
    experts = np.where(flat < held, flat % BK,
                       2 + (flat // BK + 5 * (flat % BK)) % (BE - 2))
    experts = jnp.asarray(experts.reshape(BT, BK), jnp.int32)
    weights = jax.nn.softmax(jnp.take_along_axis(
        (x @ wg).astype(jnp.float32), experts, axis=-1), axis=-1)
    counts = (experts.reshape(-1, 1) == jnp.arange(BE)).sum(
        0, dtype=jnp.int32)
    return moe.held_share(moe.TopKRoute(experts, weights, counts, None,
                                        None), 0, BHELD)


def _held_layer(bound, held, act, gated):
    """((a loss, the output), the loss's gradient with respect to (x,
    wg, w1, w3, w2)) of one expert layer whose rows are bounded at
    `bound`, as the model runs it: jitted, differentiated, the layer
    recomputed."""
    def loss(x, wg, w1, w3, w2):
        y = moe.sorted_moe_ffn(x, _forced_route(x, wg, held), w1,
                               w3 if gated else None, w2, act, bound)
        return jnp.sum(y.astype(jnp.float32) * jnp.cos(
            jnp.arange(y.size).reshape(y.shape))), y

    return jax.jit(jax.value_and_grad(jax.checkpoint(loss), has_aux=True,
                                      argnums=(0, 1, 2, 3, 4)))


def _held_operands(dtype, d=128, f=128):
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    shapes = ((BT, d), (d, BE), (BHELD, d, f), (BHELD, d, f), (BHELD, f, d))
    return [(jax.random.normal(k, s, jnp.float32)
             / np.sqrt(s[-2])).astype(dtype) for k, s in zip(ks, shapes)]


FORMS = ("gather", "product")


@pytest.fixture
def row_sum(request, monkeypatch):
    """The rule `moe.row_sum_gathers` answering for the form the case
    names, whatever the shapes."""
    monkeypatch.setattr(moe, "row_sum_gathers",
                        lambda *a: request.param == "gather")
    return request.param


@pytest.mark.parametrize("row_sum", FORMS, indirect=True)
@pytest.mark.parametrize("held", sorted(HELD))
@pytest.mark.parametrize("path", ["ragged_dot", "kernels"])
@pytest.mark.parametrize("experts", ["gated_silu", "ungated_relu"])
def test_the_bounded_layer_is_the_full_layer(experts, path, held, row_sum,
                                             request, pvar_clean):
    """The layer on `BOUND` rows with its fallback against the layer on
    all T * k, output and every gradient (rows, router, w1, w3, w2), a
    token's rows added in both forms: held assignments under the bound,
    exactly at it, none, and over it (the fallback taken: the layer on
    all rows through `lax.ragged_dot` — where the full layer's products
    are that too, bit for bit)."""
    if path == "kernels":
        request.getfixturevalue("kernels_on_cpu")
    act, gated = ("silu", True) if experts == "gated_silu" else ("relu",
                                                                 False)
    args = _held_operands(jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, want_g = _held_layer(None, HELD[held], act, gated)(*args)
        assert (pvar.read("moe_full_layers"),
                pvar.read("moe_bounded_layers")) == (1, 0)
        got, got_g = _held_layer(BOUND, HELD[held], act, gated)(*args)
    assert (pvar.read("moe_full_layers"),
            pvar.read("moe_bounded_layers")) == (1, 1)
    assert (pvar.read("moe_row_sum_gather_layers"),
            pvar.read("moe_row_sum_product_layers")) == (
                (1, 0) if row_sum == "gather" else (0, 1))
    # the products of both layers took the path asked for
    assert pvar.read("moe_grouped_kernel_layers" if path == "kernels"
                     else "moe_ragged_dot_layers") == 2
    over = HELD[held] > BOUND
    for name, a, b in zip(("y", "x", "wg", "w1", "w3", "w2"),
                          (got[1],) + got_g, (want[1],) + want_g):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        if (over and path == "ragged_dot") or (
                name == "y" and row_sum == "gather"
                and path == "ragged_dot"):
            # the fallback IS the full layer; the gathered sum adds the
            # full layer's terms, and two a token have one order
            assert (a == b).all(), name
        else:  # the float32 sums of a token's rows, in another order
            assert np.linalg.norm(a - b) <= 1e-6 * np.linalg.norm(b) + 1e-9, (
                name, np.linalg.norm(a - b), np.linalg.norm(b))
    if HELD[held] and gated:
        assert all(float(jnp.linalg.norm(g)) > 0 for g in want_g)


@pytest.mark.parametrize("row_sum", FORMS, indirect=True)
def test_the_bounded_layer_in_bfloat16_sums_in_float32(row_sum, pvar_clean):
    """bfloat16 rows: the experts' outputs and weight gradients are the
    full layer's bit for bit (the same rows in the same order), the
    output differs by the order of at most k float32 terms before ONE
    rounding — and by nothing where the rows are gathered: those are
    the full layer's terms, and two a token have one order."""
    args = _held_operands(jnp.bfloat16)
    want, want_g = _held_layer(None, HELD["under"], "silu", True)(*args)
    got, got_g = _held_layer(BOUND, HELD["under"], "silu", True)(*args)
    for name, a, b in zip(("y", "x", "wg", "w1", "w3", "w2"),
                          (got[1],) + got_g, (want[1],) + want_g):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        if row_sum == "gather" and name in ("y", "w1", "w3", "w2"):
            assert (a == b).all(), name
        else:
            assert np.linalg.norm(a - b) <= 1e-2 * np.linalg.norm(b), name


def _sorted_assignments(rng, t, k, bound):
    """A stable sort of t * k assignments of which `bound` or fewer
    stand in front, as (order, inv, token of each of the first `bound`
    rows)."""
    order = jnp.asarray(rng.permutation(t * k), jnp.int32)
    return order, jnp.argsort(order), order[:bound] // k


def _sums(form, v, order, inv, token, t, k):
    """`v`'s rows summed per token and the tokens' rows taken, in the
    form named: the functions and what they take differ, the
    mathematics does not."""
    if form == "product":
        return (lambda v: moe._sum_rows(v, token, t),
                lambda x: moe._take_rows(x, token, t))
    bound = v.shape[0]
    return (lambda v: moe._sum_held(v, order, inv, k, bound),
            lambda x: moe._take_held(x, order, inv, k, bound))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("form", FORMS)
def test_sum_rows_is_the_float32_sum_and_take_rows_transpose(form, dtype):
    """A token's rows summed (the 0/1 product `_sum_rows`, the gather
    by the sort's inverse `_sum_held`) against numpy's float64 sum of
    the values, rounded once: the pieces a float32 row is cut into lose
    nothing, a row fetched is a row as it was, and each sum and its
    take are each other's transposes."""
    rng = np.random.default_rng(2)
    t, k, b, d = 40, 3, 96, 24
    order, inv, token = _sorted_assignments(rng, t, k, b)
    v = jnp.asarray(rng.standard_normal((b, d)) * 10.0 ** rng.integers(
        -3, 4, (b, 1)), dtype)
    want, size = np.zeros((t, d)), np.zeros((t, d))
    np.add.at(want, np.asarray(token), np.asarray(v, np.float64))
    np.add.at(size, np.asarray(token), np.abs(np.asarray(v, np.float64)))
    sum_rows, take_rows = _sums(form, v, order, inv, token, t, k)
    got = sum_rows(v)
    assert got.dtype == dtype
    # one rounding of the sum to `dtype`, and float32's of the terms
    # on the way (a bfloat16 result differs from numpy's by no more)
    want = np.asarray(jnp.asarray(want, jnp.float32).astype(dtype),
                      np.float64)
    assert (np.abs(np.asarray(got, np.float64) - want)
            <= 3e-7 * np.maximum(size, np.abs(want))).all()
    g = jnp.asarray(rng.standard_normal((t, d)), dtype)
    back = jax.grad(lambda v: jnp.sum(
        (sum_rows(v) * g).astype(jnp.float32)))(v)
    assert back.dtype == dtype and (np.asarray(back, np.float32)
                                    == np.asarray(g[token], np.float32)).all()
    gx = jax.grad(lambda x: jnp.sum(
        (take_rows(x) * v).astype(jnp.float32)))(g)
    assert gx.dtype == dtype and (np.asarray(gx, np.float32)
                                  == np.asarray(got, np.float32)).all()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_the_weighted_sum_is_the_float32_sum_and_clears_what_is_not_held(
        dtype):
    """`_weigh_held` against numpy's float64 weighted sum rounded once,
    with the rows past the held ones NOT finite under their weights of
    0 (`held_share`'s) — the mask clears them where a product with 0
    would not — and its two gradients against autodiff's of the plain
    formula over the held rows."""
    rng = np.random.default_rng(3)
    t, k, b, d, held = 40, 3, 96, 24, 70
    order, inv, token = _sorted_assignments(rng, t, k, b)
    place = np.asarray(inv).reshape(t, k)
    weights = jnp.asarray(np.where(place < held, rng.random((t, k)), 0.0),
                          jnp.float32)
    clean = rng.standard_normal((b, d)) * (np.arange(b) < held)[:, None]
    out = jnp.asarray(np.where((np.arange(b) < held)[:, None], clean,
                               np.tile([np.nan, np.inf], d // 2)), dtype)
    clean = jnp.asarray(clean, dtype)

    def plain(out, weights):
        rows = out[jnp.minimum(inv, b - 1)].reshape(t, k, d)
        return jnp.einsum("tkd,tk->td", rows.astype(jnp.float32), weights,
                          precision="highest")

    def ours(out, weights):
        return moe._weigh_held(out, weights, order, inv, jnp.int32(held), b)

    got = ours(out, weights)
    assert got.dtype == dtype and bool(jnp.isfinite(
        got.astype(jnp.float32)).all())
    one_rounding = 4e-3 if dtype == jnp.bfloat16 else 1e-6
    assert _gap(got, plain(clean, weights)) <= one_rounding
    g = jnp.asarray(rng.standard_normal((t, d)), dtype)
    for arg in (0, 1):
        mine = jax.grad(lambda *a: jnp.sum(
            ours(*a).astype(jnp.float32) * g), argnums=arg)(clean, weights)
        auto = jax.grad(lambda *a: jnp.sum(
            plain(*a) * g.astype(jnp.float32)), argnums=arg)(clean, weights)
        if arg == 1:  # a place that is not held has no gradient
            auto = jnp.where(place < held, auto, 0)
        assert mine.dtype == auto.dtype
        assert _gap(mine, auto) <= (one_rounding if arg == 0 else 1e-6), arg
    # the rows that are not finite reach neither gradient of the held
    dw = jax.grad(lambda w: jnp.sum(ours(out, w).astype(jnp.float32) * g))(
        weights)
    assert bool(jnp.isfinite(dw).all())


#: the activations of the full layer's cases, with their derivatives
_NUMPY_ACT = {
    "silu": (lambda h: h / (1 + np.exp(-h)),
             lambda h: (1 + np.exp(-h) + h * np.exp(-h))
             / (1 + np.exp(-h)) ** 2),
    "relu": (lambda h: np.maximum(h, 0), lambda h: (h > 0) * 1.0),
}


def _numpy_layer(x, experts, weights, w1, w3, w2, act, g):
    """The expert layer and its transpose in float64, assignment by
    assignment and with no sort: ``y[t] = sum_j weights[t, j] *
    expert_{experts[t, j]}(x[t])`` and, for the cotangent `g` of y, the
    gradients of x, weights, w1, w3 (None: ungated) and w2."""
    x, weights, w1, w2, g = (np.asarray(a, np.float64)
                             for a in (x, weights, w1, w2, g))
    w3 = None if w3 is None else np.asarray(w3, np.float64)
    f, df = _NUMPY_ACT[act]
    y, dx, dweights = np.zeros_like(x), np.zeros_like(x), np.zeros_like(
        weights)
    dw1, dw2 = np.zeros_like(w1), np.zeros_like(w2)
    dw3 = None if w3 is None else np.zeros_like(w3)
    for tok, j in np.ndindex(*experts.shape):
        e = int(experts[tok, j])
        pre = x[tok] @ w1[e]
        gate = 1.0 if w3 is None else x[tok] @ w3[e]
        hidden = f(pre) * gate
        out = hidden @ w2[e]
        y[tok] += weights[tok, j] * out
        dweights[tok, j] = out @ g[tok]
        dout = weights[tok, j] * g[tok]
        dw2[e] += np.outer(hidden, dout)
        dhidden = w2[e] @ dout
        dpre = dhidden * gate * df(pre)
        dw1[e] += np.outer(x[tok], dpre)
        dx[tok] += w1[e] @ dpre
        if w3 is not None:
            dw3[e] += np.outer(x[tok], dhidden * f(pre))
            dx[tok] += w3[e] @ (dhidden * f(pre))
    return y, (dx, dweights, dw1, dw3, dw2)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("experts", ["gated_silu", "ungated_relu"])
@pytest.mark.parametrize("k", [1, 6, 8])
def test_the_full_layer_is_the_float64_layer(k, experts, dtype, pvar_clean):
    """The layer over all T * k rows — a token taken to its places by
    `_take_held`, its rows weighed and added by `_weigh_held`, their
    transposes `_sum_held` and `_take_held` — against numpy's float64
    layer written assignment by assignment, with no sort in it: output,
    and the gradients of the rows, the router's weights and every
    expert matrix, for one, six and all eight of 8 experts a token. In
    float32 the two differ by the order of sums; in bfloat16 by the
    rounding of each product's result (three a row) and of the sum."""
    act, gated = ("silu", True) if experts == "gated_silu" else ("relu",
                                                                 False)
    t, d, f, e = 48, 32, 40, 8
    rng = np.random.default_rng(7 + k)
    x, g = (jnp.asarray(rng.standard_normal((t, d)), dtype) for _ in "xg")
    w1, w3, w2 = (jnp.asarray(rng.standard_normal(s) / np.sqrt(s[1]), dtype)
                  for s in ((e, d, f), (e, d, f), (e, f, d)))
    route = moe.topk_routing(jnp.asarray(rng.standard_normal((t, e)),
                                         jnp.float32), k)

    def layer(x, weights, w1, w3, w2):
        y = moe.sorted_moe_ffn(x, route._replace(weights=weights), w1,
                               w3 if gated else None, w2, act)
        return jnp.sum(y.astype(jnp.float32) * g.astype(jnp.float32)), y

    with jax.default_matmul_precision("highest"):
        (_, y), grads = jax.jit(jax.value_and_grad(
            layer, argnums=(0, 1, 2, 3, 4), has_aux=True))(
                x, route.weights, w1, w3, w2)
    assert (pvar.read("moe_full_layers"),
            pvar.read("moe_bounded_layers")) == (1, 0)
    want_y, want = _numpy_layer(x, np.asarray(route.experts), route.weights,
                                w1, w3 if gated else None, w2, act, g)
    assert y.dtype == dtype
    tol = 1e-2 if dtype == jnp.bfloat16 else 2e-6  # measured: 6e-3, 2e-7
    for name, got, ref in zip(("y", "x", "weights", "w1", "w3", "w2"),
                              (y,) + grads, (want_y,) + want):
        if ref is None:  # an ungated layer's w3: used by nothing
            assert not np.asarray(got, np.float32).any()
            continue
        assert got.dtype == (jnp.float32 if name == "weights" else dtype)
        gap = np.linalg.norm(np.asarray(got, np.float64) - ref)
        assert np.linalg.norm(ref) > 0 and gap <= tol * np.linalg.norm(ref), (
            name, gap, np.linalg.norm(ref))


@pytest.mark.parametrize("t,k", [(4096, 8), (128, 4), (96, 2), (1000, 3)])
def test_the_bound_rule(t, k):
    """A multiple of the row tile under T * k, T * k itself at share 1
    and wherever SLACK shares cover the layer, never less than SLACK
    times the share, monotone in the share."""
    n = 256
    bounds = [moe.held_rows_bound(t, k, c, n) for c in range(1, n + 1)]
    assert bounds == sorted(bounds) and bounds[-1] == t * k
    for c, b in zip(range(1, n + 1), bounds):
        assert b == t * k or b % moe._TM == 0
        assert b >= min(t * k, moe.SLACK * c * t * k / n)
        assert b == t * k or b < moe.SLACK * c * t * k / n + moe._TM
        if moe.SLACK * c >= n:
            assert b == t * k


def test_the_bound_of_the_cells():
    """glm5-train-t4096 (8 of 256 held, 4,096 tokens x 8): 8 row tiles
    of the 64; its rehearsal (4 of 16, 128 x 4) and OLMoE: all rows."""
    assert moe.held_rows_bound(4096, 8, 8, 256) == moe.SLACK * 1024
    assert moe.held_rows_bound(128, 4, 4, 16) == 512
    assert moe.held_rows_bound(4096, 8, 64, 64) == 4096 * 8


#: the two accepted cells that bound a layer's rows: (t, k, experts
#: held, experts routed, D), (the experts' width, activation, gated),
#: and whether their per-token sums gather
BOUNDED_CELLS = {
    "nemotron-train-t8192": ((8192, 6, 16, 128, 2688),
                             (1856, "relu2", False), True),
    "glm5-train-t4096": ((4096, 8, 8, 256, 6144), (2048, "silu", True),
                         False),
}


@pytest.mark.parametrize("cell", sorted(BOUNDED_CELLS))
def test_the_row_sum_of_the_cells(cell):
    """Which form each accepted cell's shapes take — nemotron's 24,576
    rows of 49,152 the gather, GLM-5's 4,096 of 32,768 the product —
    and that the choice reads shapes and the rows' type alone: it moves
    with the bound, with k and with the type, not with t or D (both
    forms grow with them alike)."""
    (t, k, held, n, d), _, gathers = BOUNDED_CELLS[cell]
    bound = moe.held_rows_bound(t, k, held, n)
    assert moe.row_sum_gathers(t, k, bound, d, jnp.bfloat16) is gathers
    for t2, d2 in ((t // 4, d), (t * 4, d), (t, 128), (t, 8 * d)):
        assert moe.row_sum_gathers(t2, k, bound, d2, jnp.bfloat16) is gathers
    # float32 rows: six passes over twice the bytes
    assert moe.row_sum_gathers(t, k, bound, d, jnp.float32) is gathers
    # the product's operations grow with the bound, the gather's bytes
    # with k: far enough either way the other form takes over
    assert moe.row_sum_gathers(t, k, 64 * bound, d, jnp.bfloat16)
    assert not moe.row_sum_gathers(t, 64 * k, bound, d, jnp.bfloat16)
    assert not moe.row_sum_gathers(t, k, moe._TM, d, jnp.bfloat16)


def test_the_gathered_layer_lowers_without_a_scatter_or_a_t_by_bound_product(
        pvar_clean):
    """The bounded layer at nemotron-train-t8192's shapes, lowered on
    abstract operands (no compile): the rule takes the gather, and no
    `dot_general` has a ``[T, bound]`` operand and nothing scatters —
    in the bounded branch and in the fallback, both directions."""
    (t, k, held, n, d), (f, act, _), gathers = BOUNDED_CELLS[
        "nemotron-train-t8192"]
    bound = moe.held_rows_bound(t, k, held, n)
    assert gathers and bound == 24576

    def loss(x, weights, w1, w2, experts, counts, g):
        route = moe.TopKRoute(experts, weights, counts, None, None)
        y = moe.sorted_moe_ffn(x, route, w1, None, w2, act, bound)
        return jnp.sum(y.astype(jnp.float32) * g)

    arg = jax.ShapeDtypeStruct
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3))).lower(
        arg((t, d), jnp.bfloat16), arg((t, k), jnp.float32),
        arg((held, d, f), jnp.bfloat16), arg((held, f, d), jnp.bfloat16),
        arg((t, k), jnp.int32), arg((held,), jnp.int32),
        arg((t, d), jnp.float32)).as_text()
    assert (pvar.read("moe_bounded_layers"),
            pvar.read("moe_row_sum_gather_layers"),
            pvar.read("moe_row_sum_product_layers")) == (1, 1, 0)
    assert "stablehlo.gather" in text and "stablehlo.case" in text
    assert "scatter" not in text
    products = [line for line in text.splitlines() if "dot_general" in line]
    assert products  # the fallback's dense ragged_dot on this backend
    assert not [p for p in products if f"tensor<{t}x{bound}x" in p]


@pytest.mark.parametrize("leaf,k,n", [("w1_w3", CELL_D, CELL_F),
                                      ("w2", CELL_F, CELL_D)])
def test_kernels_compile_for_the_chip_at_the_cells_shapes(one_chip, leaf, k,
                                                          n):
    """What interpret mode cannot show: the chip's compiler takes the
    three kernels at the cell's shapes with the tiles the rule picks
    (VMEM, alignment), and the compiled product and its transposes hold
    no transposed copy of the rows or of the weights."""
    tiles = moe.grouped_tiles("tpu", CELL_M, k, n, jnp.bfloat16)
    kernels = moe._grouped_kernels(tiles, False)

    def loss(rows, w, counts, g):
        return jnp.sum(kernels(rows, w, counts).astype(jnp.float32) * g)

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        arg((CELL_M, k)), arg((CELL_E, k, n)), arg((CELL_E,), jnp.int32),
        arg((CELL_M, n), jnp.float32)).compile()
    text = compiled.as_text()
    for name in ("moe_gmm_nt", "moe_tgmm"):
        assert name in text, (leaf, name)
    assert "ragged-dot" not in text
    assert f"bf16[{k},{CELL_M}]" not in text          # rows^T
    assert f"bf16[{CELL_E},{n},{k}]" not in text      # weights^T
    # beside the operands and results: the cotangent rounded to
    # bfloat16 ([M, n]) and small change
    assert (compiled.memory_analysis().temp_size_in_bytes
            < 1.1 * CELL_M * n * 2)


def test_the_cells_step_compiles_for_the_chip_on_the_kernels(one_chip,
                                                             monkeypatch):
    """olmoe-train-t4096's step (one of its four layers, the published
    widths) for a described v5e, the rules asked as on the TPU: the
    experts' nine products a layer are the kernels, forward and backward
    under `moe_experts`, and no ragged-dot instruction is left."""
    from benchmark.runners import olmoe_train as ot

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs",
                           "olmoe-1b-7b.json")) as f:
        sizes = ot.model_sizes(json.load(f))
    sizes["n_layers"] = 1
    cfg = ot.program_config(sizes)
    for mod, name in ((moe, "grouped_tiles"), (att, "blockwise_tile")):
        rule = getattr(mod, name)
        monkeypatch.setattr(mod, name, functools.partial(
            lambda rule, backend, *a, **kw: rule("tpu", *a, **kw), rule))
    step = jax.jit(tfm.make_train_step(cfg, AX, tfm.param_specs(cfg, AX),
                                       lr=0.01), donate_argnums=(0,))
    shapes = jax.eval_shape(
        lambda: tfm.init_params(np.random.default_rng(0), cfg))
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one_chip), shapes)
    tok = jax.ShapeDtypeStruct((1, 4096), jnp.int32, sharding=one_chip)
    text = step.lower(params, tok, tok).compile().as_text()
    assert "ragged-dot" not in text and "ragged_dot" not in text
    calls = [line.split(" = ")[0].strip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and "/moe_experts/" in line]
    kernels = sorted(c.lstrip("%").rsplit(".", 1)[0] for c in calls)
    assert kernels == (["moe_gmm"] * 3 + ["moe_gmm_nt"] * 3
                       + ["moe_tgmm"] * 3), calls


@pytest.mark.parametrize("cell", sorted(BOUNDED_CELLS))
def test_the_bounded_layer_compiles_for_the_chip(cell, one_chip, monkeypatch,
                                                 pvar_clean):
    """The expert layer of the two cells that bound their rows
    (glm5-train-t4096: 8 of 256 experts held, 4,096 tokens x 8;
    nemotron-train-t8192: 16 of 128, 8,192 x 6, a width the lanes do
    not divide; the published widths) for a described v5e, the rules
    asked as on the TPU: one conditional a direction; the bounded
    branch's products are the kernels on `bound` rows, the fallback's
    are ragged-dot instructions and no kernel; no kernel has T * k
    rows. Where the rule gathers, nothing is ``[T, bound]`` and no
    conditional hands on float32 rows of the size of T * k (XLA moved
    the final sum out of both branches until a barrier kept it in)."""
    (t, k, held, n, d), (f, act, gated), gathers = BOUNDED_CELLS[cell]
    rule, pad = moe.grouped_tiles, moe.expert_width_pad
    monkeypatch.setattr(moe, "grouped_tiles",
                        lambda backend, *a: rule("tpu", *a))
    monkeypatch.setattr(moe, "expert_width_pad",
                        lambda backend, width: pad("tpu", width))
    bound = moe.held_rows_bound(t, k, held, n)
    assert bound == (t * k // 2 if gathers else 4096)

    def loss(x, logits, w1, w3, w2, g):
        route = moe.held_share(moe.sigmoid_routing(logits, None, k), 0, held)
        y = moe.sorted_moe_ffn(x, route, w1, w3 if gated else None, w2, act,
                               bound)
        return jnp.sum(y.astype(jnp.float32) * g)

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3, 4))).lower(
        arg((t, d)), arg((t, n), jnp.float32), arg((held, d, f)),
        arg((held, d, f)), arg((held, f, d)),
        arg((t, d), jnp.float32)).compile()
    assert (pvar.read("moe_bounded_layers"),
            pvar.read("moe_grouped_kernel_layers")) == (1, 1)
    assert (pvar.read("moe_row_sum_gather_layers"),
            pvar.read("moe_row_sum_product_layers")) == (
                (1, 0) if gathers else (0, 1))
    text = compiled.as_text()
    conditionals = [line for line in text.splitlines()
                    if " conditional(" in line]
    assert len(conditionals) == 2
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    names = [c.split(" = ")[0].strip().lstrip("%").rsplit(".", 1)[0]
             for c in calls]
    per = 3 if gated else 2  # products a direction: w1, (w3,) w2
    assert sorted(n for n in names if n.startswith("moe_")) == (
        ["moe_gmm"] * 2 * per + ["moe_gmm_nt"] * per + ["moe_tgmm"] * per
    ), names
    # libtpu's own kernels (PR 26's) are the fallback's, all of them
    assert all(n.startswith(("moe_", "ragged-dot")) for n in names), names
    for name, call in zip(names, calls):
        if name.startswith("moe_"):
            assert f"[{t * k}," not in call.split("custom_call_target")[0]
    assert (f"[{t},{bound}]" in text) is not gathers
    if gathers:
        for line in conditionals:
            result = line.split(" conditional(")[0]
            assert not re.search(
                rf"f32\[({t * k}|{t},{k}|{k},{t}),{d}\]", result), result


#: the full layer's shapes (t, k, experts, D, F): a toy for this
#: backend, mellum2-train-t16384's for the described chip
FULL_LAYERS = {"cpu": (64, 4, 8, 96, 128),
               "v5e": (16384, 8, 64, 2304, 896)}


@pytest.mark.parametrize("target", sorted(FULL_LAYERS))
def test_the_recomputed_full_layer_holds_five_row_gathers(target, request,
                                                          monkeypatch):
    """One full expert layer under `jax.checkpoint`, value and
    gradient, compiled (here, and for a described v5e at
    mellum2-train-t16384's shapes on the kernels): five gathers make
    rows of D — the forward's dispatch and combine, the recomputed
    dispatch, the combine's transpose and the dispatch's — where the
    einsum's transposes made six; three of them read the ``[T, D]``
    tokens (on the chip the fast kind, PERF.md 5) and two the ``[T k,
    D]`` rows; and the recomputed forward fetches nothing for the
    combine: the transpose keeps the products in the sort's order."""
    t, k, e, d, f = FULL_LAYERS[target]
    sharding = {}
    if target == "v5e":
        sharding["sharding"] = request.getfixturevalue("one_chip")
        rule = moe.grouped_tiles
        monkeypatch.setattr(moe, "grouped_tiles",
                            lambda backend, *a: rule("tpu", *a))

    def loss(x, logits, w1, w3, w2, g):
        y = moe.sorted_moe_ffn(x, moe.topk_routing(logits, k, True), w1, w3,
                               w2, "silu")
        return jnp.sum(y.astype(jnp.float32) * g)

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, **sharding)

    text = jax.jit(jax.value_and_grad(
        jax.checkpoint(loss), argnums=(0, 1, 2, 3, 4))).lower(
        arg((t, d)), arg((t, e), jnp.float32), arg((e, d, f)),
        arg((e, d, f)), arg((e, f, d)),
        arg((t, d), jnp.float32)).compile().as_text()
    assert ("moe_gmm" in text) is (target == "v5e")
    shape_of = dict(re.findall(r"%([\w.\-]+) = \w+\[([\d,]*)\]", text))
    gathers = []  # (rows of the source, the gather's scope path)
    for line in text.splitlines():
        made = re.search(r"= \w+\[([\d,]*)\]\S* gather\("
                         r"(?:\w+\[[\d,]*\]\S* )?%([\w.\-]+)", line)
        if made and made.group(1).split(",")[-1] == str(d):
            source = shape_of[made.group(2)].split(",")
            assert source[-1] == str(d), line
            gathers.append((int(source[0]), re.search(
                r'op_name="([^"]*)"', line).group(1)))
    assert len(gathers) == 5, gathers
    assert sorted(rows for rows, _ in gathers) == [t] * 3 + [t * k] * 2
    assert not [path for _, path in gathers
                if "rematted_computation" in path and "moe_combine" in path]
    assert sum("rematted_computation" in path for _, path in gathers) == 1


# -- the per-token sum as a kernel of per-row DMAs (PR 51) -----------------------

from ompi_tpu.ops import grouped_matmul as gk  # noqa: E402


def _pack(v, tn=None):
    """`v` [m, n] in the packed layout (`gk.packed_shape`), written here
    word by word in numpy: the kernels' reader, not their code."""
    m, n = v.shape
    tn = tn or n
    pack, lanes = gk.packed_rows(v.dtype), gk.packed_lanes(tn)
    if pack == 2:
        half = np.asarray(v.astype(jnp.float32)).view(np.uint32) >> 16
        words = half[0::2] | (half[1::2] << 16)
    else:
        words = np.asarray(v).view(np.uint32)
    out = np.full((n // tn, m // pack, lanes, 128), 0xffc00000, np.uint32)
    for b in range(n // tn):  # the sublanes past a block's columns: NaNs
        out[b, :, :tn // 128] = words[:, b * tn:(b + 1) * tn].reshape(
            m // pack, tn // 128, 128)
    return jnp.asarray(out.reshape(-1, 128))


def _unpack(words, m, n, tn, dtype):
    """The ``[m, n]`` rows of a packed array, as float32."""
    pack, lanes = gk.packed_rows(dtype), gk.packed_lanes(tn)
    words = np.asarray(words).reshape(n // tn, m // pack, lanes, 128)
    words = np.concatenate([words[b, :, :tn // 128].reshape(m // pack, tn)
                            for b in range(n // tn)], axis=1)
    if pack == 1:
        return words.view(np.float32)
    out = np.empty((m, n), np.float32)
    out[0::2] = (words << 16).view(np.float32)
    out[1::2] = (words & np.uint32(0xffff0000)).view(np.float32)
    return out


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("lanes", [16, 18, 21])
@pytest.mark.parametrize("bound", ["full", "bounded"])
@pytest.mark.parametrize("k", [6, 8])
def test_the_reduce_kernel_is_the_weighted_and_the_plain_sum(k, bound, lanes,
                                                             dtype):
    """`gk.row_reduce` (interpret mode) on a source packed by numpy
    against `_weigh_held` and `_sum_held` on the same rows in the plain
    layout: k of 6 and 8, every row there or a bound with `held` under
    it and places past it, widths of 16, 18 and 21 blocks of 128
    columns (the last two pad to 24 sublanes, NaNs here), both types.
    The rows past `held` are NOT finite, as are the words nobody wrote:
    a skipped place adds nothing whatever it would have fetched. Where
    every place has its row (`full`) both forms are read: the compact
    one, and the one that fetches every place."""
    rng = np.random.default_rng(k + lanes)
    t, d = 256, 128 * lanes
    rows = t * k if bound == "full" else t * k // 2
    held = rows if bound == "full" else rows - 200
    order, inv, _ = _sorted_assignments(rng, t, k, rows)
    place = np.asarray(inv).reshape(t, k)
    weights = jnp.asarray(np.where(place < held, rng.random((t, k)), 0.0),
                          jnp.float32)
    clean = rng.standard_normal((rows, d)) * (np.arange(rows) < held)[:, None]
    out = jnp.asarray(np.where((np.arange(rows) < held)[:, None], clean,
                               np.nan), dtype)
    places = inv.reshape(-1, k).T
    one_rounding = 4e-3 if dtype == jnp.bfloat16 else 1e-6
    # both forms where every place has its row, the compact one alone
    # under a bound; blocks of 1,024 columns where they divide the width
    # (the plain sum: once a case, in the form the layer takes there)
    forms = [(d, True)] + [(d, False)] * (bound == "full") + [
        (1024, bound == "bounded")] * (lanes == 16)
    for tn, compact in forms:
        got = gk.row_reduce(_pack(out, tn), places, weights.T,
                            jnp.int32(held), d, tn, dtype, compact=compact,
                            interpret=True)
        want = moe._weigh_held(out, weights, order, inv, jnp.int32(held),
                               rows)
        assert got.dtype == dtype and got.shape == (t, d)
        assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
        assert _gap(got, want) <= one_rounding, (tn, compact, "weighted")
    # the plain sum takes every place under the bound: clean rows
    clean = jnp.asarray(clean, dtype)
    got = gk.row_reduce(_pack(clean), places, None, jnp.int32(rows), d, d,
                        dtype, compact=bound == "bounded", interpret=True)
    assert got.dtype == dtype
    assert _gap(got, moe._sum_held(clean, order, inv, k, rows)) <= one_rounding


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("product", ["gmm", "gmm_nt_two_pairs",
                                     "gmm_nt_blocks_of_columns"])
@pytest.mark.parametrize("groups", sorted(GROUPS))
def test_the_packed_products_are_the_plain_kernels_unpacked(groups, product,
                                                            dtype):
    """`gk.gmm(packed=True)` unpacked by numpy against the plain
    kernel's result, over every case of groups (whole tiles, edges
    inside a tile and inside a WORD's pair of rows, empty groups, rows
    past the last group: zeros): the ``w2`` product's form; the rows'
    gradient's — two pairs summed in the float32 tile and rounded once,
    against the two plain products added in float32 —; and that one in
    blocks of 1,024 columns, which lead in the layout."""
    rng = np.random.default_rng(len(groups))
    sizes = jnp.asarray(GROUPS[groups], jnp.int32)
    m, k = M, 128
    n, tn = (2048, 1024) if product.endswith("columns") else (384, 384)
    nt = product != "gmm"
    tiles = (256, 128, tn)

    def operands():
        return (jnp.asarray(rng.standard_normal((m, k)), dtype),
                jnp.asarray(rng.standard_normal((E, n, k) if nt
                                                else (E, k, n)), dtype))

    pairs = [operands() for _ in range(2 if nt else 1)]
    more = dict(zip(("lhs2", "rhs2"), pairs[1])) if nt else {}
    got = gk.gmm(*pairs[0], sizes, tiles, transpose_rhs=nt, packed=True,
                 interpret=True, **more)
    assert got.dtype == jnp.uint32
    assert got.shape == gk.packed_shape(m, n, tn, dtype) == (
        n // tn * (m // gk.packed_rows(dtype)) * gk.packed_lanes(tn), 128)
    want = sum(gk.gmm(lhs, rhs, sizes, tiles, transpose_rhs=nt,
                      out_dtype=jnp.float32, interpret=True)
               for lhs, rhs in pairs).astype(dtype)
    got = _unpack(got, m, n, tn, dtype)
    # the same float32 tile rounded once; the edge blocks' products are
    # made 128 columns at a time, so a float32 sum's order may differ
    assert _gap(got, want) <= (4e-3 if dtype == jnp.bfloat16 else 1e-6)
    assert not got[int(sizes.sum()):].any()


@pytest.fixture
def reduce_on_cpu(kernels_on_cpu, monkeypatch):
    """`kernels_on_cpu`, and the rule `moe.row_reduce_kernel` asked as
    on a TPU about a source of any size: the layer's sums are
    `gk.row_reduce`'s, in interpret mode."""
    for name in ("row_reduce_kernel", "reduce_tiles"):
        monkeypatch.setattr(moe, name, functools.partial(
            lambda rule, backend, *a: rule("tpu", *a), getattr(moe, name)))
    monkeypatch.setattr(moe, "ROW_REDUCE_MIN_BYTES", 0)
    monkeypatch.setattr(moe, "row_sum_gathers", lambda *a: True)
    monkeypatch.setattr(moe, "_reduced_rows", functools.partial(
        moe._reduced_rows, interpret=True))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("experts", ["gated_silu", "ungated_relu"])
@pytest.mark.parametrize("rows", ["full", "bounded"])
def test_the_layer_on_the_reduce_kernel_is_the_float64_layer(
        rows, experts, dtype, reduce_on_cpu, pvar_clean):
    """`test_the_full_layer_is_the_float64_layer`'s comparison with the
    kernel path forced (`_reduced_rows`: the packed ``w2`` product and
    rows' gradient, `gk.row_reduce` for the combine and the dispatch's
    transpose, the backward pass's plain ``w2`` product): output and
    all five gradients, over all the rows and under a bound that the
    held assignments fit (2 of 8 experts held; the others' assignments
    are nobody's, and their weights' gradient is zero)."""
    act, gated = ("silu", True) if experts == "gated_silu" else ("relu",
                                                                 False)
    t, k, d, f, e = 128, 4, 128, 256, 8
    held, bound = (e, None) if rows == "full" else (2, 256)
    rng = np.random.default_rng(11)
    x, g = (jnp.asarray(rng.standard_normal((t, d)), dtype) for _ in "xg")
    w1, w3, w2 = (jnp.asarray(rng.standard_normal(s) / np.sqrt(s[1]), dtype)
                  for s in ((e, d, f), (e, d, f), (e, f, d)))
    route = moe.held_share(moe.topk_routing(jnp.asarray(
        rng.standard_normal((t, e)), jnp.float32), k), 0, held)
    assert int(route.counts.sum()) <= (bound or t * k)

    def layer(x, weights, w1, w3, w2):
        y = moe.sorted_moe_ffn(x, route._replace(weights=weights), w1[:held],
                               w3[:held] if gated else None, w2[:held], act,
                               bound)
        return jnp.sum(y.astype(jnp.float32) * g.astype(jnp.float32)), y

    with jax.default_matmul_precision("highest"):
        (_, y), grads = jax.jit(jax.value_and_grad(
            jax.checkpoint(layer), argnums=(0, 1, 2, 3, 4), has_aux=True))(
                x, route.weights, w1, w3, w2)
    assert (pvar.read("moe_row_reduce_kernel_layers"),
            pvar.read("moe_row_reduce_xla_layers")) == (1, 0)
    assert pvar.read("moe_bounded_layers") == (rows == "bounded")
    # numpy's layer over ALL the experts: an assignment to one that is
    # not held weighs nothing, and has no gradient of its weight
    here = np.asarray(route.experts) < held
    want_y, want = _numpy_layer(
        x, np.where(here, np.asarray(route.experts), held), route.weights,
        w1, w3 if gated else None, w2, act, g)
    want = list(want)
    want[1] = np.where(here, want[1], 0)
    tol = 1e-2 if dtype == jnp.bfloat16 else 2e-6
    for name, got, ref in zip(("y", "x", "weights", "w1", "w3", "w2"),
                              (y,) + grads, [want_y] + want):
        if ref is None:
            assert not np.asarray(got, np.float32).any()
            continue
        assert got.dtype == (jnp.float32 if name == "weights" else dtype)
        gap = np.linalg.norm(np.asarray(got, np.float64) - ref)
        assert np.linalg.norm(ref) > 0 and gap <= tol * np.linalg.norm(ref), (
            name, gap, np.linalg.norm(ref))


#: the seven expert cells: (t, k, experts held, experts routed, D), the
#: experts' width as the kernels see it, gated, and what the rule says
#: (of glm5-train-t4096 it is never asked: `row_sum_gathers` keeps that
#: cell's sums on the 0/1 product)
EXPERT_CELLS = {
    "mellum2-train-t16384": ((16384, 8, 64, 64, 2304), 896, True, True),
    "solar2-train-t8192": ((8192, 8, 40, 320, 4096), 1280, True, True),
    "kexaone-train-t8192": ((8192, 8, 8, 128, 6144), 2048, True, True),
    "nemotron-train-t8192": ((8192, 6, 16, 128, 2688), 1920, False, True),
    "glm5-train-t4096": ((4096, 8, 8, 256, 6144), 2048, True, True),
    "olmoe-train-t4096": ((4096, 8, 64, 64, 2048), 1024, True, False),
    "kimivl-train-t4096": ((4096, 6, 64, 64, 2048), 1408, True, False),
}


@pytest.mark.parametrize("cell", sorted(EXPERT_CELLS))
def test_the_reduce_rule_at_the_cells_shapes(cell):
    """`moe.row_reduce_kernel`'s truth table at the seven expert cells'
    shapes, and that it reads what it is given and nothing else: no on
    every backend but the TPU, for a width the lanes do not divide, for
    tokens that are not whole tiles of 128 and for a type the kernels
    do not take; the grouped kernels' tiles exist wherever it says
    yes."""
    (t, k, held, n, d), f, gated, kernel = EXPERT_CELLS[cell]
    bound = moe.held_rows_bound(t, k, held, n)
    ask = functools.partial(moe.row_reduce_kernel, "tpu")
    assert ask(t, k, bound, d, jnp.bfloat16) is kernel
    for backend in ("cpu", "gpu"):
        assert not moe.row_reduce_kernel(backend, t, k, bound, d,
                                         jnp.bfloat16)
    assert not ask(t, k, bound, d + 64, jnp.bfloat16)
    assert not ask(t + 64, k, bound, d, jnp.bfloat16)
    assert not ask(t, k, bound + 128, d, jnp.bfloat16)
    assert not ask(t, k, bound, d, jnp.float16)
    # the bytes of all the places' rows, held or not: they grow with t,
    # k and the width; the bound moves nothing
    assert ask(8 * t, k, bound, d, jnp.bfloat16)
    assert ask(t, 8 * k, bound, d, jnp.bfloat16)
    assert not ask(t // 8, k, moe._TM, d, jnp.bfloat16)
    assert ask(t, k, moe._TM, d, jnp.bfloat16) is kernel
    assert ask(t, k, bound, d, jnp.float32) is (
        kernel or cell == "olmoe-train-t4096")
    tiles = moe.reduce_tiles("tpu", bound, d, f, jnp.bfloat16, gated)
    assert tiles is not None and moe.reduce_tiles(
        "cpu", bound, d, f, jnp.bfloat16, gated) is None
    for tm, sub, tn in (tiles.out, tiles.drows):
        assert bound % tm == 0 and tm % sub == 0
        assert tn == d or (d % tn == 0 and tn % 1024 == 0)


@pytest.mark.parametrize("cell", ["mellum2-train-t16384",
                                  "kexaone-train-t8192"])
def test_the_reduced_layer_compiles_for_the_chip_without_a_row_gather(
        cell, one_chip, monkeypatch, pvar_clean):
    """One recomputed expert layer, value and gradient, at the two
    cells' shapes for a described v5e, every rule asked as on the TPU:
    the sums are `moe_row_reduce` (two a layer: the combine, the
    dispatch's transpose); no XLA gather reads a ``[bound, D]`` source
    on the taken path — what is gathered comes from the ``[T, D]``
    tokens —; the packed arrays go from the kernel that writes them to
    the kernel that reads them with no copy, convert or bitcast of
    XLA's between; and the recomputed forward makes neither the packed
    ``w2`` product nor its sum again."""
    (t, k, held, n, d), f, gated, kernel = EXPERT_CELLS[cell]
    assert kernel
    for name in ("grouped_tiles", "row_reduce_kernel", "reduce_tiles",
                 "expert_width_pad"):
        monkeypatch.setattr(moe, name, functools.partial(
            lambda rule, backend, *a: rule("tpu", *a), getattr(moe, name)))
    bound = moe.held_rows_bound(t, k, held, n)

    def loss(x, logits, w1, w3, w2, g):
        route = moe.held_share(moe.sigmoid_routing(logits, None, k), 0, held)
        y = moe.sorted_moe_ffn(x, route, w1, w3, w2, "silu", bound)
        return jnp.sum(y.astype(jnp.float32) * g)

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(jax.value_and_grad(
        jax.checkpoint(loss), argnums=(0, 1, 2, 3, 4))).lower(
        arg((t, d)), arg((t, n), jnp.float32), arg((held, d, f)),
        arg((held, d, f)), arg((held, f, d)),
        arg((t, d), jnp.float32)).compile().as_text()
    assert (pvar.read("moe_row_reduce_kernel_layers"),
            pvar.read("moe_row_reduce_xla_layers")) == (1, 0)
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    names = [c.split(" = ")[0].strip().lstrip("%").rsplit(".", 1)[0]
             for c in calls]
    assert names.count("moe_row_reduce") == 2, names
    packed = sorted((c for c in calls if " = u32[" in c),
                    key=lambda c: "moe_gmm_nt" in c)
    tiles = moe.reduce_tiles("tpu", bound, d, f, jnp.bfloat16, True)
    assert len(packed) == 2  # the w2 product, the rows' gradient
    for call, (_, _, tn) in zip(packed, (tiles.out, tiles.drows)):
        words = gk.packed_shape(bound, d, tn, jnp.bfloat16)[0]
        assert f" = u32[{words},128]" in call, call
    # every u32 array of that size is a kernel's result or operand:
    # XLA makes none of its own
    for line in text.splitlines():
        if re.search(r" = u32\[\d{6,},128\]", line):
            assert "tpu_custom_call" in line or " parameter(" in line, line
    shape_of = dict(re.findall(r"%([\w.\-]+) = \w+\[([\d,]*)\]", text))
    taken = "branch_1_fun" if bound < t * k else ""
    for line in text.splitlines():
        made = re.search(r"= \w+\[([\d,]*)\]\S* gather\("
                         r"(?:\w+\[[\d,]*\]\S* )?%([\w.\-]+)", line)
        if made and made.group(1).split(",")[-1] == str(d) and taken in line:
            if "branch_0_fun" in line:
                continue  # the fallback: the full layer on ragged_dot
            assert shape_of[made.group(2)] == f"{t},{d}", line
