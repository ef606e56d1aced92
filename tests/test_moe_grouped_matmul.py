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
