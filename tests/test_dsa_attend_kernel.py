"""Learned sparse attention's kernels (ops/sparse_attention.py behind
`ops/attention.dsa_attend` and the rule `dsa_tile`) against the plain
path `_dsa_attend_blocks`, here on the CPU with the kernels in interpret
mode at toy sizes and 128-row tiles.

Tolerances. With float32 operands both sides keep float32 scores,
statistics and sums and differ by the order of the sums and by where
the division by the row sum happens: 2e-5 of the result's norm (measured
1e-6). With bfloat16 operands the kernels round the UNNORMALISED
probabilities to bfloat16 before the PV product and dS before its two
products, the plain path the normalised ones and lets XLA round the
cotangent: two roundings of the same numbers, 1% of the norm at most
(measured 0.3-0.5%; on the chip at the cell's shapes 0.29% for `o`,
0.27-0.41% for the gradients, PERF.md section 6, PR 31). The summed
probabilities are float32 on both sides (1e-5 on the chip). A key block
visited that should not be, a mask tile read for the wrong pair or a row
left out misses these by orders of magnitude.
"""

import functools
import json
import os
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402

from ompi_tpu.core import pvar  # noqa: E402
from ompi_tpu.models import transformer as tfm  # noqa: E402
from ompi_tpu.ops import attention as att  # noqa: E402
from ompi_tpu.ops import moe  # noqa: E402
from ompi_tpu.ops import sparse_attention as sa  # noqa: E402

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
AX = tfm.Axes()

#: (T, heads, D, Dv, dtype, tiles): rows x keys of a pair, heads a step
#: of the forward and the head sum / of the backward
SHAPES = {
    "t256_h4_d128_f32": (256, 4, 128, 128, jnp.float32,
                         sa.Tiles(128, 128, 2, 2)),
    "t256_h4_d128_bf16": (256, 4, 128, 128, jnp.bfloat16,
                          sa.Tiles(128, 128, 4, 1)),
    "t384_h2_d256_dv128_f32": (384, 2, 256, 128, jnp.float32,
                               sa.Tiles(128, 128, 1, 2)),
    "t512_h2_rows256_keys128_f32": (512, 2, 128, 128, jnp.float32,
                                    sa.Tiles(256, 128, 2, 1)),
    "t512_h2_rows128_keys256_bf16": (512, 2, 128, 256, jnp.bfloat16,
                                     sa.Tiles(128, 256, 1, 2)),
}


def _mask(kind: str, t: int):
    rng = np.random.default_rng(7)
    causal = np.tril(np.ones((t, t), bool))
    if kind == "causal_only":
        return causal
    scores = np.where(causal, rng.standard_normal((t, t)), -np.inf)
    keep = np.asarray(att.dsa_select(jnp.asarray(scores, jnp.float32),
                                     t // 4))
    if kind == "topk":
        return keep
    if kind == "empty_key_block":
        # no query past row 256 keeps a key of the second key block: a
        # whole 128 x 128 tile under the diagonal is empty, and rows
        # meet it with nothing yet kept in the blocks before
        keep = keep.copy()
        keep[256:, 128:256] = False
        keep[256:, :128] = False
        return keep | np.eye(t, dtype=bool)
    if kind == "one_key_rows":
        # rows that keep a single key: their own, or the very first
        keep = keep.copy()
        keep[200] = False
        keep[200, 200] = True
        keep[t - 1] = False
        keep[t - 1, 0] = True
        return keep
    raise ValueError(kind)


MASKS = ["causal_only", "topk", "empty_key_block", "one_key_rows"]


def _operands(t, h, d, dv, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    make = lambda key, *s: jax.random.normal(  # noqa: E731
        key, s, jnp.float32).astype(dtype)
    return (make(ks[0], t, h, d), make(ks[1], t, h, d), make(ks[2], t, h, dv),
            jax.random.normal(ks[3], (t, h, dv), jnp.float32))


def _gap(got, want):
    got, want = (np.asarray(x, np.float64) for x in (got, want))
    return np.linalg.norm(got - want) / np.linalg.norm(want)


def _kernel_path(tiles):
    """`dsa_attend`'s kernel branch with these tiles, interpreted."""
    def fn(q, k, v, keep, scale):
        rule = att.dsa_tile
        att.dsa_tile = lambda *a: tiles
        try:
            return att.dsa_attend(q, k, v, keep, scale, interpret=True)
        finally:
            att.dsa_tile = rule
    return fn


def _value_and_grads(fn, q, k, v, keep, scale, g):
    def loss(q, k, v):
        o, p = fn(q, k, v, keep, scale)
        return jnp.sum(o.astype(jnp.float32) * g), (o, p)
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                      has_aux=True))(q, k, v)


@pytest.mark.parametrize("mask", MASKS)
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_output_head_sum_and_gradients_equal_the_plain_path(shape, mask,
                                                            pvar_clean):
    t, h, d, dv, dtype, tiles = SHAPES[shape]
    q, k, v, g = _operands(t, h, d, dv, dtype)
    keep = jnp.asarray(_mask(mask, t))
    scale = d ** -0.5
    (_, (o, p)), grads = _value_and_grads(_kernel_path(tiles), q, k, v, keep,
                                          scale, g)
    assert pvar.read("attn_dsa_kernel_layers") == 1
    assert pvar.read("attn_dsa_masked_layers") == 0
    with jax.default_matmul_precision("highest"):
        (_, (o_w, p_w)), grads_w = _value_and_grads(
            att._dsa_attend_blocks, q, k, v, keep, scale, g)
    limit = 1e-2 if dtype == jnp.bfloat16 else 2e-5
    assert o.shape == o_w.shape and o.dtype == o_w.dtype
    assert p.shape == (t, t) and p.dtype == jnp.float32
    assert np.isfinite(np.asarray(o, np.float32)).all()
    assert _gap(o, o_w) < limit
    assert _gap(p, p_w) < (1e-2 if dtype == jnp.bfloat16 else 2e-5)
    # a pair that is not kept has no probability, exactly; every row's
    # probabilities sum to the number of heads
    assert (np.asarray(p)[~np.asarray(keep)] == 0).all()
    np.testing.assert_allclose(np.asarray(p).sum(-1), h, rtol=1e-3)
    for got, want in zip(grads, grads_w):
        assert got.dtype == want.dtype and got.shape == want.shape
        assert _gap(got, want) < limit


def test_the_three_kernels_alone_are_the_dense_formulas():
    """`forward`, `head_sum` and `backward` called by hand ([H, T, .]
    operands, the mask as int8 and transposed) against the softmax
    written out: the log-sum-exp too, which no result of `dsa_attend`
    shows."""
    t, h, d = 256, 2, 128
    q, k, v, g = _operands(t, h, d, d, jnp.float32, seed=3)
    qh, kh, vh, gh = (a.transpose(1, 0, 2) for a in (q, k, v, g))
    keep = jnp.asarray(_mask("topk", t))
    m8 = keep.astype(jnp.int8)
    tiles = sa.Tiles(128, 128, 2, 1)
    o, lse = sa.forward(qh, kh, vh, m8, tiles, interpret=True)
    with jax.default_matmul_precision("highest"):
        s = jnp.where(keep[None], jnp.einsum("hqd,hkd->hqk", qh, kh),
                      -jnp.inf)
        probs = jax.nn.softmax(s, -1)
        want_o = jnp.einsum("hqk,hkd->hqd", probs, vh)
        want_lse = jax.nn.logsumexp(s, -1)
    assert lse.shape == (h, 1, t) and lse.dtype == jnp.float32
    assert _gap(o, want_o) < 2e-5
    np.testing.assert_allclose(np.asarray(lse[:, 0]), np.asarray(want_lse),
                               rtol=1e-5, atol=1e-5)
    p = sa.head_sum(qh, kh, lse, tiles, interpret=True)
    assert _gap(jnp.where(keep, p, 0.0), probs.sum(0)) < 2e-5
    di = (gh * o).sum(-1)[:, None, :]
    got = sa.backward(qh, kh, vh, gh, lse, di, m8.T, tiles, interpret=True)

    def dense(qh, kh, vh):
        s = jnp.where(keep[None], jnp.einsum("hqd,hkd->hqk", qh, kh),
                      -jnp.inf)
        return (jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, -1), vh)
                * gh).sum()

    with jax.default_matmul_precision("highest"):
        want = jax.grad(dense, argnums=(0, 1, 2))(qh, kh, vh)
    for a, b in zip(got, want):
        assert _gap(a, b) < 2e-5


def test_tiles_that_do_not_divide_raise():
    q = jnp.zeros((2, 256, 128), jnp.float32)
    with pytest.raises(ValueError, match="do not divide"):
        sa.forward(q, q, q, jnp.zeros((256, 256), jnp.int8),
                   sa.Tiles(96, 128, 2, 2), interpret=True)
    with pytest.raises(ValueError, match="do not divide"):
        sa.head_sum(q, q, jnp.zeros((2, 1, 256)),
                    sa.Tiles(128, 128, 3, 2), interpret=True)


# -- the rule -----------------------------------------------------------------

@pytest.mark.parametrize("t,heads,d_qk,d_v,itemsize,want", [
    # glm5-train-t4096: 64 heads of 256 / 256
    (4096, 64, 256, 256, 2, (512, 512, 16, 4)),
    (4096, 64, 256, 256, 4, (512, 512, 8, 2)),
    (8192, 64, 256, 256, 2, (512, 512, 16, 2)),
    (16384, 64, 256, 256, 2, (512, 512, 16, 1)),
    (4096, 6, 128, 256, 2, (512, 512, 2, 2)),
    (768, 8, 128, 128, 2, (256, 256, 8, 8)),
    (640, 3, 256, 128, 2, (128, 128, 1, 1)),
])
def test_rule_gives_tiles_that_divide_and_fit(t, heads, d_qk, d_v, itemsize,
                                              want):
    tiles = att.dsa_tile("tpu", t, heads, d_qk, d_v, itemsize)
    assert tiles == sa.Tiles(*want)
    assert not (t % tiles.rows or t % tiles.keys)
    assert not any(heads % g for g in tiles[2:])
    # the backward's dq: float32 scratch and the block it is written
    # from, twice, under the bound
    assert (tiles.heads_bwd * t * d_qk * (4 + 2 * itemsize)
            <= att._DSA_DQ_BYTES)


@pytest.mark.parametrize("why,args", [
    ("the CPU", ("cpu", 4096, 64, 256, 256)),
    ("a GPU", ("gpu", 4096, 64, 256, 256)),
    ("query width 192: not whole lanes", ("tpu", 4096, 64, 192, 256)),
    ("value width 64", ("tpu", 4096, 64, 256, 64)),
    ("widths of 16 and 8, as the toy models", ("tpu", 256, 32, 16, 8)),
    ("a length no tile divides", ("tpu", 4096 + 64, 64, 256, 256)),
    ("a length under the smallest tile", ("tpu", 64, 64, 256, 256)),
    ("a sequence whose dq outgrows VMEM for one head",
     ("tpu", 65536, 64, 256, 256)),
    ("the same, for float32 operands at half the length",
     ("tpu", 16384, 64, 256, 256, 4)),
])
def test_rule_refuses(why, args):
    assert att.dsa_tile(*args) is None, why


def test_the_cpu_takes_the_blocks_and_counts_it(pvar_clean):
    """Lane-multiple widths, a length the tiles divide — and the CPU:
    the rule says no, the program holds no kernel, the count says
    which path was traced."""
    t, h, d = 256, 4, 128
    q, k, v, _ = _operands(t, h, d, d, jnp.bfloat16)
    keep = jnp.asarray(_mask("topk", t))
    f = jax.jit(lambda q, k, v, keep: att.dsa_attend(q, k, v, keep, 0.1))
    text = f.lower(q, k, v, keep).as_text()
    assert "tpu_custom_call" not in text and "pallas" not in text
    assert pvar.read("attn_dsa_masked_layers") == 1
    assert pvar.read("attn_dsa_kernel_layers") == 0
    o, p = f(q, k, v, keep)
    o_w, p_w = jax.jit(lambda q, k, v, keep: att._dsa_attend_blocks(
        q, k, v, keep, 0.1))(q, k, v, keep)
    np.testing.assert_array_equal(np.asarray(o, np.float32),
                                  np.asarray(o_w, np.float32))
    np.testing.assert_array_equal(np.asarray(p), np.asarray(p_w))


def test_operands_of_two_types_take_the_blocks(monkeypatch, pvar_clean):
    monkeypatch.setattr(att, "dsa_tile", lambda *a: pytest.fail(
        "the rule is not asked about operands the kernels do not take"))
    q, k, v, _ = _operands(128, 2, 128, 128, jnp.bfloat16)
    keep = jnp.asarray(_mask("causal_only", 128))
    att.dsa_attend(q, k.astype(jnp.float32), v, keep, 0.1)
    att.dsa_attend(*(a.astype(jnp.float16) for a in (q, k, v)), keep, 0.1)
    assert pvar.read("attn_dsa_masked_layers") == 2


# -- the model with the kernels put in by hand --------------------------------

@pytest.fixture
def kernels_on_cpu(monkeypatch):
    """The rule as on a TPU with 128-row tiles, the kernels in interpret
    mode: everything else is the program's own path."""
    rule = att.dsa_tile
    monkeypatch.setattr(att, "_DSA_TILES", (128,))
    monkeypatch.setattr(att, "dsa_tile",
                        lambda backend, *a: rule("tpu", *a))
    monkeypatch.setattr(att, "dsa_attend", functools.partial(
        att.dsa_attend, interpret=True))


def _toy_glm5(dtype, **over):
    """GLM-5's rehearsal configuration with heads as wide as the lanes
    (the toy's 16 / 8 are what the rule refuses)."""
    from benchmark.runners import glm5_train as gt

    with open(os.path.join(HERE, "benchmark", "configs",
                           "glm-5.rehearsal.json")) as f:
        config = json.load(f)
    config["param_dtype"] = "float32"
    cfg = gt.program_config(gt.model_sizes(config))
    return tfm.Config(**{**cfg.__dict__, "dtype": dtype, "qk_nope_dim": 96,
                         "qk_rope_dim": 32, "v_head_dim": 128, "n_heads": 2,
                         "index_topk": 100, **over})


def _step(cfg):
    return jax.jit(tfm.make_train_step(cfg, AX, tfm.param_specs(cfg, AX),
                                       lr=1.0))


def _grad_norms(params, new_params):
    return np.array([np.linalg.norm(np.asarray(a, np.float64)
                                    - np.asarray(b, np.float64))
                     for a, b in zip(jax.tree.leaves(params),
                                     jax.tree.leaves(new_params))])


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_train_step_with_the_kernels_equals_the_plain_step(dtype, request,
                                                           pvar_clean):
    """Loss and every leaf's gradient norm of a toy GLM-5 step (latent
    attention, the indexer and its loss, expert layers, the MTP module,
    every layer recomputed) at T 256 > index_topk 100. Tolerances are
    tests/test_moe_grouped_matmul.py's for a step with kernels put in:
    float32 — the order of float32 sums; bfloat16 — two roundings of one
    step."""
    cfg = _toy_glm5(dtype)
    params = tfm.init_params(np.random.default_rng(0), cfg)
    tok = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab, (2, 256)), jnp.int32)
    lab = jnp.roll(tok, -1, axis=1)
    layers = cfg.n_layers + cfg.mtp_layers
    new_w, loss_w = _step(cfg)(params, tok, lab)
    assert pvar.read("attn_dsa_layers") == layers
    assert pvar.read("attn_dsa_masked_layers") == layers
    assert pvar.read("attn_dsa_kernel_layers") == 0
    request.getfixturevalue("kernels_on_cpu")
    new_k, loss_k = _step(cfg)(params, tok, lab)
    # one count per traced layer, and every layer took the kernels
    assert pvar.read("attn_dsa_kernel_layers") == layers
    assert pvar.read("attn_dsa_masked_layers") == layers
    bf16 = dtype == jnp.bfloat16
    assert abs(float(loss_k) - float(loss_w)) <= (
        2e-3 if bf16 else 1e-5) * abs(float(loss_w))
    n_k, n_w = _grad_norms(params, new_k), _grad_norms(params, new_w)
    assert (n_w > 0).sum() >= len(n_w) - 2 * layers  # the routers' bias
    moved = n_w > 0
    assert (np.abs(n_k - n_w)[moved] <= (5e-2 if bf16 else 1e-4)
            * n_w[moved]).all(), (np.abs(n_k - n_w) / np.maximum(n_w, 1e-30))


# -- the kernels at the cell's shapes, compiled for a described chip ----------

CELL_T, CELL_H, CELL_D, CELL_DV = 4096, 64, 256, 256


def test_kernels_compile_for_the_chip_at_the_cells_shapes(one_chip):
    """What interpret mode cannot show: the chip's compiler takes the
    three kernels at the cell's shapes with the tiles the rule picks
    (VMEM, alignment, the transpositions inside them), and one layer's
    forward + backward holds nothing of [heads, rows, keys]."""
    assert att.dsa_tile("tpu", CELL_T, CELL_H, CELL_D, CELL_DV) is not None

    def loss(q, k, v, keep, g):
        rule = att.dsa_tile
        att.dsa_tile = lambda backend, *a: rule("tpu", *a)
        try:
            o, p = att.dsa_attend(q, k, v, keep, CELL_D ** -0.5)
        finally:
            att.dsa_tile = rule
        return jnp.sum(o.astype(jnp.float32) * g), p

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2),
                                has_aux=True)).lower(
        arg((CELL_T, CELL_H, CELL_D)), arg((CELL_T, CELL_H, CELL_D)),
        arg((CELL_T, CELL_H, CELL_DV)), arg((CELL_T, CELL_T), jnp.bool_),
        arg((CELL_T, CELL_H, CELL_DV), jnp.float32)).compile()
    text = compiled.as_text()
    for name in ("dsa_fwd", "dsa_head_sum", "dsa_bwd"):
        assert text.count(f"%{name}") >= 1, name
    assert not _score_blocks(text), _score_blocks(text)[:3]
    # beside operands and results: their [H, T, D] copies, o, lse, the
    # int8 mask twice, the [T, T] sum — nothing that grows with H x T x T
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


def _score_blocks(text: str):
    """The instructions under `dsa_attend` whose result is a float32
    array [several heads, a block of rows, a block of keys]: a score
    block in HBM (the blocks' are [16, 512, 512 ... 4096]; the kernels'
    own float32 results are [1, T, T], [H, 1, T] and, inside fusions,
    [H, T, 256])."""
    found = []
    for line in text.splitlines():
        if "dsa_attend" not in line or " = " not in line:
            continue
        for dims in re.findall(r"f32\[([\d,]+)\]",
                               line.split(" = ")[1].split("(")[0]):
            dims = [int(x) for x in dims.split(",")]
            if (len(dims) >= 3 and max(dims[:-2]) > 1 and dims[-2] >= 128
                    and dims[-1] >= 512):
                found.append(line.strip()[:160])
    return found


def test_the_detector_knows_a_score_block():
    at = ', metadata={op_name="jit(f)/attn_core/vmap(dsa_attend)/mul"}'
    assert _score_blocks("%a = f32[16,512,1024]{2,1,0} fusion(%b)" + at)
    assert _score_blocks("%a = f32[1,16,512,4096]{3,2,1,0} exp(%b)" + at)
    for mine in ("f32[1,4096,4096]{2,1,0}", "f32[64,1,4096]{2,1,0}",
                 "f32[1,64,4096,256]{3,2,1,0}", "bf16[16,512,1024]{2,1,0}"):
        assert not _score_blocks(f"%a = {mine} fusion(%b)" + at)
    assert not _score_blocks("%a = f32[16,512,1024]{2,1,0} fusion(%b), "
                             'metadata={op_name="jit(f)/dsa_index/mul"}')


def test_the_cells_step_compiles_for_the_chip_on_the_kernels(one_chip,
                                                             monkeypatch):
    """glm5-train-t4096's step (its dense layer at the published
    widths: one of its six attention layers) for a described v5e, the
    rules asked as on the TPU: `dsa_attend` is the
    kernels — per layer the forward and the head sum twice (the layer
    is recomputed) and one fused backward, all under the scope — and no
    float32 [heads, rows, keys] instruction is left under it."""
    from benchmark.runners import glm5_train as gt

    with open(os.path.join(HERE, "benchmark", "configs", "glm-5.json")) as f:
        sizes = gt.model_sizes(json.load(f))
    sizes["n_layers"], sizes["mtp_layers"] = 1, 0
    cfg = gt.program_config(sizes)
    for mod, name in ((moe, "grouped_tiles"), (att, "blockwise_tile"),
                      (att, "dsa_tile")):
        rule = getattr(mod, name)
        monkeypatch.setattr(mod, name, functools.partial(
            lambda rule, backend, *a, **kw: rule("tpu", *a, **kw), rule))
    step = jax.jit(tfm.make_train_step(cfg, AX, tfm.param_specs(cfg, AX),
                                       lr=0.01), donate_argnums=(0,))
    shapes = jax.eval_shape(
        lambda: tfm.init_params(np.random.default_rng(0), cfg))
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one_chip), shapes)
    tok = jax.ShapeDtypeStruct((1, CELL_T), jnp.int32, sharding=one_chip)
    text = step.lower(params, tok, tok).compile().as_text()
    calls = [line.split(" = ")[0].strip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and "(dsa_attend)/" in line]  # the scope, vmapped
    kernels = sorted(c.lstrip("%").rsplit(".", 1)[0] for c in calls)
    layers = cfg.n_layers + cfg.mtp_layers
    assert kernels == (["dsa_bwd"] * layers + ["dsa_fwd"] * 2 * layers
                       + ["dsa_head_sum"] * 2 * layers), calls
    assert not _score_blocks(text), _score_blocks(text)[:3]
