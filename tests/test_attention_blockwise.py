"""Blockwise causal attention (ops/attention.py: `attention`, the rule
`blockwise_tile`, the kernel call `blockwise_mha`) against the
reference `att.mha`, here on the CPU with the Pallas kernels in
interpret mode at toy sizes and 128-wide tiles.

Tolerances. With float32 inputs both sides compute in float32 and
differ by the order of the sums alone: 2e-5 of the largest entry
(measured 2e-6). With bfloat16 inputs q carries the scale through one
more rounding and the backward recomputes the probabilities instead of
reading rounded ones: outputs to 0.02 absolute (one bfloat16 step of a
value of order 1; measured 0.016), gradients to 1% of their norm
(measured 0.3-0.5%). A wrong mask, scale or tile order misses these by
orders of magnitude.
"""

import functools
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from ompi_tpu.core import pvar  # noqa: E402
from ompi_tpu.models import transformer as tfm  # noqa: E402
from ompi_tpu.ops import attention as att  # noqa: E402
from ompi_tpu.parallel import make_mesh  # noqa: E402
from ompi_tpu.util import jaxcompat  # noqa: E402
from tests import lowered_text  # noqa: E402

TILE = 128


def _qkvw(b, t, h, dtype, d=128, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v = (jax.random.normal(kk, (b, t, h, d), jnp.float32).astype(dtype)
               for kk in ks[:3])
    return q, k, v, jax.random.normal(ks[3], (b, t, h, d), jnp.float32)


def _value_and_grads(f, q, k, v, w):
    def loss(q, k, v):
        return jnp.sum(f(q, k, v).astype(jnp.float32) * w)
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, k, v)


def _blockwise(q, k, v):
    return att.blockwise_mha(q, k, v, TILE, interpret=True)


def _f32(x):
    return np.asarray(x.astype(jnp.float32))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("t", [256, 512])
@pytest.mark.parametrize("h", [1, 3])
@pytest.mark.parametrize("b", [1, 2])
def test_forward_and_gradients_equal_the_reference(b, h, t, dtype):
    q, k, v, w = _qkvw(b, t, h, dtype)
    out = jax.jit(_blockwise)(q, k, v)
    ref = att.mha(q, k, v, causal=True)
    assert out.shape == ref.shape and out.dtype == ref.dtype
    bf16 = dtype == jnp.bfloat16
    np.testing.assert_allclose(_f32(out), _f32(ref), rtol=0,
                               atol=0.02 if bf16 else 2e-5)
    _, grads = _value_and_grads(_blockwise, q, k, v, w)
    _, want = _value_and_grads(att.mha, q, k, v, w)
    for name, g, r in zip("qkv", grads, want):
        gap = np.linalg.norm(_f32(g) - _f32(r)) / np.linalg.norm(_f32(r))
        assert gap < (1e-2 if bf16 else 2e-5), (name, gap)


def test_first_row_sees_itself_and_the_last_row_sees_every_key():
    q, k, v, _ = _qkvw(2, 256, 3, jnp.float32, seed=3)
    out = _f32(jax.jit(_blockwise)(q, k, v))
    np.testing.assert_allclose(out[:, 0], _f32(v)[:, 0], atol=1e-6)
    full = _f32(att.mha(q, k, v, causal=False))
    np.testing.assert_allclose(out[:, -1], full[:, -1], atol=2e-5)
    # and a row in the middle of a tile does NOT see the keys after it
    assert np.abs(out[:, 100] - full[:, 100]).max() > 1e-3


@pytest.mark.parametrize("t,tile", [(4096, 1024), (2048, 1024), (1024, 1024),
                                    (1536, 512), (512, 512), (768, 256),
                                    (256, 256)])
def test_rule_gives_the_largest_tile_that_divides_t(t, tile):
    assert att.blockwise_tile("tpu", t, t, 128) == tile
    assert att.blockwise_tile("tpu", t, t, 256) == tile


@pytest.mark.parametrize("why,args,kwargs", [
    ("the CPU", ("cpu", 1024, 1024, 128), {}),
    ("a GPU", ("gpu", 1024, 1024, 128), {}),
    ("more queries than keys", ("tpu", 1024, 512, 128), {}),
    ("no head", ("tpu", 1024, 1024, 0), {}),
    ("no tile divides 384", ("tpu", 384, 384, 128), {}),
    ("no tile divides 128", ("tpu", 128, 128, 128), {}),
    ("no tile divides 1000", ("tpu", 1000, 1000, 128), {}),
    ("fewer queries than keys", ("tpu", 512, 1024, 128), {}),
    ("not causal", ("tpu", 1024, 1024, 128), {"causal": False}),
    ("a query block at an offset", ("tpu", 1024, 1024, 128),
     {"q_offset": 1024}),
    ("a key block at an offset", ("tpu", 1024, 1024, 128),
     {"k_offset": 1024}),
    ("an offset that is not a Python int", ("tpu", 1024, 1024, 128),
     {"q_offset": np.int32(0)}),
])
def test_rule_refuses(why, args, kwargs):
    assert att.blockwise_tile(*args, **kwargs) is None, why


@pytest.mark.parametrize("width,padded", [(64, 128), (72, 128), (128, 128),
                                          (192, 256), (256, 256)])
def test_rule_takes_any_head_width_and_the_kernel_pads_it(width, padded):
    """Since the fifth model (heads of 72, of 192 against 128): the
    kernels' lanes are the kernel's affair, not either rule's."""
    assert att.blockwise_tile("tpu", 1024, 1024, width) == 1024
    assert att.segment_tile("tpu", 1024, 1024, 16, width,
                            width)[:2] == (1024, 1024)
    assert att.lanes(width) == padded


def test_the_cpu_takes_the_reference_and_counts_it(pvar_clean):
    q, k, v, _ = _qkvw(1, 256, 2, jnp.bfloat16)
    f = jax.jit(lambda q, k, v: att.attention(q, k, v))
    out = f(q, k, v)
    f(q, k, v)  # a second call traces nothing
    np.testing.assert_array_equal(_f32(out), _f32(att.mha(q, k, v)))
    assert pvar.read("attn_reference_layers") == 1
    assert pvar.read("attn_blockwise_layers") == 0


# -- the model with the blockwise path put in by hand -----------------------

@pytest.fixture
def blockwise_on_cpu(monkeypatch):
    """The rule as on a TPU with 128-wide tiles, the kernels in
    interpret mode: everything else is the program's own path."""
    rule = att.blockwise_tile
    monkeypatch.setattr(att, "_TILES", (TILE,))
    monkeypatch.setattr(att, "blockwise_tile",
                        lambda backend, *a, **kw: rule("tpu", *a, **kw))
    monkeypatch.setattr(att, "blockwise_mha", functools.partial(
        att.blockwise_mha, interpret=True))


OPT = dict(vocab=128, d_model=256, n_layers=2, n_heads=2, d_ff=128,
           max_seq=256)
OLMOE = dict(vocab=128, d_model=256, n_layers=2, n_heads=2, d_ff=32,
             max_seq=256, moe_every=1, n_experts=8, top_k=2, mlp_act="silu",
             mlp_gated=True, norm="rmsnorm", pos="rope", qk_norm=True,
             tie_head=False, router_aux_weight=0.01, router_z_weight=0.001)
MODELS = {"opt": OPT, "olmoe": OLMOE}
AX = tfm.Axes()


def _toy(model, dtype, b=1, t=256):
    cfg = tfm.Config(dtype=dtype, **MODELS[model])
    params = tfm.init_params(np.random.default_rng(0), cfg)
    tok = np.random.default_rng(1).integers(0, cfg.vocab, (b, t))
    tok = jnp.asarray(tok, jnp.int32)
    return cfg, params, tok, jnp.roll(tok, -1, axis=1)


def _step(cfg, ax=AX, mesh=None):
    specs = tfm.param_specs(cfg, ax)
    step = tfm.make_train_step(cfg, ax, specs, lr=1.0)
    if mesh is not None:
        step = jaxcompat.shard_map(step, mesh=mesh,
                                   in_specs=(specs, P(), P()),
                                   out_specs=(specs, P()), check_vma=False)
    return jax.jit(step)


def _grad_norms(params, new_params):
    """Per leaf, the norm of one plain SGD step at lr 1: the gradient's
    (divided by the token count, the same on both sides)."""
    return np.array([np.linalg.norm(np.asarray(a, np.float64)
                                    - np.asarray(b, np.float64))
                     for a, b in zip(jax.tree.leaves(params),
                                     jax.tree.leaves(new_params))])


def _assert_steps_agree(got, want, params, bf16):
    """Loss and every leaf's gradient norm. In float32 the two steps
    differ by the order of float32 sums (measured: loss 2e-7, norms
    3e-6). In bfloat16 each is a different rounding of the same
    step — a toy leaf's norm moves by up to 2.3% (measured; OLMoE's
    top-2 of 8 re-routes a token or two), the loss by 3e-4."""
    (new_g, loss_g), (new_w, loss_w) = got, want
    assert abs(float(loss_g) - float(loss_w)) <= (
        2e-3 if bf16 else 1e-5) * abs(float(loss_w))
    n_g, n_w = _grad_norms(params, new_g), _grad_norms(params, new_w)
    assert (n_w > 0).all()
    assert (np.abs(n_g - n_w) <= (5e-2 if bf16 else 1e-4) * n_w).all(), \
        np.abs(n_g - n_w) / n_w


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("model", ["opt", "olmoe"])
def test_train_step_with_the_blockwise_kernel_equals_the_reference_step(
        model, dtype, request, pvar_clean):
    cfg, params, tok, lab = _toy(model, dtype)
    want = _step(cfg)(params, tok, lab)
    assert pvar.read("attn_reference_layers") == cfg.n_layers
    request.getfixturevalue("blockwise_on_cpu")
    got = _step(cfg)(params, tok, lab)
    # one count per traced layer, and every layer took the kernel
    assert pvar.read("attn_blockwise_layers") == cfg.n_layers
    assert pvar.read("attn_reference_layers") == cfg.n_layers
    _assert_steps_agree(got, want, params, dtype == jnp.bfloat16)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_blockwise_step_under_two_way_tp_equals_the_reference_step(
        dtype, request, pvar_clean):
    """Under tp the kernel sees the local heads (one of two here) inside
    shard_map. OLMoE's QK-norm has no tp path (it raises), so OPT."""
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    cfg, params, tok, lab = _toy("opt", dtype)
    want = _step(cfg)(params, tok, lab)
    request.getfixturevalue("blockwise_on_cpu")
    ax = tfm.Axes(tp="tp")
    got = _step(cfg, ax, make_mesh(("tp",), (2,)))(params, tok, lab)
    assert pvar.read("attn_blockwise_layers") == cfg.n_layers
    _assert_steps_agree(got, want, params, dtype == jnp.bfloat16)


@pytest.mark.parametrize("model", ["opt", "olmoe"])
def test_the_cpu_step_is_the_step_that_calls_the_reference(model,
                                                           monkeypatch):
    """On the CPU the entry adds nothing to the program: the lowered
    step is, as text, the one whose layers call att.mha themselves, as
    every layer did before there was an entry (the names the entry
    gives q, k, v and the output for a recomputed layer's policy are
    taken out: they lower to their operands, and move jax's numbering
    of its private functions — tests/lowered_text.py)."""
    lowered_text.without_names(monkeypatch)
    cfg, params, tok, lab = _toy(model, jnp.bfloat16)
    through_entry = _step(cfg).lower(params, tok, lab).as_text()
    monkeypatch.setattr(
        att, "attention",
        lambda q, k, v, causal=True, scale=None: att.mha(q, k, v,
                                                         causal=causal))
    direct = _step(cfg).lower(params, tok, lab).as_text()
    assert through_entry == direct
    assert "tpu_custom_call" not in through_entry


# -- the kernels at the cells' widths, compiled for a described chip ---------

@pytest.mark.parametrize("cell,b,t,h", [("opt30b-train-t2048", 2, 2048, 56),
                                        ("opt30b-train-t1024", 4, 1024, 56),
                                        ("olmoe-train-t4096", 1, 4096, 16)])
def test_kernels_compile_for_the_chip_without_a_score_buffer(one_chip, cell,
                                                             b, t, h):
    """What interpret mode cannot show: the chip's compiler takes the
    kernels at the cell's shape with the tile the rule picks, and the
    compiled forward + backward holds nothing of the size of the
    [B, H, T, T] scores."""
    tile = att.blockwise_tile("tpu", t, t, 128)

    def loss(q, k, v, w):
        return jnp.sum(att.blockwise_mha(q, k, v, tile).astype(jnp.float32)
                       * w)

    arg = jax.ShapeDtypeStruct((b, t, h, 128), jnp.bfloat16,
                               sharding=one_chip)
    w = jax.ShapeDtypeStruct((b, t, h, 128), jnp.float32, sharding=one_chip)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        arg, arg, arg, w).compile()
    text = compiled.as_text()
    assert "splash_mha_fwd" in text and "splash_mha_dkv" in text, cell
    assert f"[{b},{h},{t},{t}]" not in text, cell
    scores_bytes = b * h * t * t * 4  # float32, as att.mha holds them
    assert compiled.memory_analysis().temp_size_in_bytes < scores_bytes / 2


@pytest.mark.parametrize("cell,t,h,d,dv,segmented", [
    ("kimivl-train-t4096 tower", 12288, 16, 72, 72, True),
    ("kimivl-train-t4096 decoder", 4096, 16, 192, 128, False)])
def test_padded_and_segmented_kernels_compile_for_the_chip(
        one_chip, cell, t, h, d, dv, segmented):
    """The fifth model's two attentions at the cell's shapes: heads of
    72 (the tower's, under the segment mask whose table of pairs is
    data: the repo's own kernels, `seg_fwd` and `seg_bwd`, heads as
    wide as they are) and of 192 against values of 128 (the decoder's,
    causal: the library's kernels, padded to their lanes) — the chip's
    compiler takes both and nothing of the size of the [H, T, T] scores
    is left."""
    if segmented:
        tile = att.segment_tile("tpu", t, t, h, d, dv)
        assert tile[:2] == (1024, 1024)
    else:
        tile = att.blockwise_tile("tpu", t, t, d)
        assert tile == 1024

    def loss(q, k, v, w, ids):
        o = att.blockwise_mha(q, k, v, tile,
                              segments=ids if segmented else None)
        return jnp.sum(o.astype(jnp.float32) * w)

    def arg(width, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct((1, t, h, width), dtype,
                                    sharding=one_chip)

    ids = jax.ShapeDtypeStruct((1, t), jnp.int32, sharding=one_chip)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        arg(d), arg(d), arg(dv), arg(dv, jnp.float32), ids).compile()
    text = compiled.as_text()
    mine = "seg_fwd" in text and "seg_bwd" in text
    library = "splash_mha_fwd" in text and "splash_mha_dkv" in text
    assert (mine, library) == (segmented, not segmented), cell
    assert not re.search(r"splash_mha_\w*segmented", text), cell
    assert f"[{h},{t},{t}]" not in text and f"[1,{h},{t},{t}]" not in text
    scores_bytes = h * t * t * 4  # float32, as att.mha holds them
    assert compiled.memory_analysis().temp_size_in_bytes < scores_bytes / 2
