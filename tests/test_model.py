"""Flagship transformer: sharded training == single-device training.

The decisive correctness test for the whole device plane: one SGD step
under every parallelism strategy (dp/tp/sp, combined, and MoE-ep) must
produce the same loss and updated params as the unsharded step — the
analog of the reference's "every algorithm vs coll/basic oracle" rule
(SURVEY.md §4).
"""

import importlib.util
import os
import re
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from benchmark import manifest as mf  # noqa: E402
from ompi_tpu.util import jaxcompat  # noqa: E402
from ompi_tpu.models import transformer as tfm  # noqa: E402
from ompi_tpu.parallel import make_mesh  # noqa: E402

_spec = importlib.util.spec_from_file_location(
    "lower_cmp", os.path.join(HERE, "scripts", "lower_cmp.py"))
lower_cmp = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(lower_cmp)

CFG = tfm.Config(vocab=64, d_model=32, n_layers=2, n_heads=8, d_ff=64,
                 max_seq=64, dtype=jnp.float32)


def _data(rng, b, t):
    tokens = rng.integers(0, CFG.vocab, (b, t)).astype(np.int32)
    labels = np.roll(tokens, -1, axis=1).astype(np.int32)
    labels[:, -1] = -1
    return tokens, labels


def _single_step(cfg, params, tokens, labels, lr=1e-2):
    ax = tfm.Axes()
    specs = tfm.param_specs(cfg, ax)
    step = jax.jit(tfm.make_train_step(cfg, ax, specs, lr=lr))
    return step(params, tokens, labels)


def _sharded_step(cfg, ax, mesh, data_spec, params, tokens, labels,
                  lr=1e-2):
    specs = tfm.param_specs(cfg, ax)
    step = tfm.make_train_step(cfg, ax, specs, lr=lr)
    smapped = jaxcompat.shard_map(
        step, mesh=mesh,
        in_specs=(specs, data_spec, data_spec),
        out_specs=(specs, P()), check_vma=False)
    return jax.jit(smapped)(params, tokens, labels)


def _assert_trees_close(a, b, atol):
    la, _ = jax.tree.flatten(a)
    lb, _ = jax.tree.flatten(b)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   atol=atol, rtol=1e-4)


@pytest.fixture(scope="module")
def rngp():
    rng = np.random.default_rng(0)
    return rng, tfm.init_params(rng, CFG)


def _skip_if_small():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")


def test_single_device_step_decreases_loss(rngp):
    rng, params = rngp
    tokens, labels = _data(rng, 4, 16)
    p, l0 = _single_step(CFG, params, tokens, labels)
    for _ in range(3):
        p, l1 = _single_step(CFG, p, tokens, labels)
    assert np.isfinite(l0) and l1 < l0


@pytest.mark.parametrize("strategy", ["dp", "tp", "sp"])
def test_1d_sharding_matches_single(rngp, strategy):
    _skip_if_small()
    rng, params = rngp
    tokens, labels = _data(rng, 8, 16)
    ref_p, ref_l = _single_step(CFG, params, tokens, labels)

    mesh = make_mesh((strategy,), (8,))
    ax = tfm.Axes(**{strategy: strategy})
    data_spec = {"dp": P("dp", None), "tp": P(),
                 "sp": P(None, "sp")}[strategy]
    p, l = _sharded_step(CFG, ax, mesh, data_spec, params, tokens,
                         labels)
    np.testing.assert_allclose(float(l), float(ref_l), atol=1e-4)
    _assert_trees_close(p, ref_p, atol=5e-4)


def test_3d_dp_tp_sp_matches_single(rngp):
    _skip_if_small()
    rng, params = rngp
    tokens, labels = _data(rng, 4, 16)
    ref_p, ref_l = _single_step(CFG, params, tokens, labels)

    mesh = make_mesh(("dp", "tp", "sp"), (2, 2, 2))
    ax = tfm.Axes(dp="dp", tp="tp", sp="sp")
    p, l = _sharded_step(CFG, ax, mesh, P("dp", "sp"), params, tokens,
                         labels)
    np.testing.assert_allclose(float(l), float(ref_l), atol=1e-4)
    _assert_trees_close(p, ref_p, atol=5e-4)


def test_moe_ep_training_decreases_loss():
    _skip_if_small()
    cfg = tfm.Config(vocab=64, d_model=32, n_layers=2, n_heads=4,
                     d_ff=64, max_seq=64, moe_every=2, n_experts=8,
                     dtype=jnp.float32)
    rng = np.random.default_rng(1)
    params = tfm.init_params(rng, cfg)
    tokens, labels = _data(rng, 8, 16)

    mesh = make_mesh(("ep",), (8,))
    ax = tfm.Axes(ep="ep")
    specs = tfm.param_specs(cfg, ax)
    step = tfm.make_train_step(cfg, ax, specs, lr=1e-1)
    smapped = jax.jit(jaxcompat.shard_map(
        step, mesh=mesh,
        in_specs=(specs, P("ep"), P("ep")),
        out_specs=(specs, P()), check_vma=False))
    p, l0 = smapped(params, tokens, labels)
    for _ in range(5):
        p, l1 = smapped(p, tokens, labels)
    assert np.isfinite(l0) and float(l1) < float(l0)


def test_moe_tp_ep_runs():
    _skip_if_small()
    cfg = tfm.Config(vocab=64, d_model=32, n_layers=2, n_heads=4,
                     d_ff=64, max_seq=64, moe_every=2, n_experts=4,
                     dtype=jnp.float32)
    rng = np.random.default_rng(2)
    params = tfm.init_params(rng, cfg)
    tokens, labels = _data(rng, 8, 16)

    mesh = make_mesh(("ep", "tp"), (4, 2))
    ax = tfm.Axes(ep="ep", tp="tp")
    specs = tfm.param_specs(cfg, ax)
    step = tfm.make_train_step(cfg, ax, specs, lr=1e-1)
    smapped = jax.jit(jaxcompat.shard_map(
        step, mesh=mesh,
        in_specs=(specs, P("ep"), P("ep")),
        out_specs=(specs, P()), check_vma=False))
    p, l0 = smapped(params, tokens, labels)
    for _ in range(5):
        p, l1 = smapped(p, tokens, labels)
    assert np.isfinite(l0) and float(l1) < float(l0)


def test_sp_ulysses_schedule_matches_single(rngp):
    """The Ulysses (all-to-all) context-parallel schedule trains
    identically to the unsharded step — same oracle rule as ring."""
    _skip_if_small()
    rng, params = rngp
    tokens, labels = _data(rng, 8, 16)
    ref_p, ref_l = _single_step(CFG, params, tokens, labels)

    import dataclasses

    cfg_u = dataclasses.replace(CFG, sp_schedule="ulysses")
    mesh = make_mesh(("sp",), (8,))
    ax = tfm.Axes(sp="sp")
    p, l = _sharded_step(cfg_u, ax, mesh, P(None, "sp"), params,
                         tokens, labels)
    np.testing.assert_allclose(float(l), float(ref_l), atol=1e-4)
    _assert_trees_close(p, ref_p, atol=5e-4)


def test_bf16_param_storage_dtype_stable():
    """Config.param_dtype=bfloat16: the SGD update must keep the
    STORAGE dtype — a promotion to f32 changes the jitted step's
    input signature and forces a recompile inside any steady-state
    loop (the exact artifact that once mis-measured bf16 as 4x
    slower; see BASELINE.md)."""
    import jax
    import ml_dtypes
    import numpy as np

    from ompi_tpu.models import transformer as tfm

    cfg = tfm.Config(vocab=64, d_model=32, n_layers=2, n_heads=4,
                     d_ff=64, max_seq=32,
                     param_dtype=ml_dtypes.bfloat16)
    ax = tfm.Axes()
    params = tfm.init_params(np.random.default_rng(0), cfg)
    assert str(np.asarray(params["embed"]).dtype) == "bfloat16"
    step = jax.jit(tfm.make_train_step(cfg, ax,
                                       tfm.param_specs(cfg, ax)))
    rng = np.random.default_rng(1)
    toks = rng.integers(0, 64, (2, 16)).astype(np.int32)
    labs = np.roll(toks, -1, 1).astype(np.int32)
    p, loss = step(params, toks, labs)
    leaves = jax.tree.leaves(p)
    assert all(str(x.dtype) == "bfloat16" for x in leaves), \
        sorted({str(x.dtype) for x in leaves})
    p2, loss2 = step(p, toks, labs)  # same signature: no recompile
    assert all(str(x.dtype) == "bfloat16"
               for x in jax.tree.leaves(p2))
    assert np.isfinite(float(loss2))


# -- the one reading of the label's logit (PR 45) ----------------------------

def _gather_nll(logits, labels):
    """What `tfm._nll` replaced: the label's logit by a gather."""
    gold = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    return jax.nn.logsumexp(logits, axis=-1) - gold


def _nll32(logits, labels):
    return tfm._nll(logits, labels, jnp.float32)


@pytest.mark.parametrize("vocab", [128, 200, 50272 // 16])
@pytest.mark.parametrize("negatives", [False, True])
def test_nll_is_the_gather_form(vocab, negatives):
    """Value and gradient with respect to the logits, float32, to 1e-6:
    over labels with and without negatives (a masked position gives 0
    to both) and over vocabularies the 128 lanes do not divide."""
    rng = np.random.default_rng(vocab + negatives)
    logits = jnp.asarray(3 * rng.standard_normal((2, 24, vocab)),
                         jnp.float32)
    labels = rng.integers(0, vocab, (2, 24)).astype(np.int32)
    labels[0, 0], labels[1, -1] = 0, vocab - 1  # both ends of the iota
    if negatives:
        labels[rng.random((2, 24)) < 0.3] = -1
        labels[1, 3] = -100
    mask = (labels >= 0).astype(np.float32)
    weight = jnp.asarray(rng.random((2, 24)), jnp.float32) * mask

    def total(fn):
        return lambda x: (fn(x, labels) * weight).sum()

    got, want = _nll32(logits, labels), _gather_nll(logits, labels)
    assert got.shape == (2, 24) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=0)
    dgot, dwant = (jax.grad(total(fn))(logits) for fn in (_nll32,
                                                          _gather_nll))
    np.testing.assert_allclose(dgot, dwant, atol=1e-6, rtol=0)
    np.testing.assert_allclose(
        tfm._token_nll(logits, labels, mask, jnp.float32),
        (want * mask).sum(), rtol=1e-6)
    assert not np.asarray(dgot)[mask == 0].any()
    # in bfloat16 activations the cotangent is that one, rounded once
    dlow = jax.grad(total(lambda x, y: tfm._nll(x, y, jnp.bfloat16)))(logits)
    assert dlow.dtype == jnp.float32
    np.testing.assert_array_equal(
        dlow, dgot.astype(jnp.bfloat16).astype(jnp.float32))


#: a scatter's or a gather's first operand's type (a scatter's follows
#: its update region, lines below its name)
_MOVER = re.compile(r'stablehlo\.(scatter|gather)"\(.*?[>)] : '
                    r'\(tensor<([^>]*)>', re.S)


def movers_of_the_logits(text: str, tokens: int, vocab: int):
    """What in a lowered (StableHLO) step moves the logits by index:
    each `scatter` and `gather` whose operand's last dimension is the
    vocabulary, and `flat` for a rank-1 float32 array of tokens x vocab
    elements (the flattened logits)."""
    found = [kind for kind, operand in _MOVER.findall(text)
             if operand.split("x")[-2:-1] == [str(vocab)]]
    if f"tensor<{tokens * vocab}xf32>" in text:
        found.append("flat")
    return found


def test_movers_of_the_logits_finds_the_gathers_chain():
    """The reader itself, on the form the model had: the gather, and
    its transpose's scatter into the logits."""
    logits = jax.ShapeDtypeStruct((2, 8, 48), jnp.float32)
    labels = np.arange(16, dtype=np.int32).reshape(2, 8)

    def lowered(fn):
        return jax.jit(jax.value_and_grad(
            lambda x: fn(x, labels).sum())).lower(logits).as_text()

    assert sorted(movers_of_the_logits(lowered(_gather_nll), 16, 48)) \
        == ["gather", "scatter"]
    assert movers_of_the_logits(lowered(_nll32), 16, 48) == []
    assert movers_of_the_logits(
        lowered(lambda x, y: x.reshape(-1) ** 2), 16, 48) == ["flat"]


#: one train cell a model configuration
CELLS = ("opt30b-train-t1024", "olmoe-train-t4096", "glm5-train-t4096",
         "ouro-train-t4096", "kimivl-train-t4096", "nemotron-train-t8192",
         "mellum2-train-t16384")


@pytest.mark.parametrize("cell", CELLS)
def test_no_train_step_moves_the_logits_by_index(cell):
    """Every model configuration's rehearsal step, lowered: the label's
    logit is read through a mask on every path that makes a loss, so
    nothing gathers from the [B, T, vocab] logits, nothing scatters
    into them and nothing flattens them."""
    step, params, toks, labs = lower_cmp.step_and_shapes(
        cell, mf.load(), mf, rehearsal=True)
    vocab, width = params["embed"].shape
    assert vocab != width  # or the embedding's own gather would count
    text = step.lower(params, toks, labs).as_text()
    assert "stablehlo.dot_general" in text and f"x{vocab}xf32>" in text
    assert movers_of_the_logits(text, labs.size, vocab) == []
