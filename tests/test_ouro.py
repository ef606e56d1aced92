"""The looped decoder (the fourth published model transformer.py
computes: a layer list run `loops` times with the same weights, a norm
on every sub-layer's output, an exit after every pass and the expected
loss over the exits) against its plain reference at toy widths on the
CPU, what its pieces must be, and what was there before."""

import functools
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights, weights_glm5, weights_ouro
from benchmark.reference import ouro_decoder as ref
from benchmark.runners import glm5_train
from benchmark.runners import ouro_train as ot
from ompi_tpu.core import pvar
from ompi_tpu.models import remat
from ompi_tpu.models import transformer as tfm
from tests import lowered_text

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
AX = tfm.Axes()


def _toy(dtype="float32", **over):
    with open(os.path.join(HERE, "benchmark", "configs",
                           "ouro-2.6b.rehearsal.json")) as f:
        config = json.load(f)
    config["param_dtype"] = dtype
    sizes = ot.model_sizes(config)
    cfg = ot.program_config(sizes)
    return sizes, tfm.Config(**{**cfg.__dict__, "dtype": jnp.dtype(dtype),
                                **over})


def _batch(sizes, seed, batch=2, seq=64):
    toks, labs = weights.batches(sizes["vocab"], 1, batch, seq, seed)
    return toks[0], labs[0]


def _mean_loss(cfg, tok, lab):
    def f(p):
        nll, cnt = tfm.loss_local(p, tok, lab, cfg, AX)
        return nll / cnt

    return f


def _close(a, b, rel=2e-4):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.linalg.norm(b), 1e-6)
    assert np.linalg.norm(a - b) <= rel * scale + 1e-7, (
        np.linalg.norm(a - b), scale)


# -- the whole step against the reference --------------------------------------

@pytest.mark.parametrize("remat", [True, False])
@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_train_step_is_the_references_loss_and_every_gradient(seed, remat):
    """Through make_train_step in float32: the loss, and each leaf's
    gradient read back from one SGD step at lr 1."""
    sizes, cfg = _toy(remat=remat)
    params = weights_ouro.device_init(sizes, seed)
    tok, lab = _batch(sizes, seed)
    spec = ot.reference_spec(sizes)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, tok, lab, spec)))(params)
    step = jax.jit(tfm.make_train_step(cfg, AX, tfm.param_specs(cfg, AX),
                                       lr=1.0))
    with jax.default_matmul_precision("highest"):
        new, loss = step(params, tok, lab)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    got = jax.tree.map(lambda a, b: a - b, params, new)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        _close(g, w, rel=2e-3), jax.tree_util.keystr(path)
    assert len(jax.tree.leaves(got)) == len(ot.gate_leaves(sizes))


@pytest.mark.parametrize("seed", [5, 2**31 + 7])
def test_bfloat16_step_is_near_the_references(seed):
    """As the cell runs it (bfloat16 storage and activations): the
    loss to bfloat16's three digits, every leaf's movement after one
    step within a few percent of the float32 reference's."""
    sizes, cfg = _toy("bfloat16")
    tok, lab = _batch(sizes, seed)
    spec = ot.reference_spec(sizes)
    step = jax.jit(tfm.make_train_step(cfg, AX, tfm.param_specs(cfg, AX),
                                       lr=0.01))
    new, loss = step(weights_ouro.device_init(sizes, seed), tok, lab)
    want, want_loss = ref.sgd_step(weights_ouro.device_init(sizes, seed),
                                   tok, lab, 0.01, spec)
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-3)
    start = weights_ouro.device_init(sizes, seed)
    from benchmark import compare

    got = np.asarray(compare.leaf_delta_norms(new, start))
    ref_moved = np.asarray(compare.leaf_delta_norms(want, start))
    gate = np.array(ot.gate_leaves(sizes))
    assert compare.worst_leaf_gap(got[~gate], ref_moved[~gate]) < 0.08
    assert compare.rms_leaf_gap(got[~gate], ref_moved[~gate]) < 0.03


@pytest.mark.parametrize("dtype, rel", [("float32", 1e-5),
                                        ("bfloat16", 3e-3)])
def test_every_exit_and_the_exit_distribution_are_the_references(dtype, rel):
    """Per position: each exit's cross-entropy and the probability of
    leaving there; and the probe's means."""
    sizes, cfg = _toy(dtype)
    params = weights_ouro.device_init(sizes, 11)
    tok, lab = _batch(sizes, 11)
    want_nll, want_p, _ = ref.exits(params, tok, lab, ot.reference_spec(sizes))

    @jax.jit
    def mine(params):
        exits = []
        h, _ = tfm._trunk(params, tok, cfg, AX, exits=exits)
        mask = jnp.ones(lab.shape, jnp.float32)
        nlls, logps = tfm._exit_terms(params, exits, h, lab, mask, cfg)
        return jnp.stack(nlls), jnp.exp(jnp.stack(logps))

    with jax.default_matmul_precision("highest"):
        nll, p = mine(params)
        mean_nll, mean_p = tfm.exit_stats(params, tok, lab, cfg)
    assert nll.shape == p.shape == (4,) + lab.shape
    _close(nll, want_nll, rel)
    _close(p, want_p, 10 * rel)
    np.testing.assert_allclose(mean_nll, want_nll.mean((1, 2)), rtol=rel)
    np.testing.assert_allclose(mean_p, want_p.mean((1, 2)), rtol=10 * rel)
    # the four exits differ: none is read from another's pass
    assert len({round(float(x), 4) for x in mean_nll}) == 4


@pytest.mark.parametrize("seed", [2, 2**31 + 9])
def test_a_shared_layers_gradient_is_the_sum_over_its_passes(seed):
    """Give every pass its own copy of the layers in the reference:
    the program's gradient of a shared leaf is the SUM of the four
    copies' gradients."""
    sizes, cfg = _toy()
    params = weights_ouro.device_init(sizes, seed)
    tok, lab = _batch(sizes, seed)
    spec = ot.reference_spec(sizes)
    copies = [params["layers"]] * spec.loops
    per_pass = jax.jit(jax.grad(lambda c: ref.loss(
        params, tok, lab, spec, per_pass=c)))(copies)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(_mean_loss(cfg, tok, lab)))(params)["layers"]
    norms = []
    for i, layer in enumerate(got):
        for name, g in layer.items():
            parts = [per_pass[s][i][name] for s in range(spec.loops)]
            want = functools.reduce(
                lambda a, b: jax.tree.map(jnp.add, a, b), parts)
            for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(want)):
                _close(a, b, rel=2e-3)
            if name == "w1":
                norms.append([float(jnp.linalg.norm(p)) for p in parts])
                # no single pass is the whole gradient
                _ = [pytest.raises(AssertionError, _close, g, p, 0.2)
                     for p in parts]
    assert all(min(n) > 0 for n in norms)


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_the_references_layerwise_step_is_its_whole_model_gradient(seed):
    sizes, _ = _toy()
    spec = ot.reference_spec(sizes)
    tok, lab = _batch(sizes, seed)
    params = weights_ouro.device_init(sizes, seed)
    want_loss, grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, tok, lab, spec)))(params)
    want = jax.tree.map(lambda p, g: p - 0.5 * g, params, grads)
    new, val = ref.sgd_step(weights_ouro.device_init(sizes, seed), tok, lab,
                            0.5, spec)
    assert float(val) == pytest.approx(float(want_loss), rel=1e-5)
    assert jax.tree.structure(new) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(want)):
        _close(a, b, rel=1e-4)
    nll, p, _ = ref.exits(params, tok, lab, spec)
    mean_nll, mean_p = ref.exit_means(params, tok, lab, spec)
    np.testing.assert_allclose(mean_nll, nll.mean((1, 2)), rtol=1e-5)
    np.testing.assert_allclose(mean_p, p.mean((1, 2)), rtol=1e-5)


# -- the pieces ------------------------------------------------------------------

def test_exit_distribution_sums_to_one_and_the_entropy_lowers_the_loss():
    """p sums to 1 at every position; the loss is the expected
    cross-entropy LESS beta x the entropy (the paper's sign: a uniform
    prior rewards spreading the exits)."""
    sizes, cfg = _toy()
    params = weights_ouro.device_init(sizes, 4)
    tok, lab = _batch(sizes, 4)
    nll, p, lambdas = ref.exits(params, tok, lab, ot.reference_spec(sizes))
    np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-6)
    assert float(p.min()) > 0 and p.shape[0] == lambdas.shape[0] + 1
    entropy = float(-(p * jnp.log(p)).sum(0).mean())
    assert 0.5 < entropy <= np.log(4) + 1e-6
    with jax.default_matmul_precision("highest"):
        with_beta = float(jax.jit(_mean_loss(cfg, tok, lab))(params))
        without = float(jax.jit(_mean_loss(tfm.Config(**{
            **cfg.__dict__, "exit_entropy_weight": 0.0}), tok, lab))(params))
    assert without == pytest.approx(float((p * nll).sum(0).mean()), rel=1e-5)
    assert without - with_beta == pytest.approx(
        cfg.exit_entropy_weight * entropy, rel=1e-4)
    assert cfg.exit_entropy_weight == 0.1 and with_beta < without


def test_a_looped_stack_without_exits_reads_the_last_pass():
    """loops > 1 with no gate: the plain next-token loss of the last
    pass's state, through the final norm once more than a pass ago."""
    sizes, cfg = _toy(exit_gate=False)
    full = weights_ouro.device_init(sizes, 6)
    params = {k: v for k, v in full.items() if k != "exit_gate"}
    tok, lab = _batch(sizes, 6)
    nll, _, _ = ref.exits(full, tok, lab, ot.reference_spec(sizes))
    with jax.default_matmul_precision("highest"):
        got = float(jax.jit(_mean_loss(cfg, tok, lab))(params))
        logits = jax.jit(lambda p: tfm.forward_local(p, tok, cfg, AX))(params)
    assert got == pytest.approx(float(nll[-1].mean()), rel=1e-5)
    assert logits.shape == tok.shape + (sizes["vocab"],)


def test_counters_and_the_probe():
    sizes, cfg = _toy()
    params = weights_ouro.device_init(sizes, 8)
    tok, lab = _batch(sizes, 8)
    names = ("loop_passes", "loop_layer_applications", "exit_probe_tokens",
             "exit_mass_micro_p0", "exit_mass_micro_p3")
    before = {n: pvar.read(n) for n in names}
    jax.jit(tfm.make_train_step(cfg, AX, tfm.param_specs(cfg, AX))).lower(
        params, tok, lab)
    got = {n: pvar.read(n) - before[n] for n in names}
    assert (got["loop_passes"], got["loop_layer_applications"]) == (4, 8)
    nll, mass = tfm.exit_stats(params, tok, lab, cfg)
    got = {n: pvar.read(n) - before[n] for n in names}
    assert got["exit_probe_tokens"] == tok.size
    assert got["exit_mass_micro_p3"] == pytest.approx(
        mass[3] * tok.size * 1e6, abs=1)
    assert mass.sum() == pytest.approx(1.0, abs=1e-6) and nll.shape == (4,)
    probe = ot.exit_probe(sizes, params, tok, lab)  # bfloat16 activations
    assert probe["exit_last_pass_mass_micro"] / 1e6 / probe[
        "exit_probe_tokens"] == pytest.approx(probe["mass"][3], abs=1e-6)
    assert probe["mass"][3] == pytest.approx(mass[3], rel=0.02)


def test_scopes_of_the_compiled_step():
    """Every pass around the accepted layer names, the norm between
    passes under `ln`, the exits and the gate under `head_loss` — in
    the compiled step's operation names, which are what a device trace
    carries: a recomputed layer stands behind a `jit` of its own since
    PR 35 (`remat.kept`), one private function of the lowered
    module for all its applications, and XLA, inlining the calls, puts
    each call's pass and layer before the names inside."""
    sizes, cfg = _toy()
    params = weights_ouro.device_init(sizes, 1)
    tok, lab = _batch(sizes, 1)
    text = jax.jit(tfm.make_train_step(
        cfg, AX, tfm.param_specs(cfg, AX))).lower(
            params, tok, lab).compile().as_text()

    def has(path):  # `a/b` as jax writes it: `jvp(a)/b`, `a/jit(layer)/b`
        return re.search(path.replace("/", r"\)*/(jit\(layer\)/)?"),
                         text) is not None

    for s in range(4):
        for part in ("ln", "attn_proj", "attn_core", "mlp"):
            assert has(f"loop_{s}/layer_1/{part}"), (s, part)
        assert has(f"head_loss/exit_{s}/btd,vd->btv")
        assert has(f"head_loss/exit_{s}/checkpoint/rematted_computation")
    assert has("head_loss/exit_gate/btd,d->bt")
    assert has("loop_2/ln") and has("head_loss/ln")
    assert not has("loop_3/ln") and not has("loop_4")
    assert not has("head_loss/exit_4")
    # a layer's four per-pass gradients are summed under its own name
    assert has("loop_0/layer_1/add_any")
    # a stack run once carries no pass name
    _, once = _toy(loops=1, exit_gate=False, post_norm=False)
    shapes = jax.eval_shape(lambda: tfm.init_params(
        np.random.default_rng(0), once))
    text = jax.jit(tfm.make_train_step(
        once, AX, tfm.param_specs(once, AX))).lower(
            shapes, tok, lab).compile().as_text()
    assert not has("loop_0") and has("layer_1/mlp")


@pytest.mark.parametrize("over", [dict(), dict(attn="mla", q_lora_rank=32,
                                               kv_lora_rank=16,
                                               qk_nope_dim=12, qk_rope_dim=4,
                                               v_head_dim=16)],
                         ids=["mha", "mla"])
def test_the_trees_agree_and_the_output_norm_is_applied(over):
    """init_params, param_specs and grad_extra_axes build one tree;
    with `post_norm` a sub-layer's output reaches the stream through
    its own norm, whatever the attention's kind."""
    _, cfg = _toy(loops=2, **over)
    params = tfm.init_params(np.random.default_rng(0), cfg)
    assert jax.tree.structure(params) == jax.tree.structure(
        tfm.param_specs(cfg, AX), is_leaf=lambda s: not isinstance(
            s, (dict, list))) == jax.tree.structure(
        tfm.grad_extra_axes(cfg, AX))
    assert {"ln1_post", "ln2_post"} <= set(params["layers"][0])
    assert set(params["exit_gate"]) == {"w", "b"}
    lp = jax.tree.map(jnp.asarray, params["layers"][0])
    h = jax.random.normal(jax.random.key(0), (1, 16, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        out = tfm.layer_forward(lp, h, cfg, AX, False)
        zero = dict(lp, ln2_post={"g": jnp.zeros(cfg.d_model)})
        half = tfm.layer_forward(zero, h, cfg, AX, False)
        zero = dict(zero, ln1_post={"g": jnp.zeros(cfg.d_model)})
        none = tfm.layer_forward(zero, h, cfg, AX, False)
    np.testing.assert_array_equal(none, h)  # both outputs gated off
    # each output joins the stream at RMS 1 (gain 1): the norm is there
    for a, b in ((half, h), (out, half)):
        rms = np.sqrt(np.mean(np.square(np.asarray(a - b)), -1))
        np.testing.assert_allclose(rms, 1.0, rtol=1e-3)


@pytest.mark.parametrize("over", [dict(), dict(loops=1),
                                  dict(exit_gate=False)],
                         ids=["both", "exits-only", "loops-only"])
def test_what_an_axis_cannot_give_yet_raises(over):
    _, cfg = _toy(**over)
    with pytest.raises(NotImplementedError, match="Queue 2a"):
        tfm._check_supported(cfg, tfm.Axes(pp="x"), False, 0)
    tfm._check_supported(cfg, tfm.Axes(dp="x", tp="y"), False, 0)


# -- what was there before ---------------------------------------------------------

#: sha256 of the lowered text of toy train steps at the parent commit
#: 4f8c89c (jax 0.9.0, CPU, batch 2 x 64): the `glm-5` rehearsal step
#: (`glm5_train.build_step(...).lower(...).as_text()`), and this file's
#: toy run ONCE without gate or output norms — a plain RMSNorm / RoPE /
#: gated-FFN decoder with an untied head, recomputed layers. PR 33
#: (rows of a held share bounded, `ops/moe.held_rows_bound`) left both
#: `glm-5` texts as they were: the rehearsal holds 4 of 16 experts, so
#: SLACK (4) shares cover the layer, its bound is all 512 rows and no
#: second path exists — nothing to re-record. PR 35 left all four as
#: they were: they are the texts without the residuals' names and
#: without the `jit` of its own that a recomputed layer stands behind
#: since (models/remat.py's `kept`; XLA inlines it). PR 44 re-recorded
#: the two `glm-5` texts (ba6f5504... and 39d35e4e... before it): the
#: rehearsal's layer is the FULL one, and that moves its rows as the
#: bounded one does since (`ops/moe._held_rows` at ``bound = T * k``).
#: PR 45 re-recorded all four: `_token_nll` (both of `glm-5`'s heads, and
#: `once`, which took the first loss body until then) reads the label's
#: logit by a mask (1dac97dc..., 39047cb2..., 7b780c71... and a0b63b53...
#: before it)
PARENT = {
    ("glm-5", "bfloat16"):
        "ab7ced94b29c0989d30f919c4f9870d57e4b11aa611ff11dabebbc1dcc5a2d7c",
    ("glm-5", "float32"):
        "a2c06cea55873db87cdf40ad14ff077e325e0594ec4932909e4dc33166ba5161",
    ("once", "bfloat16"):
        "5b6a94459e35024a4a1ec8cc4e2397768f807ed8c9731cb90bb4707321b8841c",
    ("once", "float32"):
        "5333a47d2900259c96da1d7f18dea8ddc314326cae7b90f582755d10ac32baa2",
}


@pytest.mark.parametrize("name, dtype", sorted(PARENT))
def test_with_the_fields_at_their_defaults_the_step_is_the_parents(
        name, dtype, monkeypatch):
    """Both steps recompute their layers (`remat=True`) and the CPU
    states no memory limit, so nothing is kept: with the recomputed
    layer called as it is (`remat.recomputed`, not behind
    `remat.kept`'s `jit`) and the residuals' names taken out, the
    text is the parent's, raw; the names move jax's numbering of its
    private functions and nothing else (tests/lowered_text.py); behind
    the `jit` each layer kind is a private function of the module,
    called once per application."""
    if jax.__version__ != "0.9.0":
        pytest.skip("the recorded text is jax 0.9.0's")
    if name == "glm-5":
        with open(os.path.join(HERE, "benchmark", "configs",
                               "glm-5.rehearsal.json")) as f:
            config = json.load(f)
        config["param_dtype"] = dtype
        sizes = glm5_train.model_sizes(config)
        toks, labs = weights.batches(sizes["vocab"], 1, 2, 64, 1)

        def text():
            return glm5_train.build_step(sizes, 0.01).lower(
                weights_glm5.device_init(sizes, 1), toks[0],
                labs[0]).as_text()
    else:
        _, cfg = _toy(dtype, loops=1, post_norm=False, exit_gate=False,
                      exit_entropy_weight=0.0)
        cfg = tfm.Config(**{**cfg.__dict__, "dtype": jnp.bfloat16})
        shapes = jax.eval_shape(lambda: tfm.init_params(
            np.random.default_rng(0), cfg))
        tok = jax.ShapeDtypeStruct((2, 64), jnp.int32)

        def text():
            return jax.jit(
                tfm.make_train_step(cfg, AX, tfm.param_specs(cfg, AX)),
                donate_argnums=(0,)).lower(shapes, tok, tok).as_text()

    shipped = text()
    assert re.search(r"call @layer\w*\(", shipped)
    monkeypatch.setattr(remat, "kept", remat.recomputed)
    named = text()
    lowered_text.without_names(monkeypatch)
    bare = text()
    assert lowered_text.sha256(bare) == PARENT[name, dtype]
    assert lowered_text.canonical(named) == lowered_text.canonical(bare)


def test_the_seeded_tree_is_init_params_tree():
    sizes, cfg = _toy("bfloat16")
    lib = tfm.init_params(np.random.default_rng(0), cfg)
    mine = weights_ouro.device_init(sizes, 0)
    sig = functools.partial(jax.tree.map,
                            lambda a: (tuple(a.shape), str(a.dtype)))
    assert sig(lib) == sig(mine)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(lib),
                            jax.tree.leaves(mine)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if a.std() > 0 and a.size > 500:  # same scale, not the same draw
            assert 0.8 < b.std() / a.std() < 1.25, jax.tree_util.keystr(path)
        elif a.std() == 0:
            assert (a == b).all()
    assert sum(ot.gate_leaves(sizes)) == 2
    # made again, the seed's tree is the same bits; another seed's is not
    again = weights_ouro.device_init(sizes, 0)
    assert all((np.asarray(a) == np.asarray(b)).all() for a, b in zip(
        jax.tree.leaves(mine), jax.tree.leaves(again)))
    other = weights_ouro.device_init(sizes, 2**31 + 1)
    assert not (np.asarray(other["embed"]) == np.asarray(mine["embed"])).all()
