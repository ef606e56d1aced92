"""What a recomputed layer application keeps for its backward pass
(`Config.remat`: models/transformer.py's `_Recomputed` over
models/remat.py's `Recomputed`, the rule `remat_keep`, the names of
ops/attention.py and remat.py): any
set of kept names gives the empty set's loss and gradients bit for
bit, the policy saves exactly what the rule names and reckons, the
rule itself, the programs that do not recompute are the parent's, and
the counters."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals

from benchmark import (weights, weights_glm5, weights_kimivl,
                       weights_nemotron, weights_ouro)
from benchmark.runners import (glm5_train, kimivl_train, nemotron_train,
                               ouro_train, train_step)
from ompi_tpu.core import pvar
from ompi_tpu.models import remat
from ompi_tpu.models import transformer as tfm
from ompi_tpu.ops import attention as att
from tests import lowered_text

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
AX = tfm.Axes()
CELLS = {"ouro": (ouro_train, weights_ouro, "ouro-2.6b"),
         "glm5": (glm5_train, weights_glm5, "glm-5"),
         "kimivl": (kimivl_train, weights_kimivl, "kimi-vl-a3b"),
         "nemotron": (nemotron_train, weights_nemotron,
                      "nemotron-3-nano-30b-a3b")}
#: every name there is
ALL = (tfm.ATTN_PROJ_OUT, att.DSA_PROBS, att.ATTN_OUT, tfm.MLA_LATENTS,
       tfm.MLP_OUT, tfm.DSA_SELECT, att.QKV, tfm.MLP_UP)
#: the names each cell's layers make, in the rule's order at the
#: published widths (GLM-5: no norm follows its FFN, so nothing reads
#: that output backwards)
ORDER = {
    "ouro": (tfm.MLP_OUT, att.ATTN_OUT, tfm.ATTN_PROJ_OUT, att.QKV,
             tfm.MLP_UP),
    "glm5": (tfm.ATTN_PROJ_OUT, tfm.MLA_LATENTS, tfm.MLP_UP, att.DSA_PROBS,
             att.ATTN_OUT, att.QKV, tfm.DSA_SELECT),
}
GB = 10 ** 9


def _config(model, rehearsal=True, dtype="float32"):
    runner, _, name = CELLS[model]
    with open(os.path.join(HERE, "benchmark", "configs", name + (
            ".rehearsal.json" if rehearsal else ".json"))) as f:
        config = json.load(f)
    config["param_dtype"] = dtype
    sizes = runner.model_sizes(config)
    cfg = runner.program_config(sizes)
    return sizes, tfm.Config(**{**cfg.__dict__, "dtype": jnp.dtype(dtype)})


def _names(model, rehearsal=True):
    """The names in the rule's order, at the toy widths and shape of
    these tests or at the cell's."""
    _, cfg = _config(model, rehearsal,
                     "float32" if rehearsal else "bfloat16")
    return tuple(name for name, _ in (
        remat.remat_order(tfm.step_costs(cfg, 2, 64)[0]) if rehearsal
        else remat.remat_order(tfm.step_costs(cfg, 1, 4096)[0])))


def _force(monkeypatch, keep):
    monkeypatch.setattr(tfm, "_remat_names", lambda params, tokens, cfg: keep)


def _loss_and_grads(model, seed=3):
    sizes, cfg = _config(model)
    toks, labs = weights.batches(sizes["vocab"], 1, 2, 64, seed)
    params = CELLS[model][1].device_init(sizes, seed)

    def mean_loss(p):
        nll, cnt = tfm.loss_local(p, toks[0], labs[0], cfg, AX)
        return nll / cnt

    loss, grads = jax.jit(jax.value_and_grad(mean_loss))(params)
    return np.asarray(loss), [
        (jax.tree_util.keystr(path), np.asarray(g))
        for path, g in jax.tree_util.tree_leaves_with_path(grads)]


_whole = {}


@pytest.mark.parametrize("model", sorted(ORDER))
def test_the_order_is_read_from_the_widths(model):
    """The dearest name first: by the products' operations a name
    spares per byte it holds, over all the step's applications, of
    equals the smaller first — at the cell's widths the order PR 35
    ships, at the toy widths the same names in the toy's order."""
    assert _names(model, rehearsal=False) == ORDER[model]
    assert sorted(_names(model)) == sorted(ORDER[model])
    _, cfg = _config(model, rehearsal=False, dtype="bfloat16")
    kinds = tfm._application_kinds(cfg)
    worth = []
    for name, held in remat.remat_order(tfm.step_costs(cfg, 1, 4096)[0]):
        costs = [tfm.layer_costs(cfg, 1, 4096, moe) for moe in kinds]
        assert held == sum(c.sizes.get(name, 0) for c in costs)
        spared = sum(c.spared[name] for c in costs if name in c.sizes)
        worth.append((-spared / held, held))
    assert worth == sorted(worth)
    # a product's result is worth 2 x the contracted width / item size
    per = dict(zip(ORDER[model], worth))
    assert per[tfm.ATTN_PROJ_OUT][0] == -(
        cfg.n_heads * (cfg.v_head_dim or cfg.head_dim))
    # wider, the same name is dearer: nothing reads a model's name
    wide = tfm.Config(**{**cfg.__dict__, "d_ff": 8 * cfg.d_ff,
                         "post_norm": True})
    assert remat.remat_order(tfm.step_costs(wide, 1, 4096)[0])[0][0] \
        == tfm.MLP_OUT


@pytest.mark.parametrize("model, kept", [
    (model, k) for model in sorted(ORDER)
    for k in range(1, len(ORDER[model]) + 1)])
def test_any_kept_prefix_is_the_whole_recomputation_bit_for_bit(
        model, kept, monkeypatch, pvar_clean):
    """The toy train step's loss and every gradient leaf with the first
    `kept` names of the order kept, against every application
    recomputed from its input alone (today's `remat=True`), in float32
    (in bfloat16 XLA reads a recomputed value before its rounding where
    it fuses the producer into the reader, `xla_allow_excess_precision`:
    a kept value is the rounded one). ONE exception, to float32's last
    bits: the indexer's leaves once the head-summed probabilities are
    kept — recomputed, their row sums fuse into the indexer's loss and
    add in another order (4.6e-7 of the leaf's norm at most)."""
    if model not in _whole:
        _force(monkeypatch, ())
        _whole[model] = _loss_and_grads(model)
        assert pvar.read("remat_kept_applications") == 0
    names = _names(model)[:kept]
    _force(monkeypatch, names)
    before = pvar.read("remat_kept_applications")
    loss, grads = _loss_and_grads(model)
    assert pvar.read("remat_kept_applications") > before
    want_loss, want = _whole[model]
    assert loss == want_loss
    assert len(grads) == len(want)
    for (path, got), (_, exp) in zip(grads, want):
        if att.DSA_PROBS in names and "['wi_" in path:
            assert np.linalg.norm(got - exp) <= 2e-6 * np.linalg.norm(exp)
        else:
            assert np.array_equal(got, exp), path


def _residuals(model, layer, keep):
    """(bytes of what one checkpointed application saves beside its
    arguments, were the arguments all saved as they came) of the toy
    model's layer `layer`."""
    _, cfg = _config(model)
    shapes = jax.eval_shape(
        lambda: tfm.init_params(np.random.default_rng(0), cfg))
    lp = shapes["layers"][layer]
    h = jax.ShapeDtypeStruct((2, 64, cfg.d_model), cfg.dtype)
    moe = tfm._is_moe(cfg, layer)

    def apply(lp, h):
        index_aux = []  # the indexer's loss reads the probabilities
        out = tfm._Recomputed(cfg, AX, keep)(lp, h, moe, 0, None, index_aux)
        return out.sum() + sum(kl for kl, _ in index_aux)

    saved = saved_residuals(apply, lp, h)
    made = [aval for aval, why in saved if "from the argument" not in why]
    return (sum(a.size * a.dtype.itemsize for a in made),
            tfm.layer_costs(cfg, 2, 64, moe).sizes, cfg)


@pytest.mark.parametrize("model, layer", [("ouro", 0), ("glm5", 0),
                                          ("glm5", 1)])
def test_the_policy_saves_what_the_rule_names_and_nothing_else(model, layer):
    """`saved_residuals` of one checkpointed layer: with no name kept
    only the layer's arguments; each name of the order adds what the
    rule reckons for it (less the kernels' log-sum-exp, which the CPU's
    attention does not make) — to the byte in the dense decoder; in a
    latent-attention layer at least that (jax lists a kept value again
    for every jitted reader that passes it on: `_where`, `silu`) — and
    a name no backward pass reads (the FFN's output before a bare
    residual add) adds nothing."""
    made, sizes, cfg = _residuals(model, layer, ())
    assert made == 0
    lse = 2 * 64 * cfg.n_heads * 4
    for k, name in enumerate(ALL):
        keep = ALL[:k + 1]
        want = sizes.get(name, 0) - (lse if name == att.ATTN_OUT else 0)
        before, made = made, _residuals(model, layer, keep)[0]
        if model == "ouro" or not want:
            assert made - before == want, (name, made - before, want)
        else:
            assert made - before >= want, (name, made - before, want)


def _made(f, *args):
    return sorted(a.shape for a, why in saved_residuals(f, *args)
                  if "argument" not in why and "constant" not in why)


@pytest.mark.parametrize("kernel", ["splash", "dsa"])
def test_the_kernels_forward_rules_name_their_residuals(kernel, monkeypatch):
    """The TPU's paths in interpret mode under a policy: the output
    and the log-sum-exp are named INSIDE the kernels' forward rules
    (the library's `residual_checkpoint_name`, `_dsa_kernels.fwd`), q,
    k and v in the kernels' head-major layout; kept or made again, the
    gradients are the same to the bit."""
    rng = np.random.default_rng(0)
    t, h, d = 256, 2, 128
    if kernel == "splash":
        shape, o, lse = (1, t, h, d), (1, h, t, d), (1, h, t)

        def loss(q, k, v):
            return (att.blockwise_mha(q, k, v, 128, interpret=True)
                    ** 2).sum()
    else:
        from ompi_tpu.ops import sparse_attention as sa

        shape, o, lse = (t, h, d), (h, t, d), (h, 1, t)
        monkeypatch.setattr(att, "dsa_tile",
                            lambda *a, **kw: sa.Tiles(128, 128, 2, 2))
        keep = jnp.tril(jnp.ones((t, t), bool))

        def loss(q, k, v):
            return (att.dsa_attend(q, k, v, keep, 0.1, interpret=True)[0]
                    ** 2).sum()

    q, k, v = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
               for _ in range(3))
    save = jax.checkpoint_policies.save_only_these_names
    whole = jax.checkpoint(loss)
    assert _made(whole, q, k, v) == []
    want = jax.jit(jax.grad(whole, argnums=(0, 1, 2)))(q, k, v)
    for names, made in [((att.ATTN_OUT,), [lse, o]),
                        ((att.ATTN_OUT, att.QKV), [lse] + [o] * 4)]:
        kept = jax.checkpoint(loss, policy=save(*names))
        assert _made(kept, q, k, v) == sorted(made)
        got = jax.jit(jax.grad(kept, argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(got, want):
            assert np.array_equal(a, b)


# -- the rule -----------------------------------------------------------------

def _cell(model):
    """(config at the published widths, parameter bytes) of a cell."""
    sizes, cfg = _config(model, rehearsal=False, dtype="bfloat16")
    shapes = jax.eval_shape(lambda: CELLS[model][1].device_init(sizes, 1))
    return cfg, sum(x.size * x.dtype.itemsize
                    for x in jax.tree.leaves(shapes))


def _reckoned(cfg, b, t, keep):
    return sum(tfm.layer_costs(cfg, b, t, moe).sizes.get(name, 0)
               for moe in tfm._application_kinds(cfg) for name in keep)


@pytest.mark.parametrize("model", sorted(ORDER))
def test_the_rule(model):
    cfg, params = _cell(model)
    # no limit stated (the CPU), none that allows it: today's program
    costs = tfm.step_costs(cfg, 1, 4096, params)
    assert remat.remat_keep(*costs, None) == ()
    assert remat.remat_keep(*costs, 0) == ()
    assert remat.remat_keep(*costs, params) == ()
    # monotone in the limit, a prefix of the order, never past its share
    last = ()
    for limit in range(4 * GB, 200 * GB, GB):
        keep = remat.remat_keep(*costs, limit)
        assert keep == ORDER[model][:len(keep)]
        assert len(keep) >= len(last)
        assert params + _reckoned(cfg, 1, 4096, keep) \
            <= tfm.REMAT_SHARE * limit or not keep
        last = keep
    assert last == ORDER[model]  # room for everything: everything
    # equal for equal shapes: nothing reads a model's name, and more
    # tokens never keep more
    again = tfm.Config(**cfg.__dict__)
    for limit in (12 * GB, 16 * GB, 32 * GB):
        keep = remat.remat_keep(*costs, limit)
        assert remat.remat_keep(
            *tfm.step_costs(again, 1, 4096, params), limit) == keep
        assert len(remat.remat_keep(
            *tfm.step_costs(cfg, 2, 4096, params), limit)) <= len(keep)


#: a v5e's `memory_stats()["bytes_limit"]` (my chip runs PR 35)
V5E_LIMIT = 16_909_336_064


@pytest.mark.parametrize("model, seq, patches, ships", [
    ("ouro", 4096, 0, ORDER["ouro"][:4]),  # the up-projections do not fit
    ("glm5", 4096, 0, ORDER["glm5"][:5]),  # nor do q, k and v, 2.4 GB
    # the tower's 27 blocks over 12,288 patches beside the decoder's 4
    # layers: q, k and v do not fit (read from PR 41's rule, PR 42)
    ("kimivl", 4096, 12288, (att.ATTN_OUT, tfm.MLA_LATENTS, tfm.MLP_UP,
                             tfm.ATTN_PROJ_OUT)),
    # everything a pattern's layers name, the gradients reckoned at the
    # largest layer's (an expert layer's) parameters
    ("nemotron", 8192, 0, (att.ATTN_OUT, tfm.MLP_UP, "ssm_in", att.QKV,
                           "ssm_y", "ssm_conv")),
])
def test_the_sets_the_cells_ship_with(model, seq, patches, ships):
    """The four recomputing cells' shapes against a v5e's limit: the
    keep-sets PR 35 measured for ouro-train-t4096 and glm5-train-t4096,
    and those kimivl-train-t4096 and nemotron-train-t8192 ran with at
    PR 41, read from that commit's rule before PR 42 folded the cost
    tables."""
    sizes, cfg = _config(model, rehearsal=False, dtype="bfloat16")
    shapes = jax.eval_shape(lambda: CELLS[model][1].device_init(sizes, 1))

    def nbytes(tree):
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))

    largest = max(map(nbytes, shapes["layers"])) \
        if cfg.layer_pattern is not None else None
    assert remat.remat_keep(*tfm.step_costs(
        cfg, 1, seq, nbytes(shapes), patches, largest), V5E_LIMIT) == ships


#: parameters + temporaries of the two cells' steps with the shipped
#: sets, as the TPU's compiler reckons them (`memory_analysis()` of the
#: compile for a described v5e, PR 35; the chip's runs read the same)
COMPILED = {"ouro": 12_694_000_000, "glm5": 13_019_000_000}


@pytest.mark.parametrize("model", sorted(COMPILED))
def test_the_room_covers_what_the_reckoning_misses(model):
    """`REMAT_SHARE` leaves the compiler the rest of the limit: twice
    the widest gap seen between the rule's reckoning and the compiled
    peak (0.97 GB: Ouro's, whose float32 copies of the kept outputs the
    rule does not count), and both cells' compiled peaks stand under
    the 14.5 GB their ISSUE allowed."""
    cfg, params = _cell(model)
    costs = tfm.step_costs(cfg, 1, 4096, params)
    keep = remat.remat_keep(*costs, V5E_LIMIT)
    reckoned = remat.whole_step_peak(*costs) \
        + _reckoned(cfg, 1, 4096, keep)
    room = (1 - tfm.REMAT_SHARE) * V5E_LIMIT
    assert abs(COMPILED[model] - reckoned) <= room / 2
    assert reckoned <= tfm.REMAT_SHARE * V5E_LIMIT
    assert COMPILED[model] <= 14.5 * GB


def test_the_limit_is_the_devices_own(monkeypatch):
    """`_remat_names` asks the device; the CPU states no limit, so
    nothing is kept; where one is stated the rule answers, and a config
    that does not recompute is never asked."""
    sizes, cfg = _config("ouro")
    params = jax.eval_shape(
        lambda: tfm.init_params(np.random.default_rng(0), cfg))
    tok = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    assert remat._memory_limit() is None
    assert tfm._remat_names(params, tok, cfg) == ()
    monkeypatch.setattr(remat, "_memory_limit", lambda: GB)
    assert tfm._remat_names(params, tok, cfg) == _names("ouro")
    plain = tfm.Config(**{**cfg.__dict__, "remat": False})
    assert tfm._remat_names(params, tok, plain) == ()


# -- what does not recompute is the parent's program --------------------------

def test_without_remat_the_names_leave_no_operation(monkeypatch):
    """An OPT toy step (`remat=False`) lowers to the same operations
    with the names and with `checkpoint_name` taken out of both
    modules (jax's numbering of private functions apart: the `name`
    primitive lowers to its operand)."""
    with open(os.path.join(HERE, "benchmark", "configs",
                           "opt-30b.rehearsal.json")) as f:
        config = json.load(f)
    sizes = train_step.model_sizes(config)
    toks, labs = weights.batches(sizes["vocab"], 1, 2, 64, 1)
    params = weights.device_init(sizes, 1)

    def text():
        return train_step.build_step(sizes, 0.01).lower(
            params, toks[0], labs[0]).as_text()

    named = text()
    assert named.count("stablehlo.") > 100
    lowered_text.without_names(monkeypatch)
    bare = text()
    assert lowered_text.canonical(named) == lowered_text.canonical(bare)


# -- the counters -------------------------------------------------------------

@pytest.mark.parametrize("keep", [(), (tfm.ATTN_PROJ_OUT, att.ATTN_OUT)])
def test_one_record_per_traced_application(keep, monkeypatch, pvar_clean):
    """Every application counts once — the rule's three counters and
    what layer_forward counts of itself, though jax traces the one
    jitted layer (`remat.kept`) once for all of a kind's applications;
    and a second trace of the step counts what the first did."""
    sizes, cfg = _config("ouro")
    _force(monkeypatch, keep)
    shapes = jax.eval_shape(
        lambda: tfm.init_params(np.random.default_rng(0), cfg))
    tok = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    applications = cfg.n_layers * cfg.loops
    per = tfm.layer_costs(cfg, 2, 64, False).sizes
    for traces in (1, 2):
        jax.jit(tfm.make_train_step(
            cfg, AX, tfm.param_specs(cfg, AX))).lower(shapes, tok, tok)
        assert pvar.read("loop_layer_applications") == traces * applications
        assert (pvar.read("remat_kept_applications"),
                pvar.read("remat_whole_applications"),
                pvar.read("remat_kept_bytes")) == tuple(
            traces * n for n in (
                (applications, 0, applications * sum(per[n] for n in keep))
                if keep else (0, applications, 0)))
        assert pvar.read("attn_reference_layers") == traces * applications
    # a config that does not recompute counts neither
    pvar.reset()
    plain = tfm.Config(**{**cfg.__dict__, "remat": False})
    jax.jit(tfm.make_train_step(plain, AX, tfm.param_specs(plain, AX))).lower(
        shapes, tok, tok)
    assert pvar.read("remat_kept_applications") == 0
    assert pvar.read("remat_whole_applications") == 0
    assert pvar.read("attn_reference_layers") == applications


def test_a_capture_takes_its_threads_records_and_no_others(pvar_clean):
    import threading

    pvar.record("attn_mla_layers")
    with pvar.captured() as outer:
        pvar.record("attn_mla_layers", 2)
        with pvar.captured() as inner:
            pvar.record("attn_dsa_layers")
        other = threading.Thread(target=pvar.record,
                                 args=("attn_dsa_layers", 5))
        other.start()
        other.join()
        pvar.record("attn_dsa_layers", inner["attn_dsa_layers"])
    assert inner == {"attn_dsa_layers": 1}
    assert outer == {"attn_mla_layers": 2, "attn_dsa_layers": 1}
    assert pvar.read("attn_mla_layers") == 1
    assert pvar.read("attn_dsa_layers") == 5
    pvar.record("attn_mla_layers")
    assert pvar.read("attn_mla_layers") == 2
