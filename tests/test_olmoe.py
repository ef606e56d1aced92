"""OLMoE through the one `layer_forward`: the program against the plain
reference (benchmark/reference/olmoe_decoder.py: float32, `highest`,
every expert on every token, no sort) on seeded weights at toy widths —
d 64, 4 heads of 16, 8 experts of width 32, top-2, vocabulary 128,
T 32, 2 layers.

Tolerances. The program computing in float32 differs from the reference
by float32 rounding alone: a few hundred roundings of 6e-8 on numbers
of order 1 through two layers, so 2e-5 absolute on logits, 1e-5
relative on losses and 2e-5 on a gradient leaf against its largest
entry (measured: 5e-6, 1e-7, 2e-6). A bfloat16 computation misses the
logits' tolerance by two orders of magnitude (`test_bfloat16_...`
shows it does), so lower precision where float32 is stated fails.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from benchmark import compare  # noqa: E402
from benchmark.reference import olmoe_decoder as ref  # noqa: E402
from ompi_tpu.core import pvar  # noqa: E402
from ompi_tpu.models import transformer as tfm  # noqa: E402
from ompi_tpu.ops import moe  # noqa: E402
from ompi_tpu.parallel import make_mesh  # noqa: E402
from ompi_tpu.util import jaxcompat  # noqa: E402

TOY = dict(vocab=128, d_model=64, n_layers=2, n_heads=4, d_ff=32,
           max_seq=64, moe_every=1, n_experts=8, top_k=2, mlp_act="silu",
           mlp_gated=True, norm="rmsnorm", pos="rope", qk_norm=True,
           tie_head=False, router_aux_weight=0.01, router_z_weight=0.001)
CFG = tfm.Config(dtype=jnp.float32, **TOY)
SPEC = ref.Spec(n_heads=4, top_k=2)
AX = tfm.Axes()
LOGITS_ATOL, LOSS_RTOL, GRAD_RTOL = 2e-5, 1e-5, 2e-5


def _params(seed=0, cfg=CFG):
    """Seeded weights with gains that differ per column (all-ones gains
    would hide a norm over the wrong axis)."""
    p = tfm.init_params(np.random.default_rng(seed), cfg)
    rng = np.random.default_rng(seed + 100)
    return jax.tree.map(
        lambda a: jnp.asarray(a * rng.uniform(0.5, 1.5, a.shape)
                              if a.ndim == 1 else a, jnp.float32), p)


def _batch(seed=1, b=2, t=32):
    tok = np.random.default_rng(seed).integers(0, 128, (b, t))
    tok = jnp.asarray(tok, jnp.int32)
    return tok, jnp.roll(tok, -1, axis=1)


def _loss(p, tok, lab, cfg=CFG):
    nll, cnt = tfm.loss_local(p, tok, lab, cfg, AX)
    return nll / cnt


# -- the whole model -----------------------------------------------------------

def test_logits_match_the_reference_in_float32():
    p, (tok, _) = _params(), _batch()
    got = tfm.forward_local(p, tok, CFG, AX)
    np.testing.assert_allclose(got, ref.logits(p, tok, SPEC),
                               atol=LOGITS_ATOL, rtol=0)


def test_loss_with_both_router_losses_matches_the_reference():
    p, (tok, lab) = _params(), _batch()
    want = float(ref.loss(p, tok, lab, SPEC))
    assert abs(float(_loss(p, tok, lab)) - want) < LOSS_RTOL * want
    # the two router terms are in it: without them the loss is smaller
    # by what the reference says they are
    bare = tfm.Config(dtype=jnp.float32, **dict(
        TOY, router_aux_weight=0.0, router_z_weight=0.0))
    bare_ref = ref.loss(p, tok, lab, SPEC._replace(balance_weight=0.0,
                                                   z_weight=0.0))
    assert abs(float(_loss(p, tok, lab, bare)) - float(bare_ref)) \
        < LOSS_RTOL * want
    assert want - float(bare_ref) > 5e-3  # ~0.01 x 1 + 0.001 x lse^2


@pytest.mark.parametrize("which, weight", [("balance", "router_aux_weight"),
                                           ("z", "router_z_weight")])
def test_each_router_loss_alone_matches_the_reference(which, weight):
    p, (tok, lab) = _params(3), _batch(4)
    cfg = tfm.Config(dtype=jnp.float32, **{
        **TOY, "router_aux_weight": 0.0, "router_z_weight": 0.0,
        weight: 0.5})
    spec = SPEC._replace(balance_weight=0.5 * (which == "balance"),
                         z_weight=0.5 * (which == "z"))
    want = float(ref.loss(p, tok, lab, spec))
    assert abs(float(_loss(p, tok, lab, cfg)) - want) < LOSS_RTOL * want


_LEAVES = ["embed", "head", "ln_f", "ln1", "ln2", "q_norm", "k_norm",
           "wq", "wk", "wv", "wo", "wg", "w1", "w3", "w2"]


@pytest.fixture(scope="module")
def both_gradients():
    p, (tok, lab) = _params(), _batch()
    return (jax.grad(lambda q: _loss(q, tok, lab))(p),
            jax.grad(lambda q: ref.loss(q, tok, lab, SPEC))(p))


@pytest.mark.parametrize("leaf", _LEAVES)
def test_gradient_of_every_leaf_matches_the_reference(both_gradients, leaf):
    """The router's `wg` included: its gradient comes through the
    top-k weights and through both router losses."""
    got, want = both_gradients
    pick = lambda g: (  # noqa: E731
        [g[leaf]] if leaf in g else [lp[leaf] for lp in g["layers"]])
    for a, b in zip(jax.tree.leaves(pick(got)), jax.tree.leaves(pick(want))):
        assert float(jnp.abs(b).max()) > 0
        assert float(jnp.abs(a - b).max()) < GRAD_RTOL * float(
            jnp.abs(b).max())


def test_three_sgd_steps_match_the_reference():
    p, start = _params(5), _params(5)
    r = jax.tree.map(jnp.copy, p)
    step = jax.jit(tfm.make_train_step(CFG, AX, tfm.param_specs(CFG, AX),
                                       lr=0.01))
    for i in range(3):
        tok, lab = _batch(10 + i)
        p, loss = step(p, tok, lab)
        r, want = ref.sgd_step(r, tok, lab, 0.01, SPEC)
        assert abs(float(loss) - float(want)) < LOSS_RTOL * float(want)
    # every leaf moved as far as the reference moved it: 1e-4 of the
    # leaf's own movement (three float32 steps)
    assert compare.worst_leaf_gap(compare.leaf_delta_norms(p, start),
                                  compare.leaf_delta_norms(r, start)) < 1e-4


def test_bfloat16_misses_the_float32_tolerances_and_stays_close():
    """The stated tolerance for a bfloat16 computation: logits of order
    1 carry 8 bits (4e-3 a rounding, a few dozen of them: 0.1), the
    mean loss 1%. The same run misses the float32 tolerance by far —
    so lower precision where float32 is stated fails."""
    p, (tok, lab) = _params(), _batch()
    low = tfm.Config(dtype=jnp.bfloat16, **TOY)
    want = ref.logits(p, tok, SPEC)
    err = float(jnp.abs(tfm.forward_local(p, tok, low, AX) - want).max())
    assert 100 * LOGITS_ATOL < err < 0.1
    loss, ref_loss = float(_loss(p, tok, lab, low)), float(
        ref.loss(p, tok, lab, SPEC))
    assert abs(loss - ref_loss) < 0.01 * ref_loss


# -- the parts, one claim each -------------------------------------------------

def _moe_inputs(seed, t=48, d=16, f=8, e=8):
    rng = np.random.default_rng(seed)
    n = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    return n(t, d), n(d, e), n(e, d, f), n(e, d, f), n(e, f, d)


def _reference_moe(x, wg, w1, w3, w2, spec):
    with jax.default_matmul_precision("highest"):
        lp = {"wg": wg, "w1": w1, "w2": w2}
        if w3 is not None:
            lp["w3"] = w3
        return ref.moe(x, lp, spec)[0]


@pytest.mark.parametrize("k", [1, 2, 8])
def test_every_token_routed_to_the_same_experts_is_still_exact(k):
    """A router built so that ALL tokens choose the same k experts: a
    capacity of 1.25 x tokens x k / experts would drop most of them;
    the sorted path drops none (and the other experts' groups are
    empty)."""
    x, wg, w1, w3, w2 = _moe_inputs(7)
    wg = jnp.zeros_like(wg).at[:, :k].set(0.01 * wg[:, :k])
    x = x.at[:, 0].set(1.0)
    wg = wg.at[0, :k].add(10.0 + jnp.arange(k))  # the k favoured ones
    route = moe.topk_routing(x @ wg, k)
    assert np.array_equal(np.asarray(route.counts),
                          [x.shape[0]] * k + [0] * (8 - k))
    got = moe.sorted_moe_ffn(x, route, w1, w3, w2, "silu")
    want = _reference_moe(x, wg, w1, w3, w2, ref.Spec(1, k))
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-5)


def test_the_k_weights_are_not_renormalised_unless_the_config_says_so():
    x, wg, w1, w3, w2 = _moe_inputs(8)
    wg = 0.2 * wg  # logits of order 1: two of eight sum to well under 1
    route = moe.topk_routing(x @ wg, 2)
    sums = np.asarray(route.weights.sum(-1))
    assert (sums < 0.9).all() and (sums > 0.25).all()
    probs = np.asarray(jax.nn.softmax(x @ wg, -1))
    np.testing.assert_allclose(np.sort(route.weights, -1),
                               np.sort(probs, -1)[:, -2:], rtol=1e-6)
    got = moe.sorted_moe_ffn(x, route, w1, w3, w2, "silu")
    np.testing.assert_allclose(
        got, _reference_moe(x, wg, w1, w3, w2, ref.Spec(1, 2)),
        atol=1e-4, rtol=1e-5)
    renorm = moe.topk_routing(x @ wg, 2, renormalize=True)
    np.testing.assert_allclose(renorm.weights.sum(-1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(
        moe.sorted_moe_ffn(x, renorm, w1, w3, w2, "silu"),
        _reference_moe(x, wg, w1, w3, w2,
                       ref.Spec(1, 2, norm_topk_prob=True)),
        atol=1e-4, rtol=1e-5)


def test_routing_ingredients_of_the_two_losses():
    x, wg, *_ = _moe_inputs(9)
    route = moe.topk_routing(x @ wg, 2)
    assert int(route.counts.sum()) == 2 * x.shape[0]
    np.testing.assert_allclose(route.frac.sum(), 1.0, rtol=1e-6)
    np.testing.assert_allclose(route.mean_prob.sum(), 1.0, rtol=1e-5)
    np.testing.assert_allclose(
        route.lse, jax.scipy.special.logsumexp(x @ wg, axis=-1), rtol=1e-6)
    # uniform routing gives a load-balancing loss of exactly 1
    flat = moe.topk_routing(jnp.zeros((16, 8)), 8)
    assert float(moe.load_balance_loss(flat)) == pytest.approx(1.0)
    assert float(moe.router_z_loss(flat)) == pytest.approx(np.log(8) ** 2)


def test_switch_layer_k1_ungated_is_the_same_function():
    """k = 1 without a gate is the old Switch layer wherever nothing
    would have been dropped: equal to the reference's ungated experts
    and to the one-hot dispatch/combine of `top1_routing` at a
    capacity that holds every token."""
    x, wg, w1, _, w2 = _moe_inputs(10)
    route = moe.topk_routing(x @ wg, 1)
    got = moe.sorted_moe_ffn(x, route, w1, None, w2, "relu")
    np.testing.assert_allclose(
        got, _reference_moe(x, wg, w1, None, w2, ref.Spec(1, 1)),
        atol=1e-4, rtol=1e-5)
    old = moe.top1_routing(x @ wg, capacity=x.shape[0])
    assert int(old.dropped) == 0
    slots = jnp.einsum("tec,td->ecd", old.dispatch, x)
    hidden = jnp.maximum(jnp.einsum("ecd,edf->ecf", slots, w1), 0)
    dense = jnp.einsum("tec,ecd->td", old.combine,
                       jnp.einsum("ecf,efd->ecd", hidden, w2))
    np.testing.assert_allclose(got, dense, atol=1e-4, rtol=1e-5)


def test_sorted_path_gradients_reach_rows_experts_and_router():
    x, wg, w1, w3, w2 = _moe_inputs(11)

    def prog(x, wg, w1, w3, w2):
        return (moe.sorted_moe_ffn(x, moe.topk_routing(x @ wg, 2), w1, w3,
                                   w2, "silu") ** 2).sum()

    def plain(x, wg, w1, w3, w2):
        return (_reference_moe(x, wg, w1, w3, w2, ref.Spec(1, 2)) ** 2).sum()

    got = jax.jit(jax.grad(prog, argnums=(0, 1, 2, 3, 4)))(x, wg, w1, w3, w2)
    want = jax.grad(plain, argnums=(0, 1, 2, 3, 4))(x, wg, w1, w3, w2)
    for a, b in zip(got, want):
        assert float(jnp.abs(b).max()) > 0
        assert float(jnp.abs(a - b).max()) < 1e-5 * float(jnp.abs(b).max())


def test_qk_norm_spans_the_heads():
    """Gains of width d_model, one RMS over the whole projection: the
    program equals the reference, and a norm taken per head (the other
    reading of "QK-norm") is a different function by far more than the
    tolerance."""
    p, (tok, _) = _params(12), _batch(12)
    assert p["layers"][0]["q_norm"]["g"].shape == (CFG.d_model,)
    got = tfm.forward_local(p, tok, CFG, AX)
    np.testing.assert_allclose(got, ref.logits(p, tok, SPEC),
                               atol=LOGITS_ATOL, rtol=0)
    whole = ref.rms_norm

    def per_head(x, g, eps):
        if x.shape[-1] != CFG.d_model or x.ndim != 3 or g is None:
            return whole(x, g, eps)
        h = x.reshape(*x.shape[:-1], 4, 16)
        return whole(h, g.reshape(4, 16), eps).reshape(x.shape)

    def q_only(lp, h, spec, quantize=None, offset=0):  # per head on q, k
        b, t, d = h.shape
        x = whole(h, lp["ln1"]["g"], spec.rms_eps)
        q, k, v = (x @ lp[w] for w in ("wq", "wk", "wv"))
        q, k = per_head(q, lp["q_norm"]["g"], spec.rms_eps), per_head(
            k, lp["k_norm"]["g"], spec.rms_eps)
        split = lambda a: a.reshape(b, t, 4, 16)  # noqa: E731
        o = ref.attention(ref.rope(split(q), spec.rope_theta),
                          ref.rope(split(k), spec.rope_theta), split(v))
        return h + o.reshape(b, t, d) @ lp["wo"]

    real, ref.attention_block = ref.attention_block, q_only
    try:
        other = ref.logits(p, tok, SPEC)
    finally:
        ref.attention_block = real
    assert float(jnp.abs(other - got).max()) > 100 * LOGITS_ATOL


@pytest.mark.parametrize("offset", [0, 5, 40])
def test_rope_equals_the_references_at_a_position_offset(offset):
    x = jnp.asarray(np.random.default_rng(13).standard_normal(
        (2, 24, 4, 16)), jnp.float32)
    got = tfm.rope(x, offset + jnp.arange(24), 10000.0)
    np.testing.assert_allclose(got, ref.rope(x, 10000.0, offset),
                               atol=1e-6, rtol=0)
    # pairs dimension i with i + head_dim / 2, and position 0 is the
    # identity
    np.testing.assert_allclose(tfm.rope(x, jnp.zeros(24), 1e4), x, atol=0)
    lp = _params(13)["layers"][0]
    h = jnp.asarray(np.random.default_rng(14).standard_normal(
        (2, 24, 64)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.layer_forward(lp, h, SPEC, offset=offset)[0]
    np.testing.assert_allclose(
        tfm.layer_forward(lp, h, CFG, AX, True, pos_offset=offset), want,
        atol=LOGITS_ATOL, rtol=0)


def test_sequence_parallel_rope_and_moe_equal_one_device():
    """RoPE under sp with its offset (ring attention over two shards)
    and the per-token routing give the single-device logits."""
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    p, (tok, _) = _params(15), _batch(15)
    ax = tfm.Axes(sp="sp")
    specs = tfm.param_specs(CFG, ax)
    sharded = jax.jit(jaxcompat.shard_map(
        lambda q, t: tfm.forward_local(q, t, CFG, ax),
        mesh=make_mesh(("sp",), (2,)), in_specs=(specs, P(None, "sp")),
        out_specs=P(None, "sp"), check_vma=False))(p, tok)
    np.testing.assert_allclose(sharded, tfm.forward_local(p, tok, CFG, AX),
                               atol=1e-4, rtol=0)


def test_gated_dense_ffn_is_act_w1_times_w3():
    cfg = tfm.Config(vocab=64, d_model=32, n_layers=1, n_heads=4, d_ff=48,
                     max_seq=16, mlp_act="silu", mlp_gated=True,
                     dtype=jnp.float32)
    p = _params(16, cfg)
    lp = p["layers"][0]
    assert lp["w3"].shape == lp["w1"].shape == (32, 48)
    h = jnp.asarray(np.random.default_rng(16).standard_normal((1, 8, 32)),
                    jnp.float32)
    zero_attn = dict(lp, wo=jnp.zeros_like(lp["wo"]))
    x = tfm._ln(h, lp["ln2"]["g"], lp["ln2"]["b"])
    want = h + (jax.nn.silu(x @ lp["w1"]) * (x @ lp["w3"])) @ lp["w2"]
    np.testing.assert_allclose(
        tfm.layer_forward(zero_attn, h, cfg, AX, False), want, atol=1e-5)


# -- what an axis cannot give yet is an error ----------------------------------

@pytest.mark.parametrize("ax, change, names", [
    (tfm.Axes(ep="ep"), {}, "R1b"),
    (tfm.Axes(ep="ep"), {"top_k": 1}, "R1b"),          # gated experts
    (tfm.Axes(sp="sp"), {}, "position offset"),
    (tfm.Axes(tp="tp"), {}, "QK-norm"),
])
def test_unsupported_axis_raises_and_names_the_gap(ax, change, names):
    cfg = tfm.Config(dtype=jnp.float32, **dict(TOY, **change))
    lp = _params(17)["layers"][0]
    with pytest.raises(NotImplementedError, match=names):
        tfm.layer_forward(lp, jnp.zeros((1, 8, 64)), cfg, ax, True)


def test_pipeline_refuses_what_its_end_stages_do_not_compute():
    from ompi_tpu.models import pipeline as pl

    with pytest.raises(NotImplementedError, match="R3"):
        pl.make_pp_train_step(CFG, tfm.Axes(pp="pp"), None, n_micro=2)


# -- trees, names, counters ----------------------------------------------------

def test_specs_and_extra_axes_have_the_parameters_structure():
    p = tfm.init_params(np.random.default_rng(0), CFG)
    assert sorted(p) == ["embed", "head", "layers", "ln_f"]
    assert sorted(p["layers"][0]) == sorted(
        ["ln1", "ln2", "q_norm", "k_norm", "wq", "wk", "wv", "wo", "wg",
         "w1", "w3", "w2"])
    assert list(p["ln_f"]) == ["g"]  # RMSNorm: a gain, no bias
    ax = tfm.Axes(tp="tp", ep="ep")
    specs = tfm.param_specs(CFG, ax)
    leaves, treedef = jax.tree.flatten(p)
    assert len(treedef.flatten_up_to(specs)) == len(leaves)
    assert len(treedef.flatten_up_to(tfm.grad_extra_axes(CFG, ax))) \
        == len(leaves)
    l0 = specs["layers"][0]
    assert l0["w3"] == l0["w1"] == P("ep", None, "tp")
    assert l0["w2"] == P("ep", "tp", None) and l0["wg"] == P()
    assert tfm.grad_extra_axes(CFG, ax)["layers"][1]["wg"] == "tp"
    assert specs["head"] == P()


def test_scopes_appear_in_the_lowered_steps_op_paths():
    p, (tok, lab) = _params(), _batch()
    step = jax.jit(tfm.make_train_step(CFG, AX, tfm.param_specs(CFG, AX)))
    text = step.lower(p, tok, lab).as_text(debug_info=True)
    for scope in ("moe_route", "moe_dispatch", "moe_experts", "moe_combine",
                  "qk_rope"):
        assert f"/{scope}/" in text, scope
    assert "layer_1/mlp/moe_experts" in text.replace("jvp(", "").replace(
        ")", "")
    assert "attn_proj/qk_rope" in text


def test_route_counts_probe_feeds_the_counters(pvar_clean):
    p, (tok, _) = _params(), _batch()
    counts = np.asarray(tfm.route_counts(p, tok, CFG))
    assert counts.shape == (2, 8) and counts.dtype == np.int32
    assert (counts.sum(1) == tok.size * 2).all()  # none dropped
    assert pvar.read("moe_assignments") == 2 * tok.size * 2
    assert pvar.read("moe_dropped_assignments") == 0
    assert {"moe_assignments", "moe_dropped_assignments"} <= set(
        pvar.WELL_KNOWN)
    # the same choices the reference's router makes in layer 0
    chosen = np.asarray(ref.chosen_experts(p, tok, SPEC))
    experts = np.asarray(tfm.route_experts(p, tok, CFG))
    assert experts.shape == (2, tok.size, 2)
    assert np.array_equal(np.sort(experts[0], -1), chosen)
    for layer in range(2):
        assert np.array_equal(
            np.bincount(experts[layer].ravel(), minlength=8), counts[layer])


# -- OPT's step is the parent's ------------------------------------------------

#: losses of the toy OPT step at seed 0 on the CPU, as the PARENT commit
#: (f6898e9, before this file existed) prints them with `float.hex`
PARENT_OPT_LOSSES = ['0x1.634ef00000000p+2', '0x1.536a6c0000000p+2',
                     '0x1.4895140000000p+2']


def test_toy_opt_step_losses_are_the_parents_bit_for_bit():
    cfg = tfm.Config(vocab=128, d_model=64, n_layers=2, n_heads=4, d_ff=128,
                     max_seq=64, dtype=jnp.float32)
    p = tfm.init_params(np.random.default_rng(0), cfg)
    step = jax.jit(tfm.make_train_step(cfg, AX, tfm.param_specs(cfg, AX),
                                       lr=0.01))
    losses = []
    for i in range(3):
        tok, lab = _batch(20 + i)
        p, loss = step(p, tok, lab)
        losses.append(float(loss).hex())
    assert losses == PARENT_OPT_LOSSES
