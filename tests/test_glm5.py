"""GLM-5 through the model's one block, at toy widths on the CPU, against
the plain reference benchmark/reference/glm5_decoder.py: latent
attention with the learned sparse-attention indexer, the sigmoid
`noaux_tc` router over experts of which a chip holds a share, the
shared expert, layers of three kinds (dense, expert, multi-token
prediction) and per-layer recomputation — and that what was there
before (OPT, OLMoE) lowers to the parent's text."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from benchmark import weights, weights_glm5, weights_olmoe
from benchmark.reference import glm5_decoder as ref
from benchmark.runners import glm5_train as gt
from benchmark.runners import olmoe_train, train_step
from ompi_tpu.core import pvar
from ompi_tpu.models import transformer as tfm
from ompi_tpu.ops import attention as att
from ompi_tpu.ops import moe
from tests import lowered_text

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
AX = tfm.Axes()


def _toy(**over):
    with open(os.path.join(HERE, "benchmark", "configs",
                           "glm-5.rehearsal.json")) as f:
        config = json.load(f)
    config["param_dtype"] = "float32"
    sizes = gt.model_sizes(config)
    cfg = gt.program_config(sizes)
    return sizes, tfm.Config(**{**cfg.__dict__, "dtype": jnp.float32,
                                **over})


def _batch(sizes, seed, batch=2, seq=64, n=1):
    toks, labs = weights.batches(sizes["vocab"], n, batch, seq, seed)
    return (toks[0], labs[0]) if n == 1 else (toks, labs)


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
    params = weights_glm5.device_init(sizes, seed)
    tok, lab = _batch(sizes, seed)
    spec = gt.reference_spec(sizes)
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
    assert len(jax.tree.leaves(got)) == len(gt.leaf_kinds(sizes))


@pytest.mark.parametrize("which", ["ce", "mtp", "index"])
def test_each_loss_is_the_references(which):
    sizes, _ = _toy()
    weights_of = {"ce": (0.0, 0.0), "mtp": (0.1, 0.0), "index": (0.0, 1.0)}
    mtp_w, index_w = weights_of[which]
    _, cfg = _toy(mtp_weight=mtp_w, index_loss_weight=index_w)
    params = weights_glm5.device_init(sizes, 7)
    tok, lab = _batch(sizes, 7)
    ce, mtp, index = ref.losses(params, tok, lab, gt.reference_spec(sizes))
    assert float(mtp) > 1.0 and float(index) > 1e-3
    with jax.default_matmul_precision("highest"):
        got = jax.jit(_mean_loss(cfg, tok, lab))(params)
    assert float(got) == pytest.approx(
        float(ce + mtp_w * mtp + index_w * index), rel=1e-5)


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_the_references_layerwise_step_is_its_whole_model_gradient(seed):
    sizes, _ = _toy()
    spec = gt.reference_spec(sizes)
    tok, lab = _batch(sizes, seed)
    params = weights_glm5.device_init(sizes, seed)
    want_loss, grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, tok, lab, spec)))(params)
    want = jax.tree.map(lambda p, g: p - 0.5 * g, params, grads)
    new, val = ref.sgd_step(weights_glm5.device_init(sizes, seed), tok, lab,
                            0.5, spec)
    assert float(val) == pytest.approx(float(want_loss), rel=1e-5)
    assert jax.tree.structure(new) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(want)):
        _close(a, b, rel=1e-4)


# -- latent attention ----------------------------------------------------------

@pytest.mark.parametrize("interleave", [True, False])
def test_latent_attention_is_plain_mha_of_the_expanded_matrices(interleave):
    """Head by head, with W_qb and W_kvb cut into each head's own
    matrices and the shared RoPE key given to every head."""
    sizes, cfg = _toy(index_topk=0, rope_interleave=interleave)
    lp = jax.tree.map(jnp.asarray, tfm.init_params(
        np.random.default_rng(0), cfg)["layers"][0])
    b, t, d, h = 2, 24, cfg.d_model, cfg.n_heads
    nope, rope, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    hidden = jnp.asarray(np.random.default_rng(1).standard_normal(
        (b, t, d)), jnp.float32)
    x = tfm._norm(hidden, lp["ln1"], cfg)
    with jax.default_matmul_precision("highest"):
        got = tfm._mla_attention(lp, x, cfg, None, None)
        turn = tfm.rope_interleaved if interleave else tfm.rope
        pos = jnp.arange(t)
        c_q = tfm._rms(x @ lp["wq_a"], lp["q_a_norm"]["g"], cfg.norm_eps)
        kv_a = x @ lp["wkv_a"]
        c_kv = tfm._rms(kv_a[..., :cfg.kv_lora_rank], lp["kv_a_norm"]["g"],
                        cfg.norm_eps)
        k_r = turn(kv_a[:, :, None, cfg.kv_lora_rank:], pos, cfg.rope_theta)
        wq = lp["wq_b"].reshape(-1, h, nope + rope)
        wkv = lp["wkv_b"].reshape(-1, h, nope + dv)
        heads = []
        for i in range(h):
            q = c_q @ wq[:, i]
            q = jnp.concatenate([q[..., :nope], turn(
                q[:, :, None, nope:], pos, cfg.rope_theta)[:, :, 0]], -1)
            kv = c_kv @ wkv[:, i]
            k = jnp.concatenate([kv[..., :nope], k_r[:, :, 0]], -1)
            heads.append(att.mha(q[:, :, None], k[:, :, None],
                                 kv[:, :, None, nope:])[:, :, 0])
        want = jnp.concatenate(heads, -1) @ lp["wo"]
    _close(got, want, rel=1e-5)


@pytest.mark.parametrize("dr", [4, 8, 64])
def test_interleaved_rope_is_the_pairing_written_out(dr):
    rng = np.random.default_rng(dr)
    x = rng.standard_normal((2, 5, 3, dr)).astype(np.float32)
    pos, theta = np.array([0, 1, 7, 100, 4095]), 1e6
    want = np.empty_like(x)
    for i in range(dr // 2):
        ang = pos * theta ** (-2.0 * i / dr)
        c, s = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
        a, b = x[..., 2 * i], x[..., 2 * i + 1]
        want[..., 2 * i] = a * c - b * s
        want[..., 2 * i + 1] = b * c + a * s
    got = tfm.rope_interleaved(jnp.asarray(x), jnp.asarray(pos), theta)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=2e-3)
    if len(pos) == 5:  # the reference's own, at positions 0 .. T - 1
        seq = ref.rope_pairs(jnp.moveaxis(jnp.asarray(x), 1, 2), theta)
        mine = tfm.rope_interleaved(jnp.asarray(x), jnp.arange(5), theta)
        np.testing.assert_allclose(np.asarray(jnp.moveaxis(seq, 2, 1)),
                                   np.asarray(mine), rtol=1e-5, atol=1e-6)


# -- the two discrete choices --------------------------------------------------

@pytest.mark.parametrize("seed", [1, 5, 2**31 + 9])
def test_selection_and_routing_are_the_references_in_float32(seed):
    sizes, cfg = _toy()
    params = weights_glm5.device_init(sizes, seed)
    tok, _ = _batch(sizes, seed)
    spec = gt.reference_spec(sizes)
    with jax.default_matmul_precision("highest"):
        keep = tfm.dsa_selection(params, tok, cfg)
        experts = np.asarray(tfm.route_experts(params, tok, cfg)[0])
    assert keep.shape == (sizes["n_layers"], 2, 64, 64)
    want = ref.selection(params, tok, spec)
    assert gt.select_disagreement(keep[0], want, spec.index_topk) == 0.0
    # a key that ties with the last chosen one is chosen too (at toy
    # widths a score is exactly 0 where every ReLU is): never fewer
    rows, full = np.asarray(keep[0].sum(-1)), np.minimum(
        np.arange(64) + 1, spec.index_topk)
    assert (rows >= full).all() and (rows == full).mean() > 0.9
    chosen = ref.chosen_experts(params, tok, spec)
    assert gt.route_disagreement(experts, chosen) == 0.0
    assert (np.asarray(chosen).sum(-1) == spec.top_k).all()
    # the control's choices differ, and the two numbers see it
    fp8 = jnp.float8_e4m3fn
    assert gt.select_disagreement(
        keep[0], ref.selection(params, tok, spec, fp8), 16) > 0.005
    assert gt.route_disagreement(
        experts, ref.chosen_experts(params, tok, spec, fp8)) > 0.005


def test_probe_counters(pvar_clean):
    sizes, cfg = _toy()
    params = weights_glm5.device_init(sizes, 1)
    tok, _ = _batch(sizes, 1)
    keep = tfm.dsa_selection(params, tok, cfg)
    counts = np.asarray(tfm.route_counts(params, tok, cfg))
    assert pvar.read("dsa_causal_pairs") == 2 * 2 * 64 * 65 // 2
    assert pvar.read("dsa_selected_pairs") == int(keep.sum()) \
        >= 2 * 2 * sum(min(t + 1, 16) for t in range(64))
    assert counts.shape == (1, 16) and counts.sum() == 128 * 4
    assert pvar.read("moe_assignments") == 128 * 4
    assert pvar.read("moe_dropped_assignments") == 0
    assert pvar.read("moe_held_assignments") == int(counts[:, :4].sum())
    # 4 of 16 held and SLACK shares of 4: the bound is all the rows, no
    # layer of a traced step has a second path and none can be over it
    traced = pvar.read("moe_full_layers")        # the probes' own traces
    jax.jit(_mean_loss(cfg, tok, tok)).lower(params)
    assert pvar.read("moe_full_layers") - traced == 2  # trunk and MTP
    assert pvar.read("moe_bounded_layers") == 0
    assert pvar.read("moe_over_bound_layers") == 0


def test_probe_counters_of_a_bounded_share(pvar_clean, monkeypatch):
    """ONE of 16 experts held and 256 tokens x 4: the layers are
    bounded at 512 of their 1,024 rows, each counted once per trace;
    the probe counts the layer-batches whose held rows exceed the
    bound (here with the rule cut down to the held expert's mean: the
    fullest expert is over it)."""
    sizes, cfg = _toy(held_experts=(0, 1))
    sizes = dict(sizes, held_count=1)
    params = weights_glm5.device_init(sizes, 1)
    tok, lab = _batch(sizes, 1, batch=4)
    counts = np.asarray(tfm.route_counts(params, tok, cfg))
    fullest = int(counts[0].argmax())
    cfg = tfm.Config(**{**cfg.__dict__, "held_experts": (fullest, 1)})
    assert moe.held_rows_bound(tok.size, cfg.top_k, 1, 16) == 512
    pvar.reset()
    jax.jit(_mean_loss(cfg, tok, lab)).lower(params)
    assert pvar.read("moe_bounded_layers") == 2
    assert pvar.read("moe_full_layers") == 0
    # 512 of 4,096 rows: a bound this small takes the 0/1 product
    assert (pvar.read("moe_row_sum_gather_layers"),
            pvar.read("moe_row_sum_product_layers")) == (0, 2)
    tfm.route_counts(params, tok, cfg)
    assert pvar.read("moe_held_assignments") == counts[0, fullest] < 512
    assert pvar.read("moe_over_bound_layers") == 0
    monkeypatch.setattr(moe, "SLACK", 1)
    monkeypatch.setattr(moe, "_TM", 8)
    assert moe.held_rows_bound(tok.size, cfg.top_k, 1, 16) == 64
    tfm.route_counts(params, tok, cfg)
    assert counts[0, fullest] > 64
    assert pvar.read("moe_over_bound_layers") == 1


def test_a_sequence_no_longer_than_topk_takes_todays_causal_path(
        pvar_clean, monkeypatch):
    sizes, cfg = _toy(index_topk=64)
    params = weights_glm5.device_init(sizes, 2)
    tok, lab = _batch(sizes, 2)

    def never(*a, **kw):
        raise AssertionError("the indexer ran")

    monkeypatch.setattr(tfm, "_dsa_core", never)
    monkeypatch.setattr(tfm, "_index_project", never)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            _mean_loss(cfg, tok, lab)))(params)
    assert pvar.read("attn_dsa_layers") == 0
    assert pvar.read("attn_mla_layers") == pvar.read(
        "attn_reference_layers") > 0  # att.attention, off the TPU att.mha
    spec = gt.reference_spec(sizes)._replace(index_topk=64)
    assert float(loss) == pytest.approx(
        float(ref.loss(params, tok, lab, spec)), rel=1e-5)
    assert tfm.dsa_selection(params, tok, cfg).shape[0] == 0
    for lp in grads["layers"] + grads["mtp"]:
        assert all(float(jnp.abs(g).max()) == 0.0 for g in jax.tree.leaves(
            {k: v for k, v in lp.items() if k.startswith("wi_")}))


def test_no_gradient_through_the_selection_and_none_to_the_bias():
    """The indexer learns from its own loss alone, the main model
    learns nothing from the indexer, the selection bias is a buffer."""
    sizes, on = _toy(index_loss_weight=1.0)
    _, off = _toy(index_loss_weight=0.0)
    params = weights_glm5.device_init(sizes, 4)
    tok, lab = _batch(sizes, 4)
    with jax.default_matmul_precision("highest"):
        g_on = jax.jit(jax.grad(_mean_loss(on, tok, lab)))(params)
        g_off = jax.jit(jax.grad(_mean_loss(off, tok, lab)))(params)
    flat_on = jax.tree_util.tree_leaves_with_path(g_on)
    for (path, a), b in zip(flat_on, jax.tree.leaves(g_off)):
        name = jax.tree_util.keystr(path)
        if "'wi_" in name:  # trained by the indexer's loss and nothing else
            assert float(jnp.abs(b).max()) == 0.0, name
            assert float(jnp.abs(a).max()) > 0.0, name
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)
        if "wg_bias" in name:
            assert float(jnp.abs(a).max()) == 0.0


# -- the share of the experts a chip holds ---------------------------------------

@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("shares, seq", [(4, 32), (2, 32), (1, 32),
                                         (16, 128)])
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(seed, shares, seq,
                                                         pvar_clean):
    """16 experts held by `shares` chips: what each chip's layer adds
    for its own experts, summed, with the shared expert counted once,
    is the uncut reference's layer. With 16 chips and 256 tokens each
    chip's layer carries 512 of its 1,024 rows (the bound is under
    T x k); the others carry all of theirs."""
    sizes, whole = _toy(held_experts=(0, 16))
    lp = jax.tree.map(jnp.asarray, tfm.init_params(
        np.random.default_rng(seed), whole)["layers"][1])
    h = jnp.asarray(np.random.default_rng(seed + 10).standard_normal(
        (2, seq, whole.d_model)), jnp.float32)
    per = 16 // shares
    with jax.default_matmul_precision("highest"):
        flat = tfm._norm(h, lp["ln2"], whole).reshape(-1, whole.d_model)
        shared = tfm._ffn(flat, lp["ws1"], lp["ws3"], lp["ws2"],
                          whole).reshape(h.shape)
        routed = 0.0
        for s in range(shares):
            cfg = tfm.Config(**{**whole.__dict__,
                                "held_experts": (s * per, per)})
            mine = dict(lp, **{n: lp[n][s * per:(s + 1) * per]
                               for n in ("w1", "w3", "w2")})
            routed = routed + tfm._sublayer(
                mine, h, cfg, AX, tfm.layout(cfg, True)[1]) - h - shared
        want = ref.ffn_block(lp, h, gt.reference_spec(sizes)) - h
    _close(routed + shared, want, rel=1e-5)
    assert float(jnp.linalg.norm(routed)) > 0.1 * float(
        jnp.linalg.norm(shared))
    bounded = shares if moe.held_rows_bound(
        2 * seq, whole.top_k, per, 16) < 2 * seq * whole.top_k else 0
    assert bounded == (16 if shares == 16 else 0)
    assert (pvar.read("moe_bounded_layers"),
            pvar.read("moe_full_layers")) == (bounded, shares - bounded)
    assert (pvar.read("moe_row_sum_gather_layers")
            + pvar.read("moe_row_sum_product_layers")) == bounded


def test_held_share_sorts_the_absent_past_the_last_group():
    logits = jnp.asarray(np.random.default_rng(0).standard_normal((40, 12)))
    bias = jnp.asarray(np.random.default_rng(1).standard_normal(12) * 0.3)
    route = moe.sigmoid_routing(logits, bias, 3, True, 2.5)
    probs = np.asarray(jax.nn.sigmoid(logits))
    top = np.argsort(-(probs + np.asarray(bias)), -1)[:, :3]
    assert (np.sort(np.asarray(route.experts), -1) == np.sort(top, -1)).all()
    np.testing.assert_allclose(np.asarray(route.weights.sum(-1)), 2.5,
                               rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(route.weights),
        2.5 * np.take_along_axis(probs, np.asarray(route.experts), 1)
        / np.take_along_axis(probs, np.asarray(route.experts), 1).sum(
            -1, keepdims=True), rtol=1e-5)
    held = moe.held_share(route, 4, 4)
    here = (np.asarray(route.experts) >= 4) & (np.asarray(route.experts) < 8)
    assert (np.asarray(held.experts)[here]
            == np.asarray(route.experts)[here] - 4).all()
    assert (np.asarray(held.experts)[~here] == 4).all()
    assert (np.asarray(held.weights)[~here] == 0).all()
    assert (np.asarray(held.counts) == np.asarray(route.counts)[4:8]).all()
    assert int(held.counts.sum()) == int(here.sum())


@pytest.mark.parametrize("transpose_rhs", [False, True])
def test_gmm_kernel_with_a_long_tail_is_ragged_dot(transpose_rhs):
    """The grouped-matmul kernel where most rows lie past the last
    group (interpret mode): ragged_dot's result, zeros in the tail, an
    edge tile shared by the last group and the tail."""
    from ompi_tpu.ops import grouped_matmul as gk

    rng = np.random.default_rng(3)
    m, k, n, e = 1024, 128, 256, 3
    rows = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((e, k, n)), jnp.float32)
    sizes = jnp.asarray([70, 0, 130], jnp.int32)
    want = lax.ragged_dot(rows, w, sizes)
    rhs = w.transpose(0, 2, 1) if transpose_rhs else w
    got = gk.gmm(rows, rhs, sizes, (256, 128, 128),
                 transpose_rhs=transpose_rhs, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)
    assert float(jnp.abs(got[200:]).max()) == 0.0


# -- the sparse-attention functions at sizes where rows and heads are split -----

def test_dsa_functions_in_blocks_are_the_dense_formulas():
    rng = np.random.default_rng(0)
    t, h, d, dv, hi, di, topk = 256, 32, 16, 8, 4, 8, 100
    assert len(att.dsa_row_blocks(t)) == 2 and att.dsa_row_blocks(64) == [
        (0, 64)]
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa
    q, k, v = f(t, h, d), f(t, h, d), f(t, h, dv)
    qi, ki, w = f(t, hi, di), f(t, di), f(t, hi)
    causal = np.tril(np.ones((t, t), bool))
    with jax.default_matmul_precision("highest"):
        scores = att.dsa_index_scores(qi, ki, w)
        dense = (jnp.maximum(jnp.einsum("qhd,kd->hqk", qi, ki), 0)
                 * w.T[:, :, None]).sum(0)
        np.testing.assert_allclose(np.asarray(scores)[causal],
                                   np.asarray(dense)[causal], rtol=1e-4,
                                   atol=1e-5)
        assert np.isneginf(np.asarray(scores)[~causal]).all()
        keep = att.dsa_select(scores, topk)
        kth = np.sort(np.asarray(scores), -1)[:, -topk]
        assert (np.asarray(keep) == ((np.asarray(scores) >= kth[:, None])
                                     & causal)).all()
        # the k-th largest without a sort: exact, signs, zeros, ties and
        # infinities included
        odd = jnp.asarray([[3.0, -0.0, 0.0, -jnp.inf, 2.5, -1e-30, 2.5,
                            jnp.inf, -7.0, 1e-30]], jnp.float32)
        for kk in range(1, 11):
            keys, at = att._kth_largest(odd, kk)
            want = np.sort(np.asarray(odd), -1)[:, -kk]
            assert (np.asarray(keys >= at)
                    == (np.asarray(odd) >= want[:, None])).all(), kk
        assert (np.asarray(keep.sum(-1))
                >= np.minimum(np.arange(t) + 1, topk)).all()
        assert np.asarray(keep)[:topk][causal[:topk]].all()
        o, p = att.dsa_attend(q, k, v, keep, d ** -0.5)
        s = jnp.where(keep[None], jnp.einsum("qhd,khd->hqk", q, k)
                      * d ** -0.5, -jnp.inf)
        probs = jax.nn.softmax(s, -1)
        _close(o, jnp.einsum("hqk,khd->qhd", probs, v), rel=1e-5)
        _close(p, probs.sum(0), rel=1e-5)
        kl = att.dsa_kl(scores, keep, p)
        pn = np.asarray(probs.sum(0) / h, np.float64)
        logq = np.asarray(jax.nn.log_softmax(
            jnp.where(keep, scores, -jnp.inf), -1), np.float64)
        on = np.asarray(keep) & (pn > 0)
        want = (pn[on] * (np.log(pn[on]) - logq[on])).sum() / t
        assert float(kl) == pytest.approx(want, rel=1e-4)
        # gradients pass the row blocks and head groups as the dense
        # formula's do
        g = jax.grad(lambda q, k, v: att.dsa_attend(
            q, k, v, keep, d ** -0.5)[0].sum())(q, k, v)

        def dense_o(q, k, v):
            s = jnp.where(keep[None], jnp.einsum("qhd,khd->hqk", q, k)
                          * d ** -0.5, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1),
                              v).sum()

        _close(g, jax.grad(dense_o)(q, k, v), rel=1e-4)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_no_heads_by_t_by_t_array_in_the_compiled_step(dtype):
    """T 256 in two blocks of rows, 32 heads 16 at a time: the compiled
    step holds [16, 128, <=256] blocks and nothing of [heads, T, T],
    for the indexer (8 heads) or the attention (32)."""
    import re

    sizes, cfg = _toy(n_heads=32, dtype=jnp.dtype(dtype), index_topk=100)
    shapes = jax.eval_shape(lambda: tfm.init_params(
        np.random.default_rng(0), cfg))
    tok = jax.ShapeDtypeStruct((1, 256), jnp.int32)
    step = jax.jit(tfm.make_train_step(cfg, AX, tfm.param_specs(cfg, AX)))
    text = step.lower(shapes, tok, tok).compile().as_text()
    found = set(re.findall(r"\[((?:\d+,)*)(\d+),256,256\]", text))
    heads = {(lead, n) for lead, n in found if int(n) > 1}
    assert not heads, heads
    assert re.search(r"\[(?:1,)?16,128,(?:128|256)\]", text)


# -- what an axis cannot give yet ------------------------------------------------

@pytest.mark.parametrize("axes, what", [
    (dict(tp="x"), "latent attention"), (dict(sp="x"), "latent attention"),
    (dict(ep="x"), "held experts"), (dict(pp="x"), "multi-token")])
def test_what_an_axis_cannot_give_yet_raises(axes, what):
    _, cfg = _toy()
    if "ep" in axes:  # past the attention check: plain heads
        cfg = tfm.Config(**{**cfg.__dict__, "attn": "mha", "index_topk": 0,
                            "mtp_layers": 0})
    if "pp" in axes:
        cfg = tfm.Config(**{**cfg.__dict__, "attn": "mha", "index_topk": 0,
                            "held_experts": None})
    with pytest.raises(NotImplementedError, match=what):
        tfm._check_supported(cfg, tfm.Axes(**axes), True, 0)


# -- what was there before ---------------------------------------------------------

#: sha256 of the lowered text of the toy train steps at the parent
#: commit 8f3d401 (jax 0.9.0, CPU): `build_step(...).lower(...).as_text()`
#: of the two runners at their rehearsal configurations, batch 2 x 64
#: — OLMoE's as PR 44 left it, whose full expert layer moves its rows
#: as the bounded one does (14d80870... and 0ceab40f... before it).
#: PR 45 re-recorded all four: one loss body, the label's logit by a mask
#: (2cf9a876..., 44ae5c47..., e1c69145... and 5ed4a8c0... before it)
PARENT = {
    ("opt-30b", "bfloat16"):
        "1c1ff97b8b8ebdd163542ec1ee00c89f5b642a0970b770064350024859c14822",
    ("opt-30b", "float32"):
        "240463fe65810ebf586e75ce337587596c01d1026b86a12667e8c791f3d06161",
    ("olmoe-1b-7b", "bfloat16"):
        "5ea4396b33250254f3e16794f825a0d9b1312bda790aa9ac29ae72f03b00a3a5",
    ("olmoe-1b-7b", "float32"):
        "8d34f41d586e1bcd76f325e247577299a22bba132eb4b310f1c928dea83e4266",
}


@pytest.mark.parametrize("name, dtype", sorted(PARENT))
def test_the_lowered_toy_steps_are_the_parents_text(name, dtype,
                                                    monkeypatch):
    """Without the residuals' names (PR 35) the text is the parent's,
    raw; with them it is that text but for jax's numbering of its
    private functions (tests/lowered_text.py)."""
    if jax.__version__ != "0.9.0":
        pytest.skip("the recorded text is jax 0.9.0's")
    runner, made = {"opt-30b": (train_step, weights),
                    "olmoe-1b-7b": (olmoe_train, weights_olmoe)}[name]
    with open(os.path.join(HERE, "benchmark", "configs",
                           name + ".rehearsal.json")) as f:
        config = json.load(f)
    config["param_dtype"] = dtype
    sizes = runner.model_sizes(config)
    toks, labs = weights.batches(sizes["vocab"], 1, 2, 64, 1)

    def text():
        return runner.build_step(sizes, 0.01).lower(
            made.device_init(sizes, 1), toks[0], labs[0]).as_text()

    named = text()
    lowered_text.without_names(monkeypatch)
    bare = text()
    assert lowered_text.sha256(bare) == PARENT[name, dtype]
    assert lowered_text.canonical(named) == lowered_text.canonical(bare)


def test_the_fields_of_pr_50_leave_this_configurations_toy_step_its_text():
    """`mtp_attn`, `qk_norm` per head and `NO_ROPE` at their defaults:
    the module after the last layer is `_is_moe(cfg, n_layers)` as it
    was and the toy step lowers to the text of the parent a40151f
    (canonical: tests/lowered_text.py)."""
    if jax.__version__ != "0.9.0":
        pytest.skip("the recorded text is jax 0.9.0's")
    sizes, cfg = _toy()
    assert cfg.mtp_attn is None and tfm._mtp_kind(cfg) is True
    with open(os.path.join(HERE, "benchmark", "configs",
                           "glm-5.rehearsal.json")) as f:
        sizes = gt.model_sizes(json.load(f))
    toks, labs = weights.batches(sizes["vocab"], 1, 2, 64, 1)
    text = gt.build_step(sizes, 0.01).lower(
        weights_glm5.device_init(sizes, 1), toks[0], labs[0]).as_text()
    assert lowered_text.sha256(lowered_text.canonical(text)) == \
        "3fd6789d75120ad9ddc6d636112d144ed217465912693a85073ce6ded9532a4b"


def test_the_seeded_tree_is_init_params_tree():
    sizes, cfg = _toy()
    lib = tfm.init_params(np.random.default_rng(0), cfg)
    mine = weights_glm5.device_init(sizes, 0)
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
    kinds = gt.leaf_kinds(sizes)
    assert kinds.count("router") == 2 * 2 and kinds.count("indexer") == 5 * 3
    # the movement read leaf by leaf is compare.leaf_delta_norms'
    from benchmark import compare

    moved = jax.tree.map(lambda a: a + 0.5, mine)
    np.testing.assert_allclose(
        weights_glm5.delta_norms(sizes, 0, moved),
        np.asarray(compare.leaf_delta_norms(moved, mine)), rtol=1e-5)
