"""The sixth published model of models/transformer.py at toy widths on
the CPU: layers that are one pre-norm and one mixer by a pattern
string — a Mamba-2 state-space mixer (ops/ssm.py: a chunked scan with
no loop in the step), grouped-query attention without positions, relu2
experts without a gate and with a shared expert — the program against
hand-written cases and against the float32 reference
(benchmark/reference/nemotron_decoder.py), whose recurrence runs token
by token."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from benchmark import weights, weights_nemotron  # noqa: E402
from benchmark.reference import nemotron_decoder as ref  # noqa: E402
from ompi_tpu.core import pvar  # noqa: E402
from ompi_tpu.models import remat  # noqa: E402
from ompi_tpu.models import transformer as tfm  # noqa: E402
from ompi_tpu.ops import attention as att  # noqa: E402
from ompi_tpu.ops import moe, ssm  # noqa: E402

AX = tfm.Axes()
PATTERN = "ME*EM"
SIZES = dict(
    vocab=64, d_model=32, n_layers=5, pattern=PATTERN, n_heads=4,
    head_dim=16, n_kv_heads=2, moe_d_ff=24, shared_d_ff=40, n_experts=16,
    held_first=0, held_count=16, top_k=4, ssm_heads=4, ssm_head_dim=8,
    ssm_groups=2, ssm_state=16, ssm_conv=4, ssm_chunk=8, dt_min=0.001,
    dt_max=0.1, dt_floor=1e-4, param_dtype="float32")
B, T = 2, 32


def config(**kw):
    base = dict(
        vocab=64, d_model=32, n_layers=5, n_heads=4, head_width=16,
        n_kv_heads=2, layer_pattern=PATTERN, pos="none", norm="rmsnorm",
        tie_head=False, mlp_act="relu2", moe_d_ff=24, n_experts=16, top_k=4,
        norm_topk_prob=True, router_score="sigmoid", router_bias=True,
        routed_scale=2.5, n_shared_experts=1, shared_d_ff=40,
        ssm_heads=4, ssm_head_dim=8, ssm_groups=2, ssm_state=16, ssm_conv=4,
        ssm_chunk=8, max_seq=8, dtype=jnp.float32)
    base.update(kw)
    return tfm.Config(**base)


SPEC = ref.Spec(pattern=PATTERN, n_heads=4, n_kv_heads=2, ssm_heads=4,
                ssm_groups=2, ssm_state=16, top_k=4, block=4, q_rows=8)


@pytest.fixture(scope="module")
def params():
    return weights_nemotron.device_init(SIZES, 7)


@pytest.fixture(scope="module")
def batch():
    toks, labs = weights.batches(64, 2, B, T, 7)
    return toks, labs


def highest(fn, *args):
    with jax.default_matmul_precision("highest"):
        return fn(*args)


def close(a, b, tol=2e-5, atol=0.0):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.abs(a - b).max() <= atol + tol * max(np.abs(b).max(), 1e-30)


# -- the scan ----------------------------------------------------------------

def _mixer_value(lp, x, chunk):
    out, last = ssm.mixer(lp, x, heads=4, head_dim=8, groups=2, state=16,
                          chunk=chunk, eps=1e-5)
    return (out * out).sum() + (last * last).sum(), (out, last)


def _reference_value(lp, x):
    out, last = ref.ssm_mixer(lp, x, SPEC)
    return (out * out).sum() + (last * last).sum(), (out, last)


@pytest.mark.parametrize("chunk", [1, 4, T])
def test_chunked_scan_is_the_token_by_token_recurrence(params, chunk):
    """Values, the state after the last token and the gradient of
    EVERY leaf of the mixer and of its input, for chunks of one token,
    of four, and the whole sequence in one."""
    lp = {k: v for k, v in params["layers"][0].items() if k != "ln"}
    x = jax.random.normal(jax.random.key(3), (B, T, 32))
    (_, (out, last)), grads = highest(jax.value_and_grad(
        lambda lp, x: _mixer_value(lp, x, chunk), (0, 1), has_aux=True),
        lp, x)
    (_, (r_out, r_last)), r_grads = highest(jax.value_and_grad(
        _reference_value, (0, 1), has_aux=True), lp, x)
    close(out, r_out)
    close(last, r_last)
    assert last.shape == (B, 4, 8, 16)
    named = jax.tree_util.tree_leaves_with_path(grads)
    assert {jax.tree_util.keystr(p) for p, _ in named} >= {
        f"[0]['{n}']" for n in ("A_log", "dt_bias", "D", "conv_w", "conv_b",
                                "in_proj", "out_proj")} | {
        "[0]['ssm_norm']['g']", "[1]"}
    for (path, g), r in zip(named, jax.tree.leaves(r_grads)):
        assert float(jnp.abs(r).max()) > 0, path
        close(g, r, 1e-4)


def test_a_sequence_no_chunk_divides_raises(params):
    x = jnp.zeros((1, 12, 32))
    with pytest.raises(ValueError, match="no whole number of chunks"):
        ssm.chunked_scan(jnp.zeros((1, 12, 4, 8)), jnp.ones((1, 12, 4)),
                         -jnp.ones(4), jnp.zeros((1, 12, 2, 16)),
                         jnp.zeros((1, 12, 2, 16)), 8)
    with pytest.raises(NotImplementedError, match="ssm_chunk=8"):
        tfm.layer_forward(params["layers"][0], x, config(), AX, tfm.SSM)


def test_the_state_forgets(params):
    """A decay of ~0 forgets everything but the last token; a decay of
    1 keeps the plain sum."""
    k = jax.random.split(jax.random.key(0), 3)
    x = jax.random.normal(k[0], (1, 16, 2, 4))
    bm = jax.random.normal(k[1], (1, 16, 1, 8))
    cm = jax.random.normal(k[2], (1, 16, 1, 8))
    dt = jnp.ones((1, 16, 2))
    _, gone = highest(ssm.chunked_scan, x, dt, jnp.full(2, -60.0), bm, cm, 4)
    close(gone[0], jnp.einsum("hp,n->hpn", x[0, -1], bm[0, -1, 0]))
    _, kept = highest(ssm.chunked_scan, x, dt, jnp.full(2, -1e-9), bm, cm, 4)
    close(kept[0], jnp.einsum("thp,tn->hpn", x[0], bm[0, :, 0]), 1e-4)


# -- the whole model against the reference ----------------------------------

def _mean_loss(cfg, toks, labs):
    def mean_loss(p):
        nll, count = tfm.loss_local(p, toks, labs, cfg, AX)
        return nll / count
    return mean_loss


@pytest.mark.parametrize("remat", [False, True])
def test_loss_and_every_gradient_are_the_references(params, batch, remat):
    toks, labs = batch
    loss, grads = highest(jax.jit(jax.value_and_grad(
        _mean_loss(config(remat=remat), toks[0], labs[0]))), params)
    r_loss, r_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, toks[0], labs[0], SPEC)))(params)
    close(loss, r_loss, 1e-6)
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(r_grads)):
        if "wg_bias" in jax.tree_util.keystr(path):
            assert not np.asarray(g).any()  # a buffer: no gradient
        else:
            close(g, r, 1e-4)


def test_the_reference_step_is_its_whole_model_gradient(params, batch):
    """The layer-at-a-time SGD step of the reference moves every leaf
    by lr x the whole model's gradient, and returns the float32 norms
    of the state-space layers' small leaves' gradients."""
    toks, labs = batch
    val0, grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, toks[0], labs[0], SPEC)))(params)
    new, val, small = ref.sgd_step(jax.tree.map(jnp.copy, params), toks[0],
                                   labs[0], 0.5, SPEC)
    close(val, val0, 1e-6)
    for p, n, g in zip(*map(jax.tree.leaves, (params, new, grads))):
        close(p - n, 0.5 * g, 1e-4, atol=1e-6)  # p - n cancels
    want = [[float(jnp.sqrt(sum(jnp.sum(a * a) for a in jax.tree.leaves(
        grads["layers"][i][n])))) for n in ref.SSM_SMALL] for i in (0, 4)]
    close(small, want, 1e-4)


def test_the_probes_read_the_first_state_space_layer(params, batch):
    toks, labs = batch
    cfg = config()
    s = pvar.session()
    out, last = highest(tfm.ssm_probe, params, toks[0], cfg)
    assert s.read("ssm_state_norm_micro") == int(round(
        1e6 * float(jnp.linalg.norm(last))))
    r_out, r_last = ref.first_ssm(params, toks[0], SPEC)
    close(out, r_out)
    close(last, r_last)
    close(highest(tfm.gqa_probe, params, toks[0], cfg),
          ref.first_attention(params, toks[0], SPEC), 1e-4)
    grads = highest(tfm.ssm_leaf_grads, params, toks[0], labs[0], cfg)
    _, _, small = ref.sgd_step(jax.tree.map(jnp.copy, params), toks[0],
                               labs[0], 0.01, SPEC)
    assert grads.shape == (2, len(tfm.SSM_SMALL)) and tfm.SSM_SMALL \
        == ref.SSM_SMALL
    close(grads, small, 1e-4)


def test_the_seeded_tree_is_the_programs_tree(params):
    mine = tfm.init_params(np.random.default_rng(0), config())
    assert jax.tree.structure(mine) == jax.tree.structure(params)
    assert [a.shape for a in jax.tree.leaves(mine)] == [
        a.shape for a in jax.tree.leaves(params)]
    like = tfm.param_specs(config(), AX)
    assert jax.tree.structure(like, is_leaf=lambda x: x is None or isinstance(
        x, jax.sharding.PartitionSpec)) == jax.tree.structure(params)
    # the family's initialisation of the scan's small leaves
    lp = mine["layers"][0]
    assert (np.exp(lp["A_log"]) >= 1).all() and (np.exp(lp["A_log"])
                                                 <= 16).all()
    dt = np.log1p(np.exp(lp["dt_bias"]))
    assert (dt > 0.9e-3).all() and (dt < 0.11).all() and (lp["D"] == 1).all()


# -- the step ------------------------------------------------------------------

@pytest.fixture(scope="module")
def step_and_text(params, batch):
    toks, labs = batch
    cfg = config(remat=True, held_experts=(0, 4))
    held = dict(params, layers=[
        dict(lp, w1=lp["w1"][:4], w2=lp["w2"][:4]) if "w1" in lp else lp
        for lp in params["layers"]])
    step = jax.jit(tfm.make_train_step(cfg, AX, tfm.param_specs(cfg, AX)))
    s = pvar.session()
    text = step.lower(held, toks[0], labs[0]).compile().as_text()
    counted = {n: s.read(n) for n in (
        "ssm_layers", "ssm_chunks", "attn_gqa_layers",
        "attn_reference_layers", "remat_whole_applications",
        "moe_full_layers", "moe_bounded_layers")}
    return step, held, text, counted


def test_no_while_in_the_step_of_a_pattern_with_state_space_layers(
        step_and_text):
    _, _, text, counted = step_and_text
    assert "while" not in text
    assert "ssm_scan" in text and "ssm_gate_norm" in text \
        and "ssm_conv" in text and "ssm_proj" in text
    assert counted == {
        "ssm_layers": 2, "ssm_chunks": 2 * (T // 8), "attn_gqa_layers": 1,
        "attn_reference_layers": 1, "remat_whole_applications": 5,
        "moe_full_layers": 2, "moe_bounded_layers": 0}  # toy: all rows


def test_another_seeds_batch_compiles_nothing(step_and_text):
    step, held, _, _ = step_and_text
    for seed in (11, 2**31 + 5):
        toks, labs = weights.batches(64, 1, B, T, seed)
        _, loss = step(held, toks[0], labs[0])
        assert np.isfinite(float(loss))
    assert step._cache_size() == 1


def test_kept_names_change_no_gradient(params, batch, monkeypatch):
    """Whatever a recomputed layer keeps, the gradients are the whole
    recomputation's bit for bit (float32)."""
    toks, labs = batch
    cfg = config(remat=True)
    whole = jax.jit(jax.grad(_mean_loss(cfg, toks[0], labs[0])))(params)
    order = remat.remat_order(tfm.step_costs(cfg, B, T)[0])
    names = tuple(n for n, _ in order)
    monkeypatch.setattr(tfm, "_remat_names", lambda p, t, c: names)
    s = pvar.session()
    kept = jax.jit(jax.grad(_mean_loss(cfg, toks[0], labs[0])))(params)
    assert s.read("remat_kept_applications") == 5
    assert s.read("remat_kept_bytes") == sum(
        held for _, held in order)
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(kept)):
        assert (np.asarray(a) == np.asarray(b)).all()


# -- attention -------------------------------------------------------------------

def test_shared_key_heads_are_mha_over_repeated_heads(params):
    """32-over-2 in small: query head i attends with key head i // 2,
    and a key head's gradient is the sum over its queries."""
    lp = params["layers"][2]
    cfg = config()
    x = jax.random.normal(jax.random.key(5), (B, T, 32))

    def by_hand(lp, x, separate=None):
        q = (x @ lp["wq"]).reshape(B, T, 4, 16)
        k = (x @ lp["wk"]).reshape(B, T, 2, 16)[:, :, [0, 0, 1, 1]]
        v = (x @ lp["wv"]).reshape(B, T, 2, 16)[:, :, [0, 0, 1, 1]]
        if separate is not None:
            k = k + separate
        return att.mha(q, k, v, causal=True).reshape(B, T, 64) @ lp["wo"]

    out = highest(tfm._attention, lp, x, cfg, AX, None)
    close(out, highest(by_hand, lp, x))
    # the gradient of each REPEATED key head, summed over a key head's
    # queries, is the shared key head's
    zero = jnp.zeros((B, T, 4, 16))
    per_query_head = highest(jax.grad(
        lambda s: (by_hand(lp, x, s) ** 2).sum()), zero)
    shared = highest(jax.grad(lambda k_: (tfm._attention(
        dict(lp, wk=k_), x, cfg, AX, None) ** 2).sum()), lp["wk"])
    summed = per_query_head.reshape(B, T, 2, 2, 16).sum(3).reshape(B, T, 32)
    close(shared, jnp.einsum("btd,bte->de", x, summed), 1e-4)


def test_no_positions_means_no_table_and_no_rotation(params, batch):
    toks, labs = batch
    cfg = config()  # max_seq 8 < T: a table would not reach
    assert "pos" not in tfm.init_params(np.random.default_rng(0), cfg)
    assert "pos" not in tfm.param_specs(cfg, AX)
    # attention alone sees no order: a permutation of EARLIER tokens
    # changes nothing at the last position
    lp, x = params["layers"][2], jax.random.normal(jax.random.key(1),
                                                   (1, T, 32))
    perm = jnp.concatenate([jnp.arange(T - 1)[::-1], jnp.array([T - 1])])
    out = highest(tfm._attention, lp, x, cfg, AX, None)
    mixed = highest(tfm._attention, lp, x[:, perm], cfg, AX, None)
    close(out[:, -1], mixed[:, -1])
    with pytest.raises(ValueError, match="pos='alibi'"):
        tfm.layer_forward(lp, x, config(pos="alibi"), AX, tfm.ATTENTION)


@pytest.mark.parametrize("pattern", [True, False], ids=["pattern", "block"])
@pytest.mark.parametrize("pos, qk_norm", [
    ("rope", False), ("none", True), ("rope", True), ("none", False)])
def test_one_attention_computes_what_two_refused(pattern, pos, qk_norm):
    """Until PR 42 a pattern's attention refused RoPE and QK-norm and
    the block refused shared key heads, a head width of its own and no
    positions: the ONE mixer computes every combination — here against
    `att.mha` over the key heads repeated by hand, the norms over the
    whole projection and the rotation on the split heads."""
    cfg = config(pos=pos, qk_norm=qk_norm) if pattern else config(
        pos=pos, qk_norm=qk_norm, layer_pattern=None, n_layers=2,
        moe_every=1, max_seq=T)
    lp = tfm.init_params(np.random.default_rng(3), cfg)["layers"][
        2 if pattern else 0]
    rng = np.random.default_rng(4)
    if qk_norm:  # gains that are not 1, of the projections' own widths
        assert lp["q_norm"]["g"].shape == (64,)
        assert lp["k_norm"]["g"].shape == (32,)
        lp = dict(lp, q_norm={"g": rng.uniform(0.5, 2.0, 64)},
                  k_norm={"g": rng.uniform(0.5, 2.0, 32)})
    lp = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), lp)
    x = jax.random.normal(jax.random.key(5), (B, T, 32))

    def by_hand(lp, x):
        q, k, v = x @ lp["wq"], x @ lp["wk"], x @ lp["wv"]
        if qk_norm:
            q = tfm._rms(q, lp["q_norm"]["g"], cfg.norm_eps)
            k = tfm._rms(k, lp["k_norm"]["g"], cfg.norm_eps)
        q, k, v = (a.reshape(B, T, -1, 16) for a in (q, k, v))
        if pos == "rope":
            q = tfm.rope(q, jnp.arange(T), cfg.rope_theta)
            k = tfm.rope(k, jnp.arange(T), cfg.rope_theta)
        k, v = k[:, :, [0, 0, 1, 1]], v[:, :, [0, 0, 1, 1]]
        return att.mha(q, k, v, causal=True).reshape(B, T, 64) @ lp["wo"]

    close(highest(tfm._attention, lp, x, cfg, AX, None),
          highest(by_hand, lp, x))
    # and the layer around it: the pre-norm's leaf by the layout's name
    name = "ln" if pattern else "ln1"
    kind = tfm.ATTENTION if pattern else True
    want = x + highest(by_hand, lp, tfm._norm(x, lp[name], cfg))
    if pattern:
        close(highest(tfm.layer_forward, lp, x, cfg, AX, kind), want)
    else:
        close(highest(tfm._sublayer, lp, x, cfg, AX,
                      tfm.layout(cfg, kind)[0]), want)


def test_the_block_reads_the_shared_experts_own_width(params):
    """`shared_d_ff` in the block (refused until PR 42): its expert
    half is the pattern's expert layer on the same leaves, and a whole
    step of such a block — shared key heads, a head width of its own,
    RoPE — has a gradient in every leaf that weighs anything."""
    lp = params["layers"][1]
    h = jax.random.normal(jax.random.key(9), (B, T, 32))
    cfg = config(layer_pattern=None, n_layers=2, moe_every=1, pos="rope",
                 max_seq=T)
    assert cfg.shared_width == 40 != cfg.n_shared_experts * cfg.expert_d_ff
    row = tfm.layout(cfg, True)[1]
    assert (row.mixer, row.pre) == ("experts", "ln2")
    close(highest(tfm._sublayer, dict(lp, ln2=lp["ln"]), h, cfg, AX, row),
          highest(tfm.layer_forward, lp, h, config(), AX, tfm.EXPERTS))
    tree = jax.tree.map(jnp.asarray, tfm.init_params(
        np.random.default_rng(0), cfg))
    assert tree["layers"][0]["ws1"].shape == (32, 40)
    assert tree["layers"][0]["wk"].shape == (32, 32)  # 2 heads of 16
    toks, labs = weights.batches(64, 1, B, T, 7)
    grads = jax.grad(_mean_loss(cfg, toks[0], labs[0]))(tree)
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        assert np.isfinite(np.asarray(g)).all()
        # the router's bias picks experts and weighs nothing: no gradient
        assert float(jnp.abs(g).max()) > 0 or path[-1].key == "wg_bias", \
            jax.tree_util.keystr(path)


# -- experts -----------------------------------------------------------------------

def test_relu2():
    x = jnp.array([-2.0, -0.5, 0.0, 0.5, 3.0])
    assert (moe.activation("relu2")(x) == jnp.array(
        [0.0, 0.0, 0.0, 0.25, 9.0])).all()
    with pytest.raises(ValueError, match="relu2"):
        moe.activation("relu3")


@pytest.mark.parametrize("share", [2, 4, 16])
def test_the_shares_add_up_to_the_uncut_layer(params, share):
    """The guide's share test: the expert layer's outputs under
    `held_experts` (0, n), (n, n), ... — the shared expert counted
    once — add up to the reference's layer with every expert."""
    lp = params["layers"][1]
    h = jax.random.normal(jax.random.key(9), (B, T, 32))
    x = ref.rms_norm(h, lp["ln"]["g"], 1e-5)
    whole = ref.experts(lp, x, SPEC)
    shared = ref.ffn(x.reshape(B * T, 32), lp["ws1"], lp["ws2"]).reshape(
        B, T, 32)
    total = 0
    for first in range(0, 16, share):
        mine = dict(lp, w1=lp["w1"][first:first + share],
                    w2=lp["w2"][first:first + share])
        cfg = config(held_experts=(first, share))
        total = total + highest(tfm.layer_forward, mine, h, cfg, AX,
                                tfm.EXPERTS) - h - shared
    close(total + shared, whole, 1e-4)
    # and the reference cut the same way
    part = ref.experts(dict(lp, w1=lp["w1"][4:8], w2=lp["w2"][4:8]), x,
                       SPEC._replace(held_first=4))
    close(highest(tfm.layer_forward, dict(lp, w1=lp["w1"][4:8],
                                          w2=lp["w2"][4:8]), h,
                  config(held_experts=(4, 4)), AX, tfm.EXPERTS) - h, part,
          1e-4)


@pytest.mark.parametrize("gated", [False, True])
def test_a_width_padded_to_the_lanes_is_the_same_layer(params, monkeypatch,
                                                       gated):
    """On the TPU the experts' width takes zero columns up to the
    kernels' lanes (`moe.expert_width_pad`): the layer's value and
    every gradient are the unpadded layer's, and the padding's
    gradient goes nowhere."""
    lp = dict(params["layers"][1])
    if gated:
        lp["w3"] = lp["w1"][:, :, ::-1] * 0.5
    cfg = config(mlp_gated=gated, mlp_act="silu" if gated else "relu2",
                 shared_d_ff=0, n_shared_experts=0)
    h = jax.random.normal(jax.random.key(2), (B, T, 32))
    assert moe.expert_width_pad("cpu", 24) == 0
    assert moe.expert_width_pad("tpu", 1856) == 64
    assert moe.expert_width_pad("tpu", 2048) == 0

    def value(lp):
        out = tfm.layer_forward(lp, h, cfg, AX, tfm.EXPERTS)
        return (out * out).sum(), out

    (_, plain), grads = highest(jax.value_and_grad(value, has_aux=True), lp)
    monkeypatch.setattr(moe, "expert_width_pad",
                        lambda backend, width: -width % 16)  # 24 -> 32
    (_, padded), padded_grads = highest(
        jax.value_and_grad(value, has_aux=True), lp)
    close(padded, plain, 1e-6)
    for a, b in zip(jax.tree.leaves(padded_grads), jax.tree.leaves(grads)):
        assert a.shape == b.shape
        close(a, b, 1e-5)


def test_the_router_scores_every_expert_and_drops_nothing(params, batch):
    toks, _ = batch
    cfg = config(held_experts=(4, 4))
    held = dict(params, layers=[
        dict(lp, w1=lp["w1"][4:8], w2=lp["w2"][4:8]) if "w1" in lp else lp
        for lp in params["layers"]])
    s = pvar.session()
    counts = np.asarray(tfm.route_counts(held, toks[0], cfg))
    assert counts.shape == (2, 16)
    assert (counts.sum(1) == B * T * 4).all()
    assert s.read("moe_dropped_assignments") == 0
    assert s.read("moe_held_assignments") == counts[:, 4:8].sum()
    chosen = ref.chosen_experts(params, toks[0], SPEC)
    mine = np.asarray(tfm.route_experts(held, toks[0], cfg)[0])
    assert np.take_along_axis(np.asarray(chosen), mine, 1).all()


# -- what is not written raises -----------------------------------------------------

@pytest.mark.parametrize("axis, says", [
    ("tp", "tensor parallelism"), ("sp", "sequence parallelism"),
    ("ep", "expert parallelism"), ("pp", "pipeline parallelism")])
def test_a_pattern_under_an_axis_raises(params, axis, says):
    with pytest.raises(NotImplementedError, match=says):
        tfm.layer_forward(params["layers"][0], jnp.zeros((1, 8, 32)),
                          config(), tfm.Axes(**{axis: "x"}), tfm.SSM)


@pytest.mark.parametrize("kw, error", [
    (dict(layer_pattern="ME*"), ValueError),         # not n_layers letters
    (dict(layer_pattern="ME-EM"), ValueError),       # a dense FFN alone
    (dict(n_kv_heads=3), ValueError),                # 4 heads over 3
    (dict(ssm_groups=3), ValueError),                # 4 heads in 3 groups
    (dict(post_norm=True), NotImplementedError),     # no leaf for it
    (dict(attn="mla"), NotImplementedError),         # a block's mixer
    # the block reads n_kv_heads too since PR 42: 4 heads over 3
    (dict(layer_pattern=None, n_layers=2, n_kv_heads=3), ValueError)])
def test_a_config_the_layers_cannot_compute_raises(params, kw, error):
    cfg = config(**kw)
    with pytest.raises(error):
        tfm.layer_forward(params["layers"][0], jnp.zeros((1, 8, 32)), cfg, AX,
                          tfm._layer_kind(cfg, 0))


# -- the recomputation rule -----------------------------------------------------------

def test_the_three_kinds_are_kinds_of_the_rule():
    cfg = config(dtype=jnp.bfloat16)
    assert tfm._application_kinds(cfg) == list(PATTERN)
    n = B * T
    assert tfm.layer_costs(cfg, B, T, tfm.SSM).sizes == {
        ssm.SSM_IN: n * (32 + 96 + 4) * 2, ssm.SSM_CONV: n * 96 * 2,
        ssm.SSM_Y: n * 32 * 2}
    assert tfm.layer_costs(cfg, B, T, tfm.ATTENTION).sizes == {
        att.ATTN_OUT: n * 4 * (16 * 2 + 4), att.QKV: 3 * n * 64 * 2}
    assert tfm.layer_costs(cfg, B, T, tfm.EXPERTS).sizes == {
        tfm.MLP_UP: n * 40 * 2}
    order = remat.remat_order(tfm.step_costs(cfg, B, T)[0])
    assert {name for name, _ in order} == {
        ssm.SSM_IN, ssm.SSM_CONV, ssm.SSM_Y, att.ATTN_OUT, att.QKV,
        tfm.MLP_UP}
    held = dict(order)
    assert held[ssm.SSM_IN] == 2 * n * 132 * 2  # two M layers
    assert order[-1][0] == ssm.SSM_CONV  # spares least per byte
    for kind in PATTERN:
        assert set(tfm.layer_costs(cfg, B, T, kind).spared) == set(
            tfm.layer_costs(cfg, B, T, kind).sizes)


def test_the_rule_keeps_a_prefix_and_reckons_the_largest_layer():
    cfg = config(dtype=jnp.bfloat16, remat=True)
    order = remat.remat_order(tfm.step_costs(cfg, B, T)[0])
    params = 10 ** 6
    assert remat.remat_keep(*tfm.step_costs(cfg, B, T, params), None) \
        == ()
    last = ()
    for limit in range(10 ** 6, 4 * 10 ** 6, 10 ** 4):
        keep = remat.remat_keep(*tfm.step_costs(
            cfg, B, T, params, largest=4 * 10 ** 5), limit)
        assert keep == tuple(n for n, _ in order[:len(keep)])
        assert len(keep) >= len(last)
        last = keep
    assert last == tuple(n for n, _ in order)
    # the gradients of ONE application: the largest where it is given,
    # else the mean over the five
    assert remat.whole_step_peak(*tfm.step_costs(
        cfg, B, T, params, largest=4 * 10 ** 5)) - remat.whole_step_peak(
        *tfm.step_costs(cfg, B, T, params)) == 4 * 10 ** 5 - params // 5


def test_the_trace_hands_the_rule_the_largest_layer(monkeypatch):
    cfg = config(dtype=jnp.bfloat16, remat=True)
    tree = jax.eval_shape(lambda: jax.tree.map(
        jnp.asarray, tfm.init_params(np.random.default_rng(0), cfg)))
    seen = {}

    real = tfm.step_costs

    def costs(cfg, b, t, param_bytes=0, patches=0, largest=None):
        seen.update(param_bytes=param_bytes, largest=largest)
        return real(cfg, b, t, param_bytes, patches, largest)

    monkeypatch.setattr(tfm, "step_costs", costs)
    tfm._remat_names(tree, jax.ShapeDtypeStruct((B, T), jnp.int32), cfg)

    def nbytes(t):
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(t))

    assert seen["param_bytes"] == nbytes(tree)
    assert seen["largest"] == max(map(nbytes, tree["layers"])) \
        == nbytes(tree["layers"][1])  # an expert layer
    # a block config's keep-set stays on the mean (PERF.md 6, PR 35/37)
    block = tfm.Config(remat=True)
    tfm._remat_names(jax.eval_shape(lambda: jax.tree.map(
        jnp.asarray, tfm.init_params(np.random.default_rng(0), block))),
        jax.ShapeDtypeStruct((B, T), jnp.int32), block)
    assert seen["largest"] is None
