"""The seventh published model of models/transformer.py at toy widths on
the CPU: kinds of attention mixed by layer — a sliding window on three
layers of four with plain RoPE, the whole causal triangle on the fourth
with YaRN's blended frequencies and attention factor —, shared key
heads and top-k experts: the program against hand-written cases and
against the float32 reference (benchmark/reference/mellum2_decoder.py),
whose window is a dense boolean mask over all keys."""

import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, "tests"))

import lowered_text  # noqa: E402
from benchmark import manifest as mf  # noqa: E402
from benchmark import weights, weights_mellum2  # noqa: E402
from benchmark import weights_nemotron, weights_olmoe  # noqa: E402
from benchmark.reference import mellum2_decoder as ref  # noqa: E402
from benchmark.runners import mellum2_train as mt  # noqa: E402
from benchmark.runners import (nemotron_train, olmoe_train,  # noqa: E402
                               train_step)
from ompi_tpu.core import pvar  # noqa: E402
from ompi_tpu.models import remat  # noqa: E402
from ompi_tpu.models import transformer as tfm  # noqa: E402
from ompi_tpu.ops import attention as att  # noqa: E402

AX = tfm.Axes()
B, T = 2, 64
CELL = "mellum2-train-t16384"


def _config_file(name: str) -> dict:
    with open(os.path.join(HERE, "benchmark", "configs", name)) as f:
        return json.load(f)


TOY = _config_file("mellum2-12b-a2.5b.rehearsal.json")
SIZES = mt.model_sizes(dict(TOY, param_dtype="float32"))
SPEC = mt.reference_spec(SIZES)._replace(q_rows=16)


def config(**kw):
    return tfm.Config(**{**mt.program_config(SIZES).__dict__,
                         "dtype": jnp.float32, "remat": False, **kw})


@pytest.fixture(scope="module")
def params():
    return weights_mellum2.device_init(SIZES, 7)


@pytest.fixture(scope="module")
def batch():
    return weights.batches(SIZES["vocab"], 2, B, T, 7)


def highest(fn, *args, **kw):
    with jax.default_matmul_precision("highest"):
        return fn(*args, **kw)


def close(a, b, tol=2e-5, atol=0.0):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.abs(a - b).max() <= atol + tol * max(np.abs(b).max(), 1e-30)


# -- the window ----------------------------------------------------------------

def _dense_window(t: int, window: int):
    """Query i sees key j: j <= i and i - j < window, pair by pair."""
    return np.array([[j <= i and i - j < window for j in range(t)]
                     for i in range(t)])


def _masked_softmax_attention(q, k, v, seen):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _qkvg(t, heads=2, dim=8, seed=0):
    keys = jax.random.split(jax.random.key(seed), 4)
    return [jax.random.normal(k, (1, t, heads, dim)) for k in keys]


@pytest.mark.parametrize("window", [1, 3, 10, 15])
def test_mha_under_a_window_is_the_dense_mask(window):
    """T = 10, no multiple of 3: windows of one key, a few, the whole
    sequence and more than it — values and all three gradients."""
    t = 10
    q, k, v, g = _qkvg(t)

    def grads(fn):
        return highest(jax.value_and_grad(
            lambda q, k, v: (fn(q, k, v) * g).sum(), (0, 1, 2)), q, k, v)

    seen = jnp.asarray(_dense_window(t, window))
    got = grads(lambda q, k, v: att.mha(q, k, v, window=window))
    want = grads(lambda q, k, v: _masked_softmax_attention(q, k, v, seen))
    close(got[0], want[0])
    for a, b in zip(got[1], want[1]):
        close(a, b, 1e-4)
    if window >= t:  # no narrower than the causal mask: the same rows
        close(highest(att.mha, q, k, v, window=window),
              highest(att.mha, q, k, v))


@pytest.mark.parametrize("kw, says", [
    (dict(window=0), "positive number of keys"),
    (dict(window=4, causal=False), "under the causal mask"),
    (dict(window=4, segments=jnp.zeros((1, 10), jnp.int32)),
     "with a segment mask")])
def test_a_window_no_mask_can_hold_raises(kw, says):
    q, k, v, _ = _qkvg(10)
    for entry in (att.mha, att.attention):
        with pytest.raises(ValueError, match=says):
            entry(q, k, v, **kw)


@pytest.mark.parametrize("tile, window, walked, whole", [
    (1024, 1024, 31, 136), (512, 1024, 93, 528), (256, 1024, 310, 2080),
    (16, 1, 4, 10), (16, 17, 7, 10), (16, 18, 9, 10), (16, 4096, 10, 10)])
def test_the_tiles_a_window_walks(tile, window, walked, whole):
    """The rule's count against the pairs of tiles that hold a pair the
    mask keeps, enumerated (toy lengths), and the issue's three."""
    t = 16384 if tile >= 256 else 64
    assert att.window_tiles(t, tile, window) == walked
    assert att.window_tiles(t, tile) == whole
    if t == 64:
        seen = _dense_window(t, window).reshape(t // tile, tile, t // tile,
                                                tile)
        assert int(seen.any((1, 3)).sum()) == walked


KERNEL_T, KERNEL_TILE = 512, 128


@pytest.mark.parametrize("window", [1, 128, 130, 600])
def test_the_interpreted_kernels_are_mha_under_the_window(window):
    """The blockwise kernels (interpret mode, tiles of 128, the
    two-kernel backward a window takes) against att.mha: values and
    gradients."""
    q, k, v, g = _qkvg(KERNEL_T, 2, 128, seed=1)

    def grads(fn):
        return highest(jax.value_and_grad(
            lambda q, k, v: (fn(q, k, v) * g).sum(), (0, 1, 2)), q, k, v)

    got = grads(lambda q, k, v: att.blockwise_mha(
        q, k, v, KERNEL_TILE, interpret=True, window=window))
    want = grads(lambda q, k, v: att.mha(q, k, v, window=window))
    close(got[0], want[0], atol=1e-3)  # a sum of 131,072 signed terms
    for a, b in zip(got[1], want[1]):
        close(a, b, 1e-4, atol=1e-5)


@pytest.mark.parametrize("where", ["inside", "first_row", "last_row"])
def test_a_marker_is_seen_to_the_windows_edge_and_no_further(where):
    """The comparison's exact probe through the interpreted kernels: v
    at one position replaced by a marker moves the rows position ..
    position + window - 1 and not one bit of any other."""
    window = 130
    q, k, v, _ = (a.astype(jnp.bfloat16)
                  for a in _qkvg(KERNEL_T, 2, 128, seed=2))
    p = {"inside": 2 * KERNEL_TILE + 37, "first_row": KERNEL_TILE,
         "last_row": 2 * KERNEL_TILE - 1}[where]
    assert p + window < KERNEL_T

    def attend(v):
        return att.blockwise_mha(q, k, v, KERNEL_TILE, interpret=True,
                                 window=window)

    marked = v.at[:, p].set(jnp.asarray(3e4, v.dtype))
    rows = np.asarray((attend(v) != attend(marked)).any((0, 2, 3)))
    assert not rows[:p].any() and not rows[p + window:].any()
    assert rows[p + window - 1] and rows[p]


def test_the_runners_marker_probe_reads_no_leak_and_every_edge():
    got = mt.window_probe(SIZES, B, T, 11)
    assert (got["window_leak_rows"], got["window_edge_seen"],
            got["window_edge_wanted"]) == (0, 3, 3)
    for p in got["window_probe_at"]:
        assert 0 < p and p + SIZES["window"] < T
    assert mt.marker_positions(16384, 1024, 512) == [1281, 1536, 2047]


# -- positions -----------------------------------------------------------------

def _published() -> dict:
    return _config_file("mellum2-12b-a2.5b.json")


def _yarn_by_the_lines(head_dim, theta, factor, original, fast, slow):
    """Tentpole 1's lines, written again in float64."""
    half = head_dim // 2
    e = np.array([theta ** (-i / half) for i in range(half)])

    def corr(n):
        return head_dim * math.log(original / (2 * math.pi * n)) \
            / (2 * math.log(theta))

    low = max(math.floor(corr(fast)), 0)
    high = min(math.ceil(corr(slow)), head_dim - 1)
    r = np.clip((np.arange(half) - low) / (high - low), 0, 1)
    return e * (1 - r) + e / factor * r, low, high


@pytest.mark.parametrize("which", ["published", "toy"])
def test_yarns_table_is_the_lines_written_again(which):
    sizes = mt.model_sizes(_published()) if which == "published" else SIZES
    cfg = mt.program_config(sizes)
    half = sizes["head_dim"] // 2
    want, low, high = _yarn_by_the_lines(sizes["head_dim"], *sizes["yarn"][:5])
    assert tfm.yarn_range(half, cfg.rope_full) == (low, high)
    if which == "published":
        assert (low, high) == (18, 35)
        assert cfg.rope_full.attention_factor == 1.2772588722239782 \
            == 0.1 * math.log(16) + 1
    got = np.asarray(tfm.rope_frequencies(half, cfg.rope_full), np.float64)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    np.testing.assert_allclose(got[:low + 1], want[:low + 1], rtol=2e-6)
    np.testing.assert_allclose(got[high:] * 16, want[high:] * 16, rtol=2e-6)
    # the windowed layers': plain frequencies, nothing scaled
    plain = np.asarray(tfm.rope_frequencies(half, cfg.rope_window))
    np.testing.assert_allclose(plain, sizes["sliding_theta"] ** (
        -np.arange(half) / half), rtol=2e-6)
    np.testing.assert_allclose(np.asarray(ref.inv_freq(
        sizes["head_dim"], ref.FULL, mt.reference_spec(sizes))), want,
        rtol=2e-6)


def test_rope_turns_by_the_table_and_scales_by_the_factor():
    """A `Rope` rotates pair i of position t by t x its frequency and
    multiplies cos and sin by the attention factor; a plain theta is
    the function every other configuration calls."""
    rp = mt.program_config(SIZES).rope_full
    x = jax.random.normal(jax.random.key(5), (1, 12, 2, 16))
    pos = jnp.arange(12)
    freq = np.asarray(tfm.rope_frequencies(8, rp), np.float64)
    ang = np.arange(12)[:, None] * freq[None]
    a, b = np.asarray(x[..., :8], np.float64), np.asarray(x[..., 8:],
                                                         np.float64)
    cos, sin = (f(ang)[None, :, None] * rp.attention_factor
                for f in (np.cos, np.sin))
    close(tfm.rope(x, pos, rp),
          np.concatenate([a * cos - b * sin, b * cos + a * sin], -1), 1e-5)
    close(tfm.rope(x, pos, tfm.Rope(theta=500000.0)),
          tfm.rope(x, pos, 500000.0), 1e-7)


# -- the kinds of layers ---------------------------------------------------------

def test_the_kinds_of_the_published_28_layers():
    """`layer_types` as the source has it: sliding x 3, full, seven
    times; every layer's feed-forward part the experts."""
    published = _published()
    sizes = mt.model_sizes(dict(published, num_hidden_layers=28))
    cfg = mt.program_config(sizes)
    assert cfg.attn_layers == "wwwf" * 7 and cfg.attn_window == 1024
    for i in range(28):
        kind = tfm._layer_kind(cfg, i)
        assert kind == tfm.Block(moe=True, windowed=i % 4 != 3)
        assert [s.mixer for s in tfm.layout(cfg, kind)] == [
            "window_attention" if i % 4 != 3 else "attention", "experts"]
    assert published["layer_types"] == ([ref.SLIDING] * 3 + [ref.FULL]) * 7
    assert mt.model_sizes(published)["layer_types"] == (
        ref.SLIDING,) * 3 + (ref.FULL,)
    assert (published["num_experts"], published["router_experts"],
            published["held_first"]) == (64, 64, 0)


def test_the_published_tree_is_the_issues_arithmetic():
    sizes = mt.model_sizes(_published())
    leaves = jax.tree.leaves(weights_mellum2.plan(sizes),
                             is_leaf=lambda t: isinstance(t, tuple))
    assert sum(math.prod(shape) for shape, _ in leaves) == 1_784_238_336 \
        == _published()["parameters"]["total"]


REFUSALS = [
    (dict(), dict(sp="x"), NotImplementedError, "sequence parallelism"),
    (dict(), dict(pp="x"), NotImplementedError, "pipeline parallelism"),
    (dict(attn="mla"), {}, NotImplementedError, "latent attention"),
    (dict(layer_pattern="E*E*"), {}, NotImplementedError, "a layer pattern"),
    (dict(mtp_layers=1), {}, ValueError, "multi-token-prediction module's"),
    (dict(mtp_layers=1, mtp_attn="d"), {}, ValueError,
     "a delta-rule module is not written"),
    (dict(attn_layers=None), {}, ValueError, "for Config.attn_layers to say"),
    (dict(attn_layers="wwf"), {}, ValueError, "expected n_layers = 4"),
    (dict(attn_layers="wwxf"), {}, ValueError, "letters of 'w'"),
    (dict(attn_window=0), {}, ValueError, "is none"),
]


@pytest.mark.parametrize("kw, axes, error, says", REFUSALS,
                         ids=[r[3].replace(" ", "_") for r in REFUSALS])
def test_what_the_mix_cannot_run_under_raises(kw, axes, error, says):
    cfg = config(**kw)
    with pytest.raises(error, match=says):
        tfm._check_supported(cfg, tfm.Axes(**axes), True, 0)
    if not axes and "attn" not in kw and "layer_pattern" not in kw:
        with pytest.raises(error, match=says):
            tfm._layer_kind(cfg, 0) if error is ValueError and \
                cfg.attn_layers else tfm._check_supported(cfg, AX, True, 0)


def test_the_mix_runs_under_the_axes_it_can():
    tfm._check_supported(config(), tfm.Axes(dp="x", tp="y"), True, 0)


# -- the whole model against the reference ------------------------------------

def _mean_loss(cfg, toks, labs):
    def mean_loss(p):
        nll, count = tfm.loss_local(p, toks, labs, cfg, AX)
        return nll / count
    return mean_loss


@pytest.mark.parametrize("remat_on", [False, True], ids=["plain", "remat"])
def test_loss_and_every_gradient_are_the_references(params, batch, remat_on):
    toks, labs = batch
    loss, grads = highest(jax.jit(jax.value_and_grad(
        _mean_loss(config(remat=remat_on), toks[0], labs[0]))), params)
    r_loss, r_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, toks[0], labs[0], SPEC)))(params)
    close(loss, r_loss, 1e-6)
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(r_grads)):
        assert float(jnp.abs(r).max()) > 0, path
        close(g, r, 1e-4)


def test_the_reference_step_is_its_whole_model_gradient(params, batch):
    toks, labs = batch
    val0, grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, toks[0], labs[0], SPEC)))(params)
    new, val = ref.sgd_step(jax.tree.map(jnp.copy, params), toks[0],
                            labs[0], 0.5, SPEC)
    close(val, val0, 1e-6)
    for p, n, g in zip(*map(jax.tree.leaves, (params, new, grads))):
        close(p - n, 0.5 * g, 1e-4, atol=1e-6)  # p - n cancels


@pytest.mark.parametrize("kind", [ref.SLIDING, ref.FULL])
def test_the_probe_reads_a_layers_attention(params, batch, kind):
    toks, _ = batch
    layer = mt.probed_layers(SIZES)[kind]
    assert layer == {ref.SLIDING: 0, ref.FULL: 3}[kind]
    close(highest(tfm.attn_probe, params, toks[0], config(), layer),
          ref.attention_out(params, toks[0], layer, SPEC), 1e-4)


@pytest.mark.parametrize("fault", ["window_ignored", "yarn_left_out",
                                   "factor_left_out", "wrong_key_heads"])
def test_a_fault_shows_in_its_layers_attention_output(params, batch, fault):
    """What `swa_out_gap` / `full_out_gap` are there for: each fault
    moves its kind's mixer output by far more than bfloat16 does."""
    toks, _ = batch
    cfg = config()
    kind = ref.SLIDING if fault == "window_ignored" else ref.FULL
    broken = {
        "window_ignored": dict(attn_window=4 * T),
        "yarn_left_out": dict(rope_full=cfg.rope_window),
        "factor_left_out": dict(rope_full=tfm.Rope(**{
            **cfg.rope_full.__dict__, "attention_factor": 1.0})),
        "wrong_key_heads": dict(n_kv_heads=4)}[fault]
    layer = mt.probed_layers(SIZES)[kind]
    mine = params
    if fault == "wrong_key_heads":  # head i on key head i: another pairing
        mine = dict(params, layers=[dict(lp, **{
            w: jnp.concatenate([lp[w], lp[w]], 1) for w in ("wk", "wv")})
            for lp in params["layers"]])
    want = ref.attention_out(params, toks[0], layer, SPEC)
    got = highest(tfm.attn_probe, mine, toks[0], config(**broken), layer)
    assert mt.rel_err(got, want) > 0.1


def test_the_seeded_tree_is_the_programs_tree(params):
    mine = tfm.init_params(np.random.default_rng(0), config())
    assert jax.tree.structure(mine) == jax.tree.structure(params)
    assert [a.shape for a in jax.tree.leaves(mine)] == [
        a.shape for a in jax.tree.leaves(params)]
    like = tfm.param_specs(config(), AX)
    assert jax.tree.structure(like, is_leaf=lambda x: x is None or isinstance(
        x, jax.sharding.PartitionSpec)) == jax.tree.structure(params)
    plan = weights_mellum2.plan(SIZES)
    scale = 1 / math.sqrt(4 * 16) / math.sqrt(2 * 4)
    assert plan["layers"][0]["wo"] == ((64, 64), scale)
    np.testing.assert_allclose(mine["layers"][0]["wo"].std(), scale,
                               rtol=0.1)


def test_a_bfloat16_run_holds_the_rehearsal_limits():
    """The comparison that decides `correct`, at toy widths: the
    program's first steps in bfloat16 against the float32 reference
    under the cell's `rehearsal_limits`."""
    _, _, traffic, conf, limits = mf.cell_inputs(mf.load(), CELL, True)
    sizes = mt.model_sizes(conf)
    n, lr, seed = traffic["check_steps"], traffic["lr"], 2147483659
    params = weights_mellum2.device_init(sizes, seed)
    toks, labs = weights.batches(sizes["vocab"], traffic["n_batches"],
                                 traffic["batch"], traffic["seq"], seed)
    step = mt.build_step(sizes, lr)
    probe = mt.probes(sizes, params, toks, n, seed)
    _, program = mt.first_steps(step, params, toks, labs, sizes, seed, n)
    checks = mt.checks_against(
        program, mt.reference_steps(sizes, toks, labs, seed, lr, n), limits,
        sizes) + mt.first_batch_checks(
            probe, mt.reference_first_batch(sizes, toks, seed), limits)
    assert {c[0] for c in checks} == set(limits)
    for name, value, limit in checks:
        assert value <= limit, (name, value, limit)
    assert probe["moe_dropped_assignments"] == 0


# -- the step ------------------------------------------------------------------

@pytest.fixture(scope="module")
def compiled(params, batch):
    toks, labs = batch
    cfg = config(remat=True)
    step = jax.jit(tfm.make_train_step(cfg, AX, tfm.param_specs(cfg, AX)))
    s = pvar.session()
    text = step.lower(params, toks[0], labs[0]).compile().as_text()
    return text, {n: s.read(n) for n in (
        "attn_window_layers", "attn_full_layers", "attn_gqa_layers",
        "attn_reference_layers", "attn_window_tiles", "attn_causal_tiles",
        "remat_whole_applications", "moe_full_layers")}


def test_scopes_and_counters_of_the_compiled_step(compiled):
    text, counted = compiled
    assert counted == {
        "attn_window_layers": 3, "attn_full_layers": 1, "attn_gqa_layers": 4,
        "attn_reference_layers": 4, "attn_window_tiles": 0,
        "attn_causal_tiles": 0, "remat_whole_applications": 4,
        "moe_full_layers": 4}
    for layer, scope in ((0, "attn_window"), (1, "attn_window"),
                         (2, "attn_window"), (3, "attn_full")):
        assert f"(layer_{layer})/jit(layer)/attn_core/{scope}/" in text
    assert "(layer_3)/jit(layer)/attn_core/attn_window" not in text
    assert "(layer_0)/jit(layer)/attn_core/attn_full" not in text
    assert "attn_proj/qk_rope" in text and "while" not in text


def test_a_config_of_one_kind_has_neither_scope(params, batch):
    toks, labs = batch
    cfg = config(attn_layers=None, attn_window=0, rope_window=None)
    step = jax.jit(tfm.make_train_step(cfg, AX, tfm.param_specs(cfg, AX)))
    s = pvar.session()
    text = step.lower(params, toks[0], labs[0]).compile().as_text()
    assert "attn_core" in text and "attn_full" not in text \
        and "attn_window" not in text
    assert s.read("attn_full_layers") == s.read("attn_window_layers") == 0


def test_the_step_lowered_for_the_tpu_holds_the_windows_kernels(
        params, monkeypatch):
    """With the rule answering for the TPU, at a length a tile divides:
    three layers on the kernels under the window (two-kernel backward),
    one on the causal ones (fused), no att.mha; the tiles counted."""
    rule = att.blockwise_tile
    monkeypatch.setattr(att, "blockwise_tile", lambda backend, *a, **kw: rule(
        "tpu", *a, **kw))
    cfg = config(dtype=jnp.bfloat16)
    tok = jax.ShapeDtypeStruct((1, 512), jnp.int32)
    step = jax.jit(tfm.make_train_step(cfg, AX, tfm.param_specs(cfg, AX)))
    s = pvar.session()
    text = step.trace(params, tok, tok).lower(
        lowering_platforms=("tpu",)).as_text()
    assert s.read("attn_blockwise_layers") == 4 \
        and s.read("attn_reference_layers") == 0
    # window 16 at tiles of 512 (the first of the window's list that
    # divides 512): one tile a layer, the triangle's one
    assert (s.read("attn_window_tiles"), s.read("attn_causal_tiles")) \
        == (3, 3)
    # the windowed kernels' two-kernel backward beside the fused one
    assert "splash_mha_dq" in text and "splash_mha_dkv_no_residuals" in text


#: sha256 of `lowered_text.canonical` of the CPU-lowered rehearsal steps
#: at the parent commit 0fd3f76 (jax 0.9.0, bfloat16, batch 2 x 64):
#: `build_step(...).lower(...).as_text()` of three accepted runners —
#: the two with experts as PR 44 left them, whose full expert layer
#: (OLMoE's layer, Nemotron's never-taken branch) moves its rows as
#: the bounded one does: bc56a7fe... and 81d66122... before it. PR 45
#: re-recorded all three: one loss body, the label's logit by a mask
#: (64ef42df..., a9fb0944... and 5ece6908... before it). PR 50 added
#: this file's own configuration at its parent a40151f: the fields that
#: PR brought (`qk_norm` per head, `NO_ROPE`, `mtp_attn`) at their
#: defaults leave the mixed step its text
PARENT = {
    "mellum2-12b-a2.5b":
        "df7f73ea5fb1cf8ae2cf5895c1f7bd4aad8e527d00e960be1e083dfe6f6fb53d",
    "opt-30b":
        "c90271158275193b3843c20b3f0af0c5a528ab2fa0345c3d1e2ed33a29abe869",
    "olmoe-1b-7b":
        "d64dc9ac8d2d4b8da2911ff1e10ee94c834a4d3e4290aa34db0a3ebb5d999b08",
    "nemotron-3-nano-30b-a3b":
        "1224dbf654afbbb6e92872008c9fd886dbf0fc73203b79bd6482c755acc5ee74",
}
RUNNERS = {"mellum2-12b-a2.5b": (mt, weights_mellum2),
           "opt-30b": (train_step, weights),
           "olmoe-1b-7b": (olmoe_train, weights_olmoe),
           "nemotron-3-nano-30b-a3b": (nemotron_train, weights_nemotron)}


def rehearsal_step_text(name: str) -> str:
    runner, made = RUNNERS[name]
    sizes = runner.model_sizes(_config_file(name + ".rehearsal.json"))
    toks, labs = weights.batches(sizes["vocab"], 1, 2, 64, 1)
    return lowered_text.canonical(runner.build_step(sizes, 0.01).lower(
        made.device_init(sizes, 1), toks[0], labs[0]).as_text())


@pytest.mark.parametrize("name", sorted(PARENT))
def test_a_window_that_is_absent_changes_no_accepted_step(name):
    if jax.__version__ != "0.9.0":
        pytest.skip("the recorded text is jax 0.9.0's")
    assert lowered_text.sha256(rehearsal_step_text(name)) == PARENT[name]


# -- what the recomputation rule is told ----------------------------------------

def test_the_rule_prices_the_two_kinds_apart():
    sizes = mt.model_sizes(_published())
    cfg = mt.program_config(sizes)
    apps, fixed = tfm.step_costs(cfg, 1, 16384, 3_568_476_672)
    assert len(apps) == 4 and apps[0] == apps[1] == apps[2] != apps[3]
    n, heads, dh, t, w = 16384, 32, 128, 16384, 1024
    assert apps[3].spared[att.ATTN_OUT] == 2 * n * t * heads * dh
    assert apps[0].spared[att.ATTN_OUT] == \
        2 * n * (w * (2 * t - w) // t) * heads * dh
    assert apps[0].spared[att.ATTN_OUT] * 8 < apps[3].spared[att.ATTN_OUT]
    assert apps[0].sizes == apps[3].sizes
    # at no more keys than the window the two kinds cost the same
    short = tfm.step_costs(cfg, 1, 1024)[0]
    assert short[0].spared == short[3].spared
    assert att.ATTN_OUT in dict(remat.remat_order(apps)) \
        and fixed > 3_568_476_672
