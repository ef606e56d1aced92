"""The ninth published model of models/transformer.py at toy widths on
the CPU: a sliding window NARROWER than any kernel tile on three layers
of four, an RMSNorm on every head of q and of k, RoPE on the windowed
layers and none on the full ones, a sigmoid router over experts of
which a chip holds a share, and a multi-token-prediction module whose
layer is of a kind of its own (full attention behind a windowed last
layer): the program against hand-written cases and against the float32
reference (benchmark/reference/kexaone_decoder.py), whose window is a
dense boolean mask over all keys."""

import json
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from benchmark import manifest as mf  # noqa: E402
from benchmark import weights, weights_kexaone  # noqa: E402
from benchmark.reference import kexaone_decoder as ref  # noqa: E402
from benchmark.runners import kexaone_train as kt  # noqa: E402
from ompi_tpu.core import pvar  # noqa: E402
from ompi_tpu.models import transformer as tfm  # noqa: E402
from ompi_tpu.ops import attention as att  # noqa: E402
from ompi_tpu.ops import moe  # noqa: E402

AX = tfm.Axes()
B, T = 2, 64
CELL = "kexaone-train-t8192"


def _config_file(name: str) -> dict:
    with open(os.path.join(HERE, "benchmark", "configs", name)) as f:
        return json.load(f)


TOY = _config_file("k-exaone-236b-a23b.rehearsal.json")
SIZES = kt.model_sizes(dict(TOY, param_dtype="float32"))
SPEC = kt.reference_spec(SIZES)._replace(q_rows=16)
LIMITS = mf.workload_file(CELL)["rehearsal_limits"]


def config(**kw):
    return tfm.Config(**{**kt.program_config(SIZES).__dict__,
                         "dtype": jnp.float32, "remat": False, **kw})


@pytest.fixture(scope="module")
def params():
    return weights_kexaone.device_init(SIZES, 7)


@pytest.fixture(scope="module")
def batch():
    return weights.batches(SIZES["vocab"], 2, B, T, 7)


def highest(fn, *args, **kw):
    with jax.default_matmul_precision("highest"):
        return fn(*args, **kw)


def close(a, b, tol=2e-5, atol=0.0):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.abs(a - b).max() <= atol + tol * max(np.abs(b).max(), 1e-30)


# -- the tiles of a window narrower than they are ---------------------------------

def _dense_window(t: int, window):
    return np.array([[j <= i and (not window or i - j < window)
                      for j in range(t)] for i in range(t)])


@pytest.mark.parametrize("tile", [16, 8, (16, 8), (32, 8), (32, 16),
                                  (8, 32)])
@pytest.mark.parametrize("window", [None, 1, 5, 8, 9, 40])
def test_the_tiles_walked_are_those_that_hold_a_kept_pair(tile, window):
    """`window_tiles` against the tiles enumerated over a dense mask,
    square and rectangular, the window narrower than a tile, as wide
    and wider — counted in squares of the tile's shorter side."""
    t = 64
    rows, keys = tile if isinstance(tile, tuple) else (tile, tile)
    seen = _dense_window(t, window).reshape(t // rows, rows, t // keys, keys)
    unit = min(rows, keys)
    assert att.window_tiles(t, tile, window) == int(
        seen.any((1, 3)).sum()) * (rows // unit) * (keys // unit)


def test_the_issues_counts_and_the_rule():
    kept = sum(min(i + 1, 128) for i in range(8192))
    assert kept == 1_040_448
    assert [att.window_tiles(8192, b, 128) for b in (512, 256, 128)] \
        == [31, 63, 127]
    assert round(31 * 512 * 512 / kept, 1) == 7.8
    assert round(63 * 256 * 256 / kept, 1) == 4.0
    # a window no narrower than the tiles of PR 43 keeps its rule
    assert att._WINDOW_TILES == (512, 1024, 256)
    assert att.blockwise_tile("tpu", 16384, 16384, 128, window=1024) == 512
    assert att.blockwise_tile("tpu", 16384, 16384, 128, window=256) == 512
    assert att.blockwise_tile("tpu", 8192, 8192, 128) == 1024
    # a narrower one too: the chip read 13.76 ms at 512 against 18.57
    # at 128 (PERF.md 6, PR 50); what the reader divides by is what
    # `window_tiles` counts for that tile
    tile = att.blockwise_tile("tpu", 8192, 8192, 128, window=128)
    assert tile == 512
    assert att.blockwise_tile("cpu", 8192, 8192, 128, window=128) is None
    whole = att.window_tiles(8192, tile)
    side = (math.isqrt(8 * whole + 1) - 1) // 2
    assert 8192 // side == tile  # layer_metrics/_mellum.tile_facts


KERNEL_T = 512


def test_the_interpreted_kernels_under_a_window_narrower_than_the_tile():
    """The library's kernels (interpret mode) under a window of 32, a
    quarter of the tile of 128, against att.mha: values and gradients;
    and the marker probe inside a tile, on a tile's first row and on
    its last: seen to the window's edge and no further."""
    tile, window = 128, 32
    keys = jax.random.split(jax.random.key(1), 4)
    q, k, v, g = (jax.random.normal(kk, (1, KERNEL_T, 2, 128))
                  for kk in keys)

    def grads(fn):
        return highest(jax.value_and_grad(
            lambda q, k, v: (fn(q, k, v) * g).sum(), (0, 1, 2)), q, k, v)

    got = grads(lambda q, k, v: att.blockwise_mha(
        q, k, v, tile, interpret=True, window=window))
    want = grads(lambda q, k, v: att.mha(q, k, v, window=window))
    close(got[0], want[0], atol=1e-3)
    for a, b in zip(got[1], want[1]):
        close(a, b, 1e-4, atol=1e-5)
    qb, kb, vb = (a.astype(jnp.bfloat16) for a in (q, k, v))

    def attend(v):
        return att.blockwise_mha(qb, kb, v, tile, interpret=True,
                                 window=window)

    plain = attend(vb)
    for p in kt.marker_positions(KERNEL_T, window, 96):
        marked = vb.at[:, p].set(jnp.asarray(3e4, vb.dtype))
        rows = np.asarray((plain != attend(marked)).any((0, 2, 3)))
        assert not rows[:p].any() and not rows[p + window:].any()
        assert rows[p + window - 1] and rows[p]


@pytest.mark.parametrize("off", [-1, 1], ids=["narrow", "wide"])
def test_a_window_one_key_off_is_caught_exactly(off, monkeypatch):
    """`window_leak_rows` / `window_edge_missed`: the runner's marker
    probe reads 0 and 0 on the program, and a window one key too wide
    or too narrow breaks one of them at every position tried."""
    got = kt.window_probe(SIZES, B, T, 11)
    assert (got["window_leak_rows"], got["window_edge_seen"],
            got["window_edge_wanted"]) == (0, 3, 3)
    for p in got["window_probe_at"]:
        assert 0 < p and p + SIZES["window"] < T
    assert kt.marker_positions(8192, 128, 512) == [1281, 1536, 2047]
    attention = att.attention
    monkeypatch.setattr(att, "attention", lambda q, k, v, window=None, **kw:
                        attention(q, k, v, window=window + off, **kw))
    broken = kt.window_probe(SIZES, B, T, 11)
    assert (broken["window_leak_rows"], broken["window_edge_seen"]) \
        == ((3, 3) if off > 0 else (0, 0))


# -- the kinds of layers -----------------------------------------------------------

def _published() -> dict:
    return _config_file("k-exaone-236b-a23b.json")


def test_the_kinds_of_the_published_48_layers_and_of_the_module():
    published = _published()
    sizes = kt.model_sizes(dict(published, num_hidden_layers=48))
    cfg = kt.program_config(sizes)
    assert cfg.attn_layers == "wwwf" * 12 and cfg.attn_window == 128
    assert (cfg.rope_window, cfg.rope_full, cfg.pos, cfg.rope_theta) == (
        None, tfm.NO_ROPE, "rope", 1e6)
    assert (cfg.qk_norm, cfg.mtp_attn, cfg.held_experts, cfg.n_experts) \
        == (tfm.PER_HEAD, tfm.FULL, (0, 8), 128)
    for i in range(48):
        kind = tfm._layer_kind(cfg, i)
        assert kind == tfm.Block(moe=i > 0, windowed=i % 4 != 3)
        assert [s.mixer for s in tfm.layout(cfg, kind)] == [
            "window_attention" if i % 4 != 3 else "attention",
            "experts" if i else "ffn"]
    # the module: an expert layer over the whole triangle, whatever the
    # trunk's last layer is — in the cut it is windowed
    cut = kt.program_config(kt.model_sizes(published))
    assert cut.attn_layers == "wwwfw"
    assert tfm._mtp_kind(cut) == tfm.Block(moe=True, windowed=False) \
        != tfm._layer_kind(cut, 4)
    assert tfm._application_kinds(cut) == [
        tfm._layer_kind(cut, i) for i in range(5)] + [tfm._mtp_kind(cut)]
    assert published["layer_types"] == ([ref.SLIDING] * 3 + [ref.FULL]) * 12
    assert published["mlp_layer_types"] == ["dense"] + ["sparse"] * 47
    assert (published["num_experts"], published["router_experts"],
            published["held_first"]) == (8, 128, 0)


def test_the_published_tree_is_the_issues_arithmetic():
    sizes = kt.model_sizes(_published())
    leaves = jax.tree.leaves(weights_kexaone.plan(sizes),
                             is_leaf=lambda t: isinstance(t, tuple))
    assert sum(math.prod(shape) for shape, _ in leaves) == 3_033_362_560 \
        == _published()["parameters"]["total"]


def test_the_rule_prices_the_module_as_a_full_layer():
    sizes = kt.model_sizes(_published())
    cfg = kt.program_config(sizes)
    apps, _ = tfm.step_costs(cfg, 1, 8192, 6_066_725_120)
    assert len(apps) == 6 and apps[5] == apps[3] != apps[4]
    assert apps[1] == apps[2] == apps[4] != apps[0]
    n, heads, dh, t, w = 8192, 64, 128, 8192, 128
    assert apps[5].spared[att.ATTN_OUT] == 2 * n * t * heads * dh
    assert apps[4].spared[att.ATTN_OUT] == \
        2 * n * (w * (2 * t - w) // t) * heads * dh


REFUSALS = [
    (dict(), dict(sp="x"), NotImplementedError, "sequence parallelism"),
    (dict(), dict(pp="x"), NotImplementedError, "pipeline parallelism"),
    (dict(), dict(tp="x"), NotImplementedError,
     "QK-norm per head .* under tensor parallelism"),
    (dict(attn="mla"), {}, NotImplementedError, "latent attention"),
    (dict(layer_pattern="E*E*E"), {}, NotImplementedError,
     "a layer pattern"),
    (dict(mtp_attn=None), {}, ValueError, "multi-token-prediction module's"),
    (dict(mtp_attn="d"), {}, ValueError, "delta-rule module is not written"),
    (dict(attn_layers=None, attn_window=0), {}, ValueError,
     "for Config.attn_layers to say"),
    (dict(rope_window=tfm.NO_ROPE), {}, ValueError, "says pos='none'"),
    (dict(pos="none"), {}, ValueError, "NO_ROPE takes the rotation from ONE"),
    (dict(qk_norm="heads"), {}, ValueError, "qk_norm='heads': expected"),
]


@pytest.mark.parametrize("kw, axes, error, says", REFUSALS,
                         ids=[r[3][:24].replace(" ", "_") for r in REFUSALS])
def test_what_it_still_cannot_give_raises_by_name(kw, axes, error, says):
    with pytest.raises(error, match=says):
        tfm._check_supported(config(**kw), tfm.Axes(**axes), True, 0)


def test_it_runs_where_it_can():
    tfm._check_supported(config(), tfm.Axes(dp="x"), True, 0)
    tfm._check_supported(config(qk_norm=False), tfm.Axes(dp="x", tp="y"),
                         True, 0)


# -- the whole model against the reference ---------------------------------------

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
        if "wg_bias" in jax.tree_util.keystr(path):  # a buffer: no gradient
            assert float(jnp.abs(g).max()) == float(jnp.abs(r).max()) == 0
            continue
        assert float(jnp.abs(r).max()) > 0, path
        close(g, r, 1e-4)


def test_each_loss_is_the_references(params, batch):
    toks, labs = batch
    ce, mtp = ref.losses(params, toks[0], labs[0], SPEC)
    main = highest(jax.jit(_mean_loss(config(mtp_weight=0.0), toks[0],
                                      labs[0])), params)
    both = highest(jax.jit(_mean_loss(config(mtp_weight=1.0), toks[0],
                                      labs[0])), params)
    close(main, ce, 1e-6)
    close(both - main, mtp, 1e-5)
    assert float(mtp) > 1.0


def test_the_reference_step_is_its_whole_model_gradient(params, batch):
    toks, labs = batch
    val0, grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, toks[0], labs[0], SPEC)))(params)
    new, val = ref.sgd_step(jax.tree.map(jnp.copy, params), toks[0],
                            labs[0], 0.5, SPEC)
    close(val, val0, 1e-6)
    for p, n, g in zip(*map(jax.tree.leaves, (params, new, grads))):
        close(p - n, 0.5 * g, 1e-4, atol=1e-6)  # p - n cancels


@pytest.mark.parametrize("kind", kt.OUTS)
def test_the_probe_reads_a_layers_attention(params, batch, kind):
    toks, _ = batch
    layer = kt.probed_layers(SIZES)[kind]
    assert layer == {"swa": 0, "full": 3, "mtp": 5}[kind]
    close(highest(tfm.attn_probe, params, toks[0], config(), layer),
          ref.attention_out(params, toks[0], layer, SPEC), 1e-4)


def _whole_norm(params):
    """The tree with gains over the WHOLE projections (all ones, as the
    per-head ones are), for a config with OLMoE's QK-norm."""
    def widen(lp):
        return dict(lp, q_norm={"g": jnp.ones(lp["wq"].shape[1])},
                    k_norm={"g": jnp.ones(lp["wk"].shape[1])})
    return dict(params, layers=[widen(lp) for lp in params["layers"]],
                mtp=[widen(mp) for mp in params["mtp"]])


FAULTS = {
    "window_ignored": ("swa", dict(attn_window=4 * T)),
    "full_layer_rotated": ("full", dict(rope_full=None)),
    "windowed_layer_not_rotated": ("swa", dict(
        rope_window=tfm.NO_ROPE, rope_full=None)),
    "norm_over_the_whole_projection": ("swa", dict(qk_norm=True)),
    "no_norm": ("full", dict(qk_norm=False)),
    "module_under_the_window": ("mtp", dict(mtp_attn=tfm.WINDOWED)),
    "module_rotated": ("mtp", dict(rope_full=None)),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_is_caught_by_its_named_limit(params, batch, fault):
    """What `swa_out_gap` / `full_out_gap` / `mtp_out_gap` are there
    for: each new piece broken on purpose moves its mixer's output past
    the cell's rehearsal limit of that name, which the sound program
    holds with room."""
    toks, _ = batch
    kind, broken = FAULTS[fault]
    layer = kt.probed_layers(SIZES)[kind]
    want = ref.attention_out(params, toks[0], layer, SPEC)
    mine = _whole_norm(params) if broken.get("qk_norm") is True else params
    got = highest(tfm.attn_probe, mine, toks[0], config(**broken), layer)
    sound = highest(tfm.attn_probe, params, toks[0], config(), layer)
    limit = LIMITS[kind + "_out_gap"]
    assert kt.rel_err(got, want) > 3 * limit
    assert kt.rel_err(sound, want) < limit / 10


def test_the_per_head_norm_is_each_head_alone(params, batch):
    """A gain of [head_dim] on each head's own root mean square: the
    program's q and k against the lines written out, and against the
    norm over the whole projection, which is another function."""
    toks, _ = batch
    lp = params["layers"][3]  # a full layer: no rotation after the norm
    cfg = config()
    x = jax.random.normal(jax.random.key(3), (1, 8, SIZES["d_model"]))
    h, kv, dh = SIZES["n_heads"], SIZES["n_kv_heads"], SIZES["head_dim"]
    gq = jnp.linspace(0.5, 1.5, dh)
    lp = dict(lp, q_norm={"g": gq}, k_norm={"g": gq[::-1]})
    seen = {}
    attention = att.attention

    def spy(q, k, v, **kw):
        seen.update(q=q, k=k)
        return attention(q, k, v, **kw)

    att.attention, before = spy, pvar.read("attn_head_norm_layers")
    try:
        highest(tfm._attention, lp, x, cfg, AX, None)
    finally:
        att.attention = attention
    assert pvar.read("attn_head_norm_layers") == before + 1
    q = np.asarray(x @ lp["wq"], np.float64).reshape(1, 8, h, dh)
    k = np.asarray(x @ lp["wk"], np.float64).reshape(1, 8, kv, dh)

    def normed(a, g):
        return a / np.sqrt((a * a).mean(-1, keepdims=True) + 1e-5) \
            * np.asarray(g, np.float64)

    close(seen["q"], normed(q, gq), 1e-5)
    close(seen["k"], np.repeat(normed(k, gq[::-1]), h // kv, axis=2), 1e-5)


# -- the share of the experts a chip holds ------------------------------------------

def test_sixteen_shares_of_eight_add_up_to_the_uncut_references_layer():
    """128 experts held by 16 chips, 8 each, top-8: what each chip's
    layer adds for its own experts (the PROGRAM's bounded or full path,
    `held_experts` (8 s, 8)), summed, with the shared expert and the
    router — what every chip computes alike — counted once, is the
    reference's layer with all 128 held."""
    sizes = dict(SIZES, n_experts=128, held_count=128, top_k=8)
    lp = weights_kexaone.device_init(sizes, 3)["layers"][1]
    spec = kt.reference_spec(sizes)
    x = jax.random.normal(jax.random.key(5), (1, 32, sizes["d_model"]))
    want = highest(ref.ffn_part, ref._f32(lp), x, spec)
    flat = x.reshape(32, -1)
    cfg = config(n_experts=128, top_k=8)
    shared = highest(tfm._ffn, flat, lp["ws1"], lp["ws3"], lp["ws2"], cfg)
    routed = 0.0
    for s in range(16):
        mine = dict(lp, **{w: lp[w][8 * s:8 * s + 8]
                           for w in ("w1", "w3", "w2")})
        share = config(n_experts=128, top_k=8, held_experts=(8 * s, 8))
        routed = routed + highest(tfm._experts, mine, x, share, AX,
                                  None).reshape(32, -1) - shared
    close(routed + shared, want.reshape(32, -1), 1e-4)
    assert float(jnp.linalg.norm(routed)) > 0.5 * float(
        jnp.linalg.norm(shared))
    assert moe.held_rows_bound(8192, 8, 8, 128) < 8192 * 8


# -- the step ---------------------------------------------------------------------

def test_scopes_and_counters_of_the_compiled_step(params, batch):
    toks, labs = batch
    cfg = config(remat=True)
    step = jax.jit(tfm.make_train_step(cfg, AX, tfm.param_specs(cfg, AX)))
    s = pvar.session()
    text = step.lower(params, toks[0], labs[0]).compile().as_text()
    assert {n: s.read(n) for n in (
        "attn_window_layers", "attn_full_layers", "attn_gqa_layers",
        "attn_head_norm_layers", "attn_unrotated_layers", "mtp_full_layers",
        "mtp_window_layers", "remat_whole_applications")} == {
        "attn_window_layers": 4, "attn_full_layers": 2, "attn_gqa_layers": 6,
        "attn_head_norm_layers": 6, "attn_unrotated_layers": 2,
        "mtp_full_layers": 1, "mtp_window_layers": 0,
        "remat_whole_applications": 6}
    def core(layer, scope):  # forward, recomputed forward or backward
        return re.search(rf"layer_{layer}\)+/jit\(layer\)/(checkpoint/)?"
                         rf"attn_core/{scope}/", text)

    for layer, scope in ((0, "attn_window"), (3, "attn_full"),
                         (4, "attn_window"), (5, "attn_full")):
        assert core(layer, scope)
    # the module's core under `attn_full` inside its own `layer_5`,
    # where the accepted `mtp_ms` reader finds the whole module
    assert not core(5, "attn_window") and not core(4, "attn_full")
    assert re.search(r"layer_5\)+/attn_proj/mtp_merge/", text)
    assert "head_loss)/mtp/" in text and "attn_proj/qk_rope" in text


def test_the_step_lowered_for_the_tpu_holds_the_narrow_windows_kernels(
        params, monkeypatch):
    """With the rule answering for the TPU at a length its tile
    divides: four applications on the kernels under the window (the
    two-kernel backward), two on the causal ones (the module's is one),
    no att.mha; the tiles counted are `window_tiles`'."""
    rule = att.blockwise_tile
    monkeypatch.setattr(att, "blockwise_tile", lambda backend, *a, **kw: rule(
        "tpu", *a, **kw))
    cfg = config(dtype=jnp.bfloat16, remat=True)
    tok = jax.ShapeDtypeStruct((1, 1024), jnp.int32)
    step = jax.jit(tfm.make_train_step(cfg, AX, tfm.param_specs(cfg, AX)))
    s = pvar.session()
    text = step.trace(params, tok, tok).lower(
        lowering_platforms=("tpu",)).as_text()
    assert s.read("attn_blockwise_layers") == 6 \
        and s.read("attn_reference_layers") == 0
    tile = rule("tpu", 1024, 1024, 16, window=16)
    assert tile == 512
    assert (s.read("attn_window_tiles"), s.read("attn_causal_tiles")) == (
        4 * att.window_tiles(1024, tile, 16), 4 * att.window_tiles(1024, tile))
    assert "splash_mha_dq" in text and "splash_mha_dkv_no_residuals" in text


def test_the_seeded_tree_is_the_programs_tree(params):
    mine = tfm.init_params(np.random.default_rng(0), config())
    assert jax.tree.structure(mine) == jax.tree.structure(params)
    assert [a.shape for a in jax.tree.leaves(mine)] == [
        a.shape for a in jax.tree.leaves(params)]
    assert mine["layers"][1]["q_norm"]["g"].shape == (16,) \
        == mine["mtp"][0]["k_norm"]["g"].shape
    like = tfm.param_specs(config(), AX)
    assert jax.tree.structure(like, is_leaf=lambda x: x is None or isinstance(
        x, jax.sharding.PartitionSpec)) == jax.tree.structure(params)
    assert sum(kt.router_leaves(SIZES)) == 2 * 5


def test_a_bfloat16_run_holds_the_rehearsal_limits():
    """The comparison that decides `correct`, at toy widths: the
    program's first steps in bfloat16 against the float32 reference
    under the cell's `rehearsal_limits`."""
    _, _, traffic, conf, limits = mf.cell_inputs(mf.load(), CELL, True)
    sizes = kt.model_sizes(conf)
    n, lr, seed = traffic["check_steps"], traffic["lr"], 2147483659
    params = weights_kexaone.device_init(sizes, seed)
    toks, labs = weights.batches(sizes["vocab"], traffic["n_batches"],
                                 traffic["batch"], traffic["seq"], seed)
    step = kt.build_step(sizes, lr)
    probe = kt.probes(sizes, params, toks, n, seed)
    _, program = kt.first_steps(step, params, toks, labs, sizes, seed, n)
    checks = kt.checks_against(
        program, kt.reference_steps(sizes, toks, labs, seed, lr, n), limits,
        sizes) + kt.first_batch_checks(
            probe, kt.reference_first_batch(sizes, toks, seed), limits)
    assert {c[0] for c in checks} == set(limits)
    for name, value, limit in checks:
        assert value <= limit, (name, value, limit)
    assert probe["moe_dropped_assignments"] == 0
    assert (probe["window_leak_rows"], probe["window_edge_seen"]) == (0, 3)
