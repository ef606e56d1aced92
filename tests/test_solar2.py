"""The eighth published model of models/transformer.py at toy widths on
the CPU: blocks whose first sub-layer is gated grouped-query attention
without positions or Kimi Delta Attention (ops/kda.py: a per-channel
gated delta rule in chunks, `jax.numpy` around a `lax.scan` here and
two Pallas kernels on the TPU), every layer with sigmoid-routed
experts of which a share is held and a shared expert — the program
against the recurrence written a second time here, token by token,
against hand-written cases and against the float32 reference
(benchmark/reference/solar2_decoder.py); tests/test_kda.py holds the
mixer, its chunked core and the core's kernels on their own."""

import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, "scripts"))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import lowered_text  # noqa: E402
import lower_cmp  # noqa: E402
from benchmark import manifest as mf  # noqa: E402
from benchmark import weights, weights_solar2  # noqa: E402
from benchmark.reference import solar2_decoder as ref  # noqa: E402
from benchmark.runners import solar2_train  # noqa: E402
from ompi_tpu.core import pvar  # noqa: E402
from ompi_tpu.models import transformer as tfm  # noqa: E402
from ompi_tpu.ops import attention as att  # noqa: E402
from ompi_tpu.ops import kda  # noqa: E402

AX = tfm.Axes()
SIZES = dict(
    vocab=64, d_model=32, n_layers=4, gqa_layers=(0,), n_heads=4,
    n_kv_heads=2, head_dim=16, kda_heads=4, kda_head_dim=8, kda_conv=4,
    kda_chunk=16, kda_rank=8, dt_min=0.001, dt_max=0.1, moe_d_ff=24,
    n_experts=16, held_first=0, held_count=16, top_k=4, n_shared_experts=1,
    param_dtype="float32")
B, T = 2, 64


def config(**kw):
    base = dict(
        vocab=64, d_model=32, n_layers=4, n_heads=4, head_width=16,
        n_kv_heads=2, attn_layers="fddd", attn_gate=True, kda_heads=4,
        kda_head_dim=8, kda_chunk=16, pos="none", norm="rmsnorm",
        tie_head=False, mlp_act="silu", mlp_gated=True, moe_every=1,
        moe_d_ff=24, n_experts=16, top_k=4, norm_topk_prob=True,
        router_score="sigmoid", router_bias=True, n_shared_experts=1,
        max_seq=8, dtype=jnp.float32)
    base.update(kw)
    return tfm.Config(**base)


SPEC = ref.Spec(gqa_layers=(0,), n_heads=4, n_kv_heads=2, kda_heads=4,
                top_k=4, block=8, head_block=2, q_rows=16)


@pytest.fixture(scope="module")
def params():
    return weights_solar2.device_init(SIZES, 7)


@pytest.fixture(scope="module")
def batch():
    return weights.batches(64, 3, B, T, 7)


def highest(fn, *args):
    with jax.default_matmul_precision("highest"):
        return fn(*args)


def close(a, b, tol=2e-5, atol=0.0):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.abs(a - b).max() <= atol + tol * max(np.abs(b).max(), 1e-30)


# -- gated attention -----------------------------------------------------------

def test_gated_attention_is_mha_over_repeated_heads_times_the_gate(params):
    lp = params["layers"][0]
    x = jax.random.normal(jax.random.key(5), (B, T, 32))
    cfg = config()
    s = pvar.session()
    got = highest(tfm._attention, lp, x, cfg, AX, None)
    assert s.read("attn_gated_layers") == 1 and s.read("attn_gqa_layers") == 1
    q = (x @ lp["wq"]).reshape(B, T, 4, 16)
    k, v = ((x @ lp[n]).reshape(B, T, 2, 16) for n in ("wk", "wv"))
    o = highest(att.mha, q, jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2), True)
    want = (o.reshape(B, T, 64) * jax.nn.sigmoid(x @ lp["wa"])) @ lp["wo"]
    close(got, highest(lambda: want), 1e-4)
    # the gate is there: without it the output is another
    plain = highest(tfm._attention, {n: lp[n] for n in lp if n != "wa"}, x,
                    config(attn_gate=False), AX, None)
    assert float(jnp.abs(plain - got).max()) > 0.1 * float(
        jnp.abs(got).max())
    close(got, ref.attention(jax.tree.map(lambda a: a.astype(jnp.float32),
                                          ref.parts(lp, True)[0]), x, SPEC),
          1e-4)


# -- the model against the reference -------------------------------------------

def _mean_loss(cfg, toks, labs):
    def loss(p):
        nll, count = tfm.loss_local(p, toks, labs, cfg, AX)
        return nll / count
    return loss


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
            close(g, r, 2e-4, atol=1e-9)


def test_three_steps_are_the_references(params, batch):
    """`make_train_step` against the reference's sub-layer-at-a-time
    SGD step, three steps from the seed's state: every loss and every
    leaf's movement (at a rate that re-routes no token of the toy:
    top-4 of 16 is discrete, and at lr 0.5 the third loss is another
    model's)."""
    toks, labs = batch
    cfg = config(remat=True)
    step = jax.jit(tfm.make_train_step(cfg, AX, tfm.param_specs(cfg, AX),
                                       lr=0.1))
    mine, theirs = params, jax.tree.map(jnp.copy, params)
    for i in range(3):
        mine, loss = highest(step, mine, toks[i], labs[i])
        theirs, r_loss = ref.sgd_step(theirs, toks[i], labs[i], 0.1, SPEC)
        close(loss, r_loss, 2e-6)
    for a, b, p in zip(*map(jax.tree.leaves, (mine, theirs, params))):
        close(a - p, b - p, 5e-3, atol=1e-7)


def test_the_probes_read_the_mixers_on_the_embedded_batch(params, batch):
    toks, _ = batch
    cfg = config()
    s = pvar.session()
    out, last = highest(tfm.kda_probe, params, toks[0], cfg, 1)
    assert s.read("kda_state_norm_micro") == int(round(
        1e6 * float(jnp.linalg.norm(last))))
    r_out, r_last = ref.mixer_out(params, toks[0], 1, SPEC)
    close(out, r_out, 1e-4)
    close(last, r_last, 1e-4)
    close(highest(tfm.attn_probe, params, toks[0], cfg, 0),
          ref.mixer_out(params, toks[0], 0, SPEC), 1e-4)
    with pytest.raises(ValueError, match="no delta-rule mixer"):
        tfm.kda_probe(params, toks[0], cfg, 0)
    with pytest.raises(ValueError, match="is a delta-rule layer"):
        tfm.attn_probe(params, toks[0], cfg, 1)
    chosen = np.asarray(ref.chosen_experts(params, toks[0], SPEC))
    mine = np.asarray(highest(tfm.route_experts, params, toks[0], cfg)[0])
    assert solar2_train.route_disagreement(mine, chosen) == 0.0


def test_the_seeded_tree_is_the_programs_tree(params):
    mine = tfm.init_params(np.random.default_rng(0), config())
    assert jax.tree.structure(mine) == jax.tree.structure(params)
    assert [a.shape for a in jax.tree.leaves(mine)] == [
        a.shape for a in jax.tree.leaves(params)]
    like = tfm.param_specs(config(), AX)
    assert jax.tree.structure(like, is_leaf=lambda x: x is None or isinstance(
        x, jax.sharding.PartitionSpec)) == jax.tree.structure(params)
    # the family's initialisation of the decay's small leaves
    for tree in (mine, params):
        lp = tree["layers"][1]
        a = np.exp(np.asarray(lp["A_log"]))
        assert (a >= 1).all() and (a <= 16).all()
        dt = np.log1p(np.exp(np.asarray(lp["dt_bias"])))
        assert (dt > 0.9e-3).all() and (dt < 0.11).all()
        assert lp["dt_bias"].shape == (32,) and lp["A_log"].shape == (4,)


def _published():
    with open(os.path.join(HERE, "benchmark", "configs",
                           "solar-open2-250b.json")) as f:
        return json.load(f)


def test_the_parameter_count_to_the_parameter():
    """ISSUE 46's table: 3,308,353,344, of the benchmark's plan and of
    the program's own description alike."""
    file = _published()
    sizes = solar2_train.model_sizes(file)
    plan = weights_solar2.plan(sizes)
    count = sum(int(np.prod(shape)) for shape, _ in jax.tree.leaves(
        plan, is_leaf=lambda t: isinstance(t, tuple)))
    assert count == file["parameters"]["total"] == 3_308_353_344

    def layer(i):
        return sum(int(np.prod(shape)) for shape, _ in jax.tree.leaves(
            plan["layers"][i], is_leaf=lambda t: isinstance(t, tuple)))

    assert layer(0) == 755_245_376 and layer(1) == layer(2) == layer(3) \
        == 783_925_760
    from ompi_tpu.models import params as pm

    cfg = solar2_train.program_config(sizes)
    assert cfg.attn_layers == "fddd"
    mixers = {kind: sum(int(np.prod(leaf.shape))
                        for leaf in pm.MIXERS[kind](cfg))
              for kind in ("attention", "kda", "experts")}
    assert mixers == {"attention": 109_051_904, "kda": 137_732_288,
                      "experts": 646_185_280}
    described = sum(int(np.prod(leaf.shape)) for leaf in pm._top_leaves(cfg)) \
        + sum(int(np.prod(leaf.shape)) for i in range(4)
              for leaf in pm._layer_leaves(cfg, pm._layer_kind(cfg, i)))
    assert described == count


def test_the_file_differs_from_the_catalog_in_the_reduced_keys_alone():
    file = _published()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Solar-Open2-250B")
    assert file["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if file.get(k) != v}
    assert differs == set(file["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert {k: row["config"][k] for k in differs} == file["published"]


# -- the share test ------------------------------------------------------------

@pytest.mark.parametrize("share", [2, 4, 16])
def test_the_shares_add_up_to_the_uncut_layer(params, share):
    """The guide's share test: the expert part's outputs under
    `held_experts` (0, n), (n, n), ... — the shared expert counted once
    — add up to the reference's layer with every expert (16 / n chips
    of n experts, as the cell's 8 shares of 40)."""
    lp = ref.parts(params["layers"][1], False)[1]
    h = jax.random.normal(jax.random.key(9), (B, T, 32))
    x = ref.rms_norm(h, lp["ln2"]["g"], 1e-5)
    whole = ref.experts(lp, x, SPEC)
    shared = ref.ffn(x.reshape(B * T, 32), lp["ws1"], lp["ws3"],
                     lp["ws2"]).reshape(B, T, 32)
    sub = tfm.layout(config(), tfm.Block(True, False, True))[1]
    total = 0
    for first in range(0, 16, share):
        mine = dict(lp, **{n: lp[n][first:first + share]
                           for n in ("w1", "w3", "w2")})
        cfg = config(held_experts=(first, share))
        total = total + highest(tfm._sublayer, mine, h, cfg, AX, sub) \
            - h - shared
    close(total + shared, whole, 1e-4)
    # and the reference cut the same way
    cut = dict(lp, **{n: lp[n][4:8] for n in ("w1", "w3", "w2")})
    close(highest(tfm._sublayer, cut, h, config(held_experts=(4, 4)), AX,
                  sub) - h,
          ref.experts(cut, x, SPEC._replace(held_first=4)), 1e-4)


def test_a_sequence_no_chunk_divides_raises(params):
    cfg = config()
    with pytest.raises(NotImplementedError, match="no whole number"):
        tfm.layer_forward(params["layers"][1], jnp.zeros((1, 24, 32)), cfg,
                          AX, tfm._layer_kind(cfg, 1))


# -- what is not written raises ------------------------------------------------

@pytest.mark.parametrize("axis, says", [
    ("tp", "tensor parallelism"), ("sp", "sequence parallelism"),
    ("ep", "expert parallelism"), ("pp", "pipeline parallelism")])
def test_the_mixer_under_an_axis_raises(params, axis, says):
    cfg = config()
    with pytest.raises(NotImplementedError, match=says):
        tfm.layer_forward(params["layers"][1], jnp.zeros((1, 16, 32)), cfg,
                          tfm.Axes(**{axis: "x"}), tfm._layer_kind(cfg, 1))


@pytest.mark.parametrize("kw, error, says", [
    (dict(attn="mla"), NotImplementedError, "latent attention"),
    (dict(layer_pattern="MEEM"), NotImplementedError, "a layer pattern"),
    (dict(mtp_layers=1), ValueError, "multi-token-prediction module's"),
    (dict(mtp_layers=1, mtp_attn="d"), ValueError,
     "a delta-rule module is not written"),
    (dict(kda_heads=0), ValueError, "has delta-rule layers"),
    (dict(attn_layers="fdd"), ValueError, "expected n_layers = 4"),
    (dict(attn_layers="fdxd"), ValueError, "letters of 'w'")])
def test_a_config_the_layers_cannot_compute_raises(kw, error, says):
    cfg = config(**kw)
    with pytest.raises(error, match=says):
        tfm._check_supported(cfg, AX, tfm.Block(True, False, True), 0)


def test_the_layers_kinds_and_layouts():
    cfg = config()
    kinds = [tfm._layer_kind(cfg, i) for i in range(4)]
    assert kinds == [tfm.Block(True, False, False)] \
        + [tfm.Block(True, False, True)] * 3
    assert [tfm.layout(cfg, k)[0].mixer for k in kinds] == [
        "attention", "kda", "kda", "kda"]
    assert tfm.layout(cfg, kinds[1])[0].scopes == ("kda", "kda_proj")
    assert all(tfm.layout(cfg, k)[1].mixer == "experts" for k in kinds)


# -- the step ------------------------------------------------------------------

COUNTED = ("kda_layers", "kda_chunks", "kda_carry_kernel_layers",
           "kda_carry_scan_layers", "kda_core_kernel_layers",
           "attn_gated_layers", "attn_gqa_layers",
           "attn_full_layers", "remat_whole_applications")


def _compiled_step(params, batch):
    toks, labs = batch
    cfg = config(remat=True)
    step = jax.jit(tfm.make_train_step(cfg, AX, tfm.param_specs(cfg, AX)))
    s = pvar.session()
    text = step.lower(params, toks[0], labs[0]).compile().as_text()
    return text, {n: s.read(n) for n in COUNTED}


def _loops(text: str) -> int:
    return sum(1 for line in text.splitlines() if " while(" in line)


def test_the_steps_loops_are_the_carrys_scans(params, batch):
    """On the CPU the rule takes the `lax.scan`: a loop forward, one in
    each of the two recomputed forwards and one backward for each run
    of heads of each delta-rule layer (toy: one run), and no other
    loop in the step."""
    text, counted = _compiled_step(params, batch)
    assert counted == {
        "kda_layers": 3, "kda_chunks": 3 * (T // 16),
        "kda_carry_kernel_layers": 0, "kda_carry_scan_layers": 3,
        "kda_core_kernel_layers": 0, "attn_gated_layers": 1, "attn_gqa_layers": 1, "attn_full_layers": 1,
        "remat_whole_applications": 4}
    assert 0 < _loops(text) <= 3 * 4
    for scope in ("kda_proj", "kda_conv", "kda_core", "kda_gate_norm",
                  "attn_gate"):
        assert scope in text
    # every loop is a delta-rule layer's core's
    for line in text.splitlines():
        if " while(" in line:
            assert "kda/" in line and "kda_core" in line, line


def test_no_loop_in_the_step_lowered_for_the_tpu(params, batch, monkeypatch):
    """With the rule answering for the TPU the core — chunk-local work,
    carry and read-out — is the two kernels and the step has no loop
    (interpret mode emulates a kernel's grid with a loop, so this is
    read from the text lowered for the TPU; the toy's heads are 8 wide,
    so a grid step takes all four: a block as wide as its array)."""
    toks, labs = batch
    monkeypatch.setattr(kda, "carry_tile",
                        lambda backend, t, heads, *a: heads)
    cfg = config(remat=True, dtype=jnp.bfloat16)
    step = jax.jit(tfm.make_train_step(cfg, AX, tfm.param_specs(cfg, AX)))
    s = pvar.session()
    text = step.trace(params, toks[0], labs[0]).lower(
        lowering_platforms=("tpu",)).as_text()
    assert (s.read("kda_carry_kernel_layers"),
            s.read("kda_core_kernel_layers"),
            s.read("kda_carry_scan_layers")) == (3, 3, 0)
    assert "stablehlo.while" not in text
    # a delta-rule layer is one function of the module: the core runs
    # forward in the step and in the layer's recomputation (which keeps
    # the entering states), NOT a third time in the run of heads' own,
    # and backward once
    assert [len(re.findall(f'kernel_name = "{name}"', text))
            for name in ("kda_delta_fwd", "kda_delta_bwd")] == [2, 1]


def test_the_step_on_the_kernels_is_the_step_on_the_scan(params, batch,
                                                         kernels_on_cpu):
    toks, labs = batch
    loss, grads = highest(jax.jit(jax.value_and_grad(
        _mean_loss(config(remat=True), toks[0], labs[0]))), params)
    r_loss, r_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, toks[0], labs[0], SPEC)))(params)
    close(loss, r_loss, 1e-6)
    for g, r in zip(jax.tree.leaves(grads), jax.tree.leaves(r_grads)):
        close(g, r, 2e-4, atol=1e-9)


@pytest.mark.parametrize("core", ["numpy", "kernels"])
def test_the_rule_prices_the_mixers_names(core, monkeypatch):
    """What the gated output spares follows the core's form: on the
    kernels a run of heads keeps the core's results from the layer's
    recomputation, which runs the core whatever is kept."""
    if core == "kernels":
        rule = kda.carry_tile
        monkeypatch.setattr(kda, "carry_tile",
                            lambda backend, *a: rule("tpu", *a))
    sizes = solar2_train.model_sizes(_published())
    cfg = solar2_train.program_config(sizes)
    apps, fixed = tfm.step_costs(cfg, 1, 8192, 6_616_706_688)
    assert len(apps) == 4 and apps[1] == apps[2] == apps[3] != apps[0]
    n, wide = 8192, 64 * 128
    assert apps[1].sizes[kda.KDA_PROJ] == 3 * n * wide * 2
    assert apps[1].sizes[kda.KDA_OUT] == n * wide * 2
    assert apps[1].spared[kda.KDA_PROJ] == 2 * n * 4096 * 3 * wide
    assert apps[1].spared[kda.KDA_OUT] == (
        0 if core == "kernels" else n * 64 * kda.core_flops_per_token(
            128, 64))
    assert att.QKV in apps[0].sizes and kda.KDA_PROJ not in apps[0].sizes


# -- the accepted configurations -----------------------------------------------

#: sha256 of `lowered_text.canonical` of the CPU-lowered rehearsal steps
#: at the parent commit 9fb9690 (jax 0.9.0, bfloat16, the rehearsal's
#: batch): `lower_cmp.step_and_shapes(cell, ..., rehearsal=True)` of
#: every accepted train cell — olmoe's and nemotron's are the values
#: tests/test_mellum2.py records
PARENT = {
    "opt30b-train-t1024":
        "457c04d2a40bf0e0461220af87237e60eaaf95e2b832f92f25263a7d341d6064",
    "opt30b-train-t2048":
        "486238262d4d8ae8f52dad7d33024c78cc0db3f60fa848405cc2feaf24d5d983",
    "olmoe-train-t4096":
        "d64dc9ac8d2d4b8da2911ff1e10ee94c834a4d3e4290aa34db0a3ebb5d999b08",
    "glm5-train-t4096":
        "3fd6789d75120ad9ddc6d636112d144ed217465912693a85073ce6ded9532a4b",
    "ouro-train-t4096":
        "0ccc93b329226a216d3171de7f7ca56a177f8c09a76067abdb8f7f3ae0cdbc10",
    "kimivl-train-t4096":
        "f35731168ce502c7ced12054e954a47b8c7ccfdcc06016a735260a6a8fdcf294",
    "nemotron-train-t8192":
        "1224dbf654afbbb6e92872008c9fd886dbf0fc73203b79bd6482c755acc5ee74",
    "mellum2-train-t16384":
        "df7f73ea5fb1cf8ae2cf5895c1f7bd4aad8e527d00e960be1e083dfe6f6fb53d",
}


@pytest.mark.parametrize("cell", sorted(PARENT))
def test_a_mixer_that_is_absent_changes_no_accepted_step(cell):
    """With the new fields at their defaults every accepted step lowers
    to the parent's text (`ops/ssm.causal_conv`'s optional bias among
    what that holds: nemotron-train-t8192's)."""
    if jax.__version__ != "0.9.0":
        pytest.skip("the recorded text is jax 0.9.0's")
    step, *shapes = lower_cmp.step_and_shapes(cell, mf.load(), mf,
                                              rehearsal=True)
    assert lowered_text.sha256(lowered_text.canonical(
        step.lower(*shapes).as_text())) == PARENT[cell]
