"""Kimi Delta Attention (ops/kda.py) on the CPU at toy sizes: the mixer
against the recurrence written a second time here, token by token —
values, the state after the last token and every leaf's gradient, over
chunks of 16 and of 64, with decays near 0 and near 1 and beta near 2 —,
the pair sums and the intra-chunk inverse against their definitions,
the carry's Pallas kernels in interpret mode against their `lax.scan`
oracle, runs of heads against the whole mixer, the rule that picks the
carry's form, and the kernels compiled for a described v5e
(tests/test_solar2.py holds the model around it)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from benchmark import weights_solar2  # noqa: E402
from benchmark.reference import solar2_decoder as ref  # noqa: E402
from ompi_tpu.ops import kda  # noqa: E402

SIZES = dict(
    vocab=64, d_model=32, n_layers=2, gqa_layers=(0,), n_heads=4,
    n_kv_heads=2, head_dim=16, kda_heads=4, kda_head_dim=8, kda_conv=4,
    kda_chunk=16, kda_rank=8, dt_min=0.001, dt_max=0.1, moe_d_ff=24,
    n_experts=16, held_first=0, held_count=16, top_k=4, n_shared_experts=1,
    param_dtype="float32")
B = 2


@pytest.fixture(scope="module")
def params():
    return weights_solar2.device_init(SIZES, 7)


def highest(fn, *args):
    with jax.default_matmul_precision("highest"):
        return fn(*args)


def close(a, b, tol=2e-5, atol=0.0):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.abs(a - b).max() <= atol + tol * max(np.abs(b).max(), 1e-30)


# -- the delta rule ------------------------------------------------------------

def recurrence(q, k, v, g, beta):
    """The module docstring's three lines, one sequence, token by
    token: q, k, g [T, H, K], v [T, H, V], beta [T, H] -> (o [T, H, V],
    the last state [H, K, V])."""
    def token(s, now):
        q_t, k_t, v_t, g_t, b_t = now
        s = jnp.exp(g_t)[:, :, None] * s
        s = s + b_t[:, None, None] * k_t[:, :, None] * (
            v_t - jnp.einsum("hk,hkv->hv", k_t, s))[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    last, o = jax.lax.scan(
        token, jnp.zeros((k.shape[1], k.shape[2], v.shape[2])),
        (q, k, v, g, beta))
    return o, last


def mixer_by_tokens(lp, x, heads=4, eps=1e-5):
    """The whole mixer a second time, in the plainest jax.numpy: the
    issue's equations, one sequence at a time."""
    def one(x):
        t = x.shape[0]

        def conv(a, w):
            padded = jnp.concatenate([jnp.zeros((3, a.shape[1])), a])
            return jax.nn.silu(sum(padded[j:j + t] * w[:, j]
                                   for j in range(4)))

        def heads_of(a):
            return a.reshape(t, heads, -1)

        def l2(a):
            return a / jnp.sqrt((a * a).sum(-1, keepdims=True) + 1e-6)

        q = l2(heads_of(conv(x @ lp["wq"], lp["conv_q"])))
        k = l2(heads_of(conv(x @ lp["wk"], lp["conv_k"])))
        v = heads_of(conv(x @ lp["wv"], lp["conv_v"]))
        g = -jnp.exp(lp["A_log"])[:, None] * heads_of(jax.nn.softplus(
            (x @ lp["w_fa"]) @ lp["w_fb"] + lp["dt_bias"]))
        beta = 2 * jax.nn.sigmoid(x @ lp["w_b"])
        o, last = recurrence(q * q.shape[-1] ** -0.5, k, v, g, beta)
        o = o / jnp.sqrt((o * o).mean(-1, keepdims=True) + eps) \
            * lp["o_norm"]["g"]
        y = o.reshape(t, -1) * jax.nn.sigmoid((x @ lp["w_ga"]) @ lp["w_gb"])
        return y @ lp["wo"], last

    return jax.vmap(one)(x)


def _value(fn):
    def value(lp, x):
        out, last = fn(lp, x)
        return (out * out).sum() + (last * last).sum(), (out, last)
    return value


def _kda_leaves(params, layer=1):
    return {n: params["layers"][layer][n] for n in ref.KDA if n != "ln1"}


#: the decays' and beta's pre-activations pushed to their ends: decays
#: near 1 (g ~ -1e-4 a step) and near 0 (g ~ -6 a step: a token keeps
#: a four-hundredth of the state before it), beta near 2
REGIMES = {
    "as_seeded": {},
    "slow_decay": dict(dt_bias=-9.0, A_log=0.0),
    "fast_decay": dict(dt_bias=1.0, A_log=1.5),
    "beta_near_2": dict(w_b_shift=6.0),
}


@pytest.mark.parametrize("regime", sorted(REGIMES))
@pytest.mark.parametrize("chunk", [16, 64])
def test_the_chunked_mixer_is_the_token_by_token_recurrence(params, chunk,
                                                            regime):
    """Values, the state after the last token and the gradient of EVERY
    leaf of the mixer and of its input, over several chunks (of 16: 8;
    of 64: 2, four sub-blocks each)."""
    lp = dict(_kda_leaves(params))
    x = jax.random.normal(jax.random.key(3), (B, 128, 32))
    how = REGIMES[regime]
    for name in ("dt_bias", "A_log"):
        if name in how:
            lp[name] = jnp.full_like(lp[name], how[name])
    if "w_b_shift" in how:  # beta = 2 sigmoid(x W_b): push every x W_b up
        x = x.at[..., 0].set(4.0)
        lp["w_b"] = lp["w_b"].at[0].set(how["w_b_shift"] / 4.0)

    def program(lp, x):
        return kda.mixer(lp, x, heads=4, head_dim=8, chunk=chunk, eps=1e-5)

    (_, (out, last)), grads = highest(jax.value_and_grad(
        _value(program), (0, 1), has_aux=True), lp, x)
    (_, (r_out, r_last)), r_grads = highest(jax.value_and_grad(
        _value(mixer_by_tokens), (0, 1), has_aux=True), lp, x)
    assert last.shape == (B, 4, 8, 8)
    close(out, r_out, 1e-4)
    close(last, r_last, 1e-4)
    named = jax.tree_util.tree_leaves_with_path(grads)
    assert {jax.tree_util.keystr(p) for p, _ in named} >= {
        f"[0]['{n}']" for n in ref.KDA if n not in ("ln1", "o_norm")} | {
        "[0]['o_norm']['g']", "[1]"}
    for (path, g), r in zip(named, jax.tree.leaves(r_grads)):
        assert float(jnp.abs(r).max()) > 0, path
        # a fast decay's cumulative sums reach hundreds inside a chunk,
        # and a difference of two of them keeps float32's ABSOLUTE error
        close(g, r, 3e-3 if regime == "fast_decay" else 5e-4, atol=1e-7)


def test_runs_of_heads_are_the_whole_mixer(params, monkeypatch):
    """The mixer works `HEADS_A_RUN` heads at a time, one run after the
    other behind an optimization barrier, each recomputed in its own
    backward pass: two runs of two heads are one run of four, values
    and every gradient."""
    lp = dict(_kda_leaves(params))
    x = jax.random.normal(jax.random.key(6), (B, 32, 32))

    def program(lp, x):
        return kda.mixer(lp, x, heads=4, head_dim=8, chunk=16, eps=1e-5)

    whole = highest(jax.value_and_grad(_value(program), (0, 1),
                                       has_aux=True), lp, x)
    monkeypatch.setattr(kda, "HEADS_A_RUN", 2)
    runs = highest(jax.value_and_grad(_value(program), (0, 1), has_aux=True),
                   lp, x)
    text = jax.jit(jax.grad(lambda lp, x: _value(program)(lp, x)[0])).lower(
        lp, x).as_text()
    assert "optimization_barrier" in text
    for a, b in zip(jax.tree.leaves(runs), jax.tree.leaves(whole)):
        close(a, b, 1e-5, atol=1e-8)


def _core_operands(seed, b, t, h, width, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 5)
    q = kda.l2norm(jax.random.normal(ks[0], (b, t, h, width)), 1e-6) \
        * width ** -0.5
    k = kda.l2norm(jax.random.normal(ks[1], (b, t, h, width)), 1e-6)
    v = jax.random.normal(ks[2], (b, t, h, width))
    g = -jnp.exp(jax.random.uniform(ks[3], (b, t, h, width), minval=-7.0,
                                    maxval=1.0))
    beta = 2 * jax.nn.sigmoid(3 * jax.random.normal(ks[4], (b, t, h)))
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta)


def _weighed(fn):
    def loss(*args):
        o, last = fn(*args)
        return (o.astype(jnp.float32) * jnp.cos(jnp.arange(o.size).reshape(
            o.shape))).sum() + (last * last).sum()
    return loss


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("per", [1, 2])
def test_the_carrys_kernels_are_the_scan(kernels_on_cpu, per, dtype, tol):
    """The kernel form (interpret mode) against its `lax.scan` oracle:
    the outputs, the last state and the gradient of all five operands,
    one head a grid step and two; both forms round the same operands of
    the same products."""
    args = _core_operands(1, 2, 64, 4, 8, dtype)

    def form(per):
        return lambda *a: kda.chunked_delta(*a, 16, per)

    o, last = highest(form(per), *args)
    r_o, r_last = highest(form(None), *args)
    close(o, r_o, tol)
    close(last, r_last, tol)
    grads = highest(jax.grad(_weighed(form(per)), (0, 1, 2, 3, 4)), *args)
    r_grads = highest(jax.grad(_weighed(form(None)), (0, 1, 2, 3, 4)), *args)
    for g, r in zip(grads, r_grads):
        assert float(jnp.abs(r.astype(jnp.float32)).max()) > 0
        close(g, r, tol)


def test_a_carry_dropped_shows(monkeypatch):
    """What the comparisons above would miss if they could miss
    anything: the state not carried from chunk to chunk moves the
    output far over their tolerance."""
    args = _core_operands(2, 1, 64, 2, 8)
    o, _ = highest(lambda *a: kda.chunked_delta(*a, 16), *args)

    carry = kda.scan_carry

    def forgetful(w, u, kd, grown):
        return carry(w, u, kd, jnp.zeros_like(grown))

    monkeypatch.setattr(kda, "scan_carry", forgetful)
    lost, _ = highest(lambda *a: kda.chunked_delta(*a, 16), *args)
    assert float(jnp.abs(lost - o).max()) > 0.05 * float(jnp.abs(o).max())


def test_the_pair_sums_and_the_inverse(params):
    """`decayed_pairs` against the elementwise definition and
    `unit_lower_inverse` against numpy's, at four sub-blocks of 16."""
    q, k, _, g, _ = _core_operands(4, 1, 64, 2, 8)
    q, k, cum = (jnp.moveaxis(a, 2, 1) for a in (q, k, jnp.cumsum(g, 1)))
    pairs, kk = highest(kda.decayed_pairs, q, k, cum)
    low = np.tril(np.ones((64, 64), bool))
    e = np.exp(np.where(low[..., None], np.asarray(cum)[..., :, None, :]
                        - np.asarray(cum)[..., None, :, :], -np.inf))
    close(pairs, (np.asarray(q)[..., :, None, :]
                  * np.asarray(k)[..., None, :, :] * e).sum(-1), 1e-5)
    close(kk, (np.asarray(k)[..., :, None, :]
               * np.asarray(k)[..., None, :, :] * e).sum(-1), 1e-5)
    a = jnp.where(np.tril(low, -1), 1.7 * kk, 0.0)
    close(highest(kda.unit_lower_inverse, a),
          np.linalg.inv(np.eye(64) + np.asarray(a, np.float64)), 1e-5)


def test_a_sequence_no_chunk_divides_raises():
    with pytest.raises(ValueError, match="no whole number of chunks"):
        kda.chunked_delta(*_core_operands(0, 1, 24, 2, 8), 16)


def test_the_rule_sends_the_cells_carry_to_the_kernels():
    assert kda.carry_tile("tpu", 8192, 64, 128, 64, jnp.bfloat16) == 8
    assert kda.carry_tile("tpu", 8192, 6, 128, 64, jnp.bfloat16) == 2
    for backend, t, head_dim, chunk in (
            ("cpu", 8192, 128, 64), ("tpu", 8200, 128, 64),
            ("tpu", 8192, 96, 64), ("tpu", 8192, 128, 8)):
        assert kda.carry_tile(backend, t, 64, head_dim, chunk,
                              jnp.bfloat16) is None


def test_the_kernels_compile_for_the_chip(one_chip):
    """What interpret mode cannot show: the chip's compiler takes both
    kernels at the cell's widths (64 heads of 128, chunks of 64), eight
    heads a grid step."""
    b, h, nc, c, width = 1, 64, 8, 64, 128
    per = kda.carry_tile("tpu", nc * c, h, width, c, jnp.bfloat16)

    def loss(w, u, kd, grown, a, e, f):
        vp, entering, last = kda.kernel_carry(w, u, kd, grown, per)
        return (vp.astype(jnp.float32) * a).sum() + (
            entering.astype(jnp.float32) * e).sum() + (last * f).sum()

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    wide = (b, h, nc, c, width)
    text = jax.jit(jax.value_and_grad(loss, (0, 1, 2, 3))).lower(
        arg(wide), arg(wide), arg(wide), arg((b, h, nc, width), jnp.float32),
        arg(wide, jnp.float32), arg((b, h, nc, width, width), jnp.float32),
        arg((b, h, width, width), jnp.float32)).compile().as_text()
    assert "kda_carry_fwd" in text and "kda_carry_bwd" in text
    assert "while" not in text
