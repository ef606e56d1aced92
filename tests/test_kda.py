"""Kimi Delta Attention (ops/kda.py) on the CPU at toy sizes: the mixer
against the recurrence written a second time here, token by token —
values, the state after the last token and every leaf's gradient, over
chunks of 16 and of 64, with decays near 0 and near 1 and beta near 2 —,
the pair sums and the intra-chunk inverse against their definitions,
the mixer with its core on the Pallas kernels (interpret mode), runs of
heads against the whole mixer, the rule that picks the core's form, and
the kernels compiled for a described v5e (tests/test_kda_kernels.py
holds the kernels against their oracle, tests/test_solar2.py the model
around it)."""

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


def _mixer_against_tokens(params, chunk, regime):
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


@pytest.mark.parametrize("regime", sorted(REGIMES))
@pytest.mark.parametrize("chunk", [16, 64])
def test_the_chunked_mixer_is_the_token_by_token_recurrence(params, chunk,
                                                            regime):
    """Values, the state after the last token and the gradient of EVERY
    leaf of the mixer and of its input, over several chunks (of 16: 8;
    of 64: 2, four sub-blocks each)."""
    _mixer_against_tokens(params, chunk, regime)


@pytest.mark.parametrize("chunk, regime", [(16, "beta_near_2"),
                                           (64, "as_seeded")])
def test_the_mixer_on_the_kernels_is_the_recurrence(params, kernels_on_cpu,
                                                    chunk, regime):
    """The same with the core on its kernels (interpret mode): q and k
    reach them as the convolutions left them and are normed inside,
    forward and backward, and a run of heads keeps the kernel's output
    and entering states for its own backward pass."""
    _mixer_against_tokens(params, chunk, regime)


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


#: the core's operands pushed to their ends: log-decays of ~ -1e-4 and
#: of ~ -6 a token (a chunk's cumulative sum reaches hundreds), beta
#: near 2 (the factor I - beta k k^T turns k's direction over)
CORE_REGIMES = {
    "as_drawn": dict(low=-7.0, high=1.0),
    "slow_decay": dict(low=-9.5, high=-9.0),
    "fast_decay": dict(low=1.7, high=1.8),
    "beta_near_2": dict(low=-7.0, high=1.0, beta_shift=4.0),
}


def _core_operands(seed, b, t, h, width, dtype=jnp.float32,
                   low=-7.0, high=1.0, beta_shift=0.0):
    ks = jax.random.split(jax.random.key(seed), 5)
    q = kda.l2norm(jax.random.normal(ks[0], (b, t, h, width)), 1e-6) \
        * width ** -0.5
    k = kda.l2norm(jax.random.normal(ks[1], (b, t, h, width)), 1e-6)
    v = jax.random.normal(ks[2], (b, t, h, width))
    g = -jnp.exp(jax.random.uniform(ks[3], (b, t, h, width), minval=low,
                                    maxval=high))
    beta = 2 * jax.nn.sigmoid(3 * jax.random.normal(ks[4], (b, t, h))
                              + beta_shift)
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta)


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


def test_the_rule_sends_the_cells_core_to_the_kernels():
    assert kda.carry_tile("tpu", 8192, 16, 128, 64, jnp.bfloat16) == 4
    assert kda.carry_tile("tpu", 8192, 6, 128, 64, jnp.bfloat16) == 2
    assert kda.carry_tile("tpu", 64, 1, 128, 16, jnp.bfloat16) == 1
    # off the TPU; a sequence the chunk does not divide; a head the
    # lanes do not divide; a chunk under the type's sublane tile; three
    # sub-blocks, which do not pair up
    for backend, t, head_dim, chunk in (
            ("cpu", 8192, 128, 64), ("tpu", 8200, 128, 64),
            ("tpu", 8192, 96, 64), ("tpu", 8192, 128, 8),
            ("tpu", 8160, 128, 48)):
        assert kda.carry_tile(backend, t, 64, head_dim, chunk,
                              jnp.bfloat16) is None


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_the_kernels_compile_for_the_chip(one_chip, dtype):
    """What interpret mode cannot show: the chip's compiler takes the
    forward kernel (with and without the entering states) and the
    backward kernel at the cell's block shape — a run of 16 heads of
    128, chunks of 64, the rule's heads a grid step — and nothing of
    the core is left for a loop."""
    b, t, h, c, width = 1, 512, 16, 64, 128
    per = kda.carry_tile("tpu", t, h, width, c, dtype)

    def loss(q, k, v, g, beta, a, f):
        o, last = kda.kernel_delta(q, k, v, g, beta, h, c, per)
        return (o.astype(jnp.float32) * a).sum() + (last * f).sum()

    def arg(shape, dtype=dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    wide = (b, t, h * width)
    shapes = (arg(wide), arg(wide), arg(wide), arg(wide, jnp.float32),
              arg((b, t, h), jnp.float32), arg(wide, jnp.float32),
              arg((b, h, width, width), jnp.float32))
    forward = jax.jit(loss).lower(*shapes).compile().as_text()
    assert "kda_delta_fwd" in forward and "kda_delta_bwd" not in forward
    text = jax.jit(jax.value_and_grad(loss, (0, 1, 2, 3, 4))).lower(
        *shapes).compile().as_text()
    assert "kda_delta_fwd" in text and "kda_delta_bwd" in text
    assert "while" not in text
