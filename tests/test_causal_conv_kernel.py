"""The causal convolution's kernels (ops/causal_conv.py behind
`ops/ssm.causal_conv` and the rule `conv_tile`) against the `jax.numpy`
form `ssm.shifted_conv`, here on the CPU with the kernels in interpret
mode at toy sizes, in both layouts: time in the sublanes (`[B, T, C]`
in and out: ops/kda._heads) and time in the lanes (`[B, C, T]`:
ops/ssm.mixer under the scan's kernels).

Tolerances. Both forms make the same K products and the same sums in
the same order in float32 and round once; XLA's CPU code may contract a
product and a sum where the interpreter's does not, so float32 results
are held to 2e-6 of the largest entry and a bfloat16 result to one
rounding (2^-8 of the largest entry; measured: one last place in a few
entries). dw and db are float32 sums over batch and time in another
order: 2e-5. A tap dropped, a shift by one too many, a halo not masked
at the sequence's start or taken from the wrong side miss these by
orders of magnitude (the marker tests show where a value may land).
"""

import functools
import os
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "scripts"))

import lower_cmp  # noqa: E402
from benchmark import manifest as mf  # noqa: E402
from ompi_tpu.core import pvar  # noqa: E402
from ompi_tpu.ops import causal_conv as ck  # noqa: E402
from ompi_tpu.ops import kda, ssm  # noqa: E402

F32 = jnp.float32
K = 4
#: name -> (time last, the block): two and more tiles along time and
#: along the channels in either layout
LAYOUTS = {"time_in_sublanes": (False, ck.Tile(64, 128)),
           "time_in_lanes": (True, ck.Tile(128, 16))}
DTYPES = {"float32": (jnp.float32, 2e-6), "bfloat16": (jnp.bfloat16, 2 ** -8)}


def _operands(b, t, c, dtype, bias, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (b, t, c)).astype(dtype),
            jax.random.normal(ks[1], (c, K)) * 0.5,
            jax.random.normal(ks[2], (c,)) if bias else None,
            jax.random.normal(ks[3], (b, t, c)).astype(dtype))


def kernels(x, w, b, tile, time_last):
    """`ck.conv` in interpret mode, its result as [B, T, C]."""
    y = ck.conv(x, w, b, tile, time_last, interpret=True)
    return jnp.swapaxes(y, 1, 2) if time_last else y


def gap(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no_bias"])
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("layout", LAYOUTS)
def test_the_kernels_are_the_shifted_sums(layout, dtype, bias):
    """The result and every cotangent, across tiles of time and of
    channels and two batch rows."""
    time_last, tile = LAYOUTS[layout]
    dtype, tol = DTYPES[dtype]
    x, w, b, dy = _operands(2, 256, 256 if not time_last else 48, dtype, bias)
    y0, back0 = jax.vjp(ssm.shifted_conv, x, w, b)
    y1, back1 = jax.vjp(functools.partial(kernels, tile=tile,
                                          time_last=time_last), x, w, b)
    assert y1.dtype == x.dtype and gap(y1, y0) <= tol
    g0, g1 = back0(dy), back1(dy)
    assert g1[0].dtype == x.dtype and gap(g1[0], g0[0]) <= tol
    assert g1[1].dtype == w.dtype and gap(g1[1], g0[1]) <= 2e-5
    if bias:
        assert gap(g1[2], g0[2]) <= 2e-5
    else:
        assert g1[2] is None


@pytest.mark.parametrize("row", [1, 2, 3])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_a_marker_crosses_a_tiles_edge_forwards_only(layout, row):
    """An impulse `row` entries before a tile's end (unit taps, no
    bias) reaches the K entries from its own on — the next tile's
    first ones among them — and nothing before it; the cotangent runs
    the other way: a dy in the next tile's first entries reaches back
    over the edge."""
    time_last, tile = LAYOUTS[layout]
    t, c = 3 * tile.time, tile.channels
    at = 2 * tile.time - row
    w = jnp.ones((c, K))
    x = jnp.zeros((1, t, c)).at[0, at, :].set(1.0)
    y = kernels(x, w, None, tile, time_last)
    hit = np.flatnonzero(np.asarray(y[0, :, 0]))
    assert list(hit) == list(range(at, at + K))
    assert gap(y, ssm.shifted_conv(x, w)) <= 2e-6
    # backwards: d x[s] = sum of w * d pre[s .. s + K - 1]
    here = 2 * tile.time + row - 1          # in the NEXT tile's first rows
    dy = jnp.zeros((1, t, c)).at[0, here, :].set(1.0)
    x = jax.random.normal(jax.random.PRNGKey(row), (1, t, c))
    dx = jax.vjp(functools.partial(kernels, tile=tile, time_last=time_last),
                 x, w, None)[1](dy)[0]
    hit = np.flatnonzero(np.asarray(dx[0, :, 0]))
    assert list(hit) == list(range(here - K + 1, here + 1))
    assert gap(dx, jax.vjp(ssm.shifted_conv, x, w)[1](dy)[0]) <= 2e-6


@pytest.mark.parametrize("layout", LAYOUTS)
def test_zeros_stand_before_the_sequence_and_rows_are_alone(layout):
    """The first K - 1 results read zeros in front of the sequence
    (NOT the clamped halo's rows), the last rows' dx nothing behind it,
    and a batch row nothing of another."""
    time_last, tile = LAYOUTS[layout]
    x, w, b, dy = _operands(2, 2 * tile.time, tile.channels, jnp.float32,
                            True, seed=3)
    run = functools.partial(kernels, tile=tile, time_last=time_last)
    y = run(x, w, b)
    first = jax.nn.silu(b + w[:, K - 1] * x[:, 0])
    np.testing.assert_allclose(y[:, 0], first, rtol=2e-6, atol=2e-6)
    other = x.at[1].set(jax.random.normal(jax.random.PRNGKey(9), x.shape[1:]))
    assert np.array_equal(run(other, w, b)[0], y[0])
    dx = jax.vjp(run, x, w, b)[1](dy)[0]
    dx_other = jax.vjp(run, other, w, b)[1](dy)[0]
    assert np.array_equal(dx_other[0], dx[0])
    pre = b + w[:, K - 1] * x[:, -1] + w[:, K - 2] * x[:, -2] \
        + w[:, K - 3] * x[:, -3] + w[:, 0] * x[:, -4]
    s = jax.nn.sigmoid(pre)
    last = dy[:, -1] * s * (1 + pre * (1 - s)) * w[:, K - 1]
    np.testing.assert_allclose(dx[:, -1], last, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no_bias"])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_columns_of_a_wider_array_are_read_where_they_lie(layout, bias):
    """`first`: the convolved channels lie inside a wider array (the
    mixer's `[z | xBC | dt]`); the block index takes the offset, the
    result is the slice's and the wide array's cotangent is the
    slice's, zero around it."""
    time_last, tile = LAYOUTS[layout]
    c, first = 2 * tile.channels, tile.channels
    wide = first + c + 24
    x, w, b, dy = _operands(2, 256, c, jnp.float32, bias, seed=5)
    whole = jax.random.normal(jax.random.PRNGKey(11), (2, 256, wide))
    whole = whole.at[..., first:first + c].set(x)

    def run(whole, w, b):
        y = ck.conv(whole, w, b, tile, time_last, interpret=True,
                    first=first)
        return jnp.swapaxes(y, 1, 2) if time_last else y

    def sliced(whole, w, b):
        return ssm.shifted_conv(whole[..., first:first + c], w, b)

    y0, back0 = jax.vjp(sliced, whole, w, b)
    y1, back1 = jax.vjp(run, whole, w, b)
    assert y1.shape == y0.shape and gap(y1, y0) <= 2e-6
    for got, want in zip(back1(dy), back0(dy)):
        assert (got is None and want is None) or (
            got.shape == want.shape and gap(got, want) <= 2e-5)
    assert not np.asarray(back1(dy)[0][..., :first]).any()
    assert not np.asarray(back1(dy)[0][..., first + c:]).any()
    with pytest.raises(ValueError, match="does not tile"):
        ck.conv(whole, w, b, tile, time_last, interpret=True, first=8)


RULE = {
    "the_cells_delta_rule_run": (("tpu", 8192, 2048, 4, jnp.bfloat16),
                                 (2048, 512)),
    "the_cells_xbc": (("tpu", 8192, 6144, 4, jnp.bfloat16), (2048, 512)),
    "float32": (("tpu", 8192, 2048, 4, jnp.float32), (2048, 512)),
    "a_short_sequence_few_channels": (("tpu", 384, 640, 2, jnp.bfloat16),
                                      (128, 128)),
    "one_tap": (("tpu", 1024, 256, 1, jnp.bfloat16), (1024, 256)),
    "nine_taps_fill_the_halo": (("tpu", 1024, 256, 9, jnp.bfloat16),
                                (1024, 256)),
    "off_the_tpu": (("cpu", 8192, 2048, 4, jnp.bfloat16), None),
    "on_a_gpu": (("gpu", 8192, 2048, 4, jnp.bfloat16), None),
    "a_sequence_the_lanes_do_not_divide": (
        ("tpu", 8192 + 64, 2048, 4, jnp.bfloat16), None),
    "channels_the_lanes_do_not_divide": (
        ("tpu", 8192, 2048 + 64, 4, jnp.bfloat16), None),
    "taps_over_the_halo": (("tpu", 8192, 2048, 10, jnp.bfloat16), None),
    "a_type_of_one_byte": (("tpu", 8192, 2048, 4, jnp.int8), None),
}


@pytest.mark.parametrize("case", RULE)
def test_the_rule_reads_the_backend_and_the_shapes(case):
    asked, tile = RULE[case]
    assert ssm.conv_tile(*asked) == tile
    if tile is not None:    # what the rule hands out tiles the operand
        assert asked[1] % tile[0] == 0 and asked[2] % tile[1] == 0
        assert asked[3] - 1 <= ck.HALO[0] == ssm._CONV_HALO


@pytest.fixture
def conv_kernels_on_cpu(monkeypatch):
    """A switch: from its call on `ssm.causal_conv` is as on a TPU —
    the rule answering with a block that tiles a toy operand (as wide
    as its channels: the interpreter asks nothing of a block's shape),
    the kernels in interpret mode."""
    def tile(backend, t, channels, taps, dtype):
        step = 128 if t % 128 == 0 else 16
        return (step, channels) if t % step == 0 else None

    def switch():
        monkeypatch.setattr(ssm, "conv_tile", tile)
        monkeypatch.setattr(ck, "conv", functools.partial(ck.conv,
                                                          interpret=True))
    return switch


@pytest.mark.parametrize("time_last", [False, True],
                         ids=["as_it_came", "time_last"])
def test_a_refusal_falls_back_and_is_counted(pvar_clean, time_last):
    """Off the TPU the entry is the shifted sums, whatever the layout
    asked for, and says so."""
    x, w, b, _ = _operands(2, 40, 24, jnp.float32, True)
    y = ssm.causal_conv(x, w, b, time_last=time_last)
    want = ssm.shifted_conv(x, w, b)
    assert np.array_equal(y, jnp.swapaxes(want, 1, 2) if time_last else want)
    assert (pvar.read("conv_kernel_layers"),
            pvar.read("conv_shifted_layers")) == (0, 1)


@pytest.mark.parametrize("time_last", [False, True],
                         ids=["as_it_came", "time_last"])
def test_the_entry_takes_the_kernels_where_the_rule_says(
        pvar_clean, conv_kernels_on_cpu, time_last):
    conv_kernels_on_cpu()
    x, w, b, _ = _operands(2, 256, 24, jnp.float32, True)
    y = ssm.causal_conv(x, w, b, time_last=time_last)
    want = ssm.shifted_conv(x, w, b)
    assert y.shape == ((2, 24, 256) if time_last else (2, 256, 24))
    assert gap(jnp.swapaxes(y, 1, 2) if time_last else y, want) <= 2e-6
    assert (pvar.read("conv_kernel_layers"),
            pvar.read("conv_shifted_layers")) == (1, 0)
    # a sequence the toy rule's block does not divide: counted, shifted
    ssm.causal_conv(x[:, :40], w, b, time_last=time_last)
    assert pvar.read("conv_shifted_layers") == 1
    # a slice of a wider array, handed over with it: an offset the
    # block divides rides the block index, another takes the slice
    wide = jnp.concatenate([x, x, x[..., :8]], axis=-1)
    for first in (24, 8):
        again = ssm.causal_conv(wide[..., first:first + 24], w, b,
                                time_last=time_last, within=(wide, first))
        assert gap(jnp.swapaxes(again, 1, 2) if time_last else again,
                   ssm.shifted_conv(wide[..., first:first + 24], w, b)) <= 2e-6
    assert pvar.read("conv_kernel_layers") == 3


# -- the two callers -------------------------------------------------------------

def _leaves(shapes, dtype, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    return {name: (jax.random.normal(k, shape) * 0.3).astype(dtype)
            for k, (name, shape) in zip(ks, shapes.items())}


def _ssm_mixer(d, h, p, g, n, chunk, dtype, seed=0):
    inner, bc = h * p, g * n
    lp = _leaves(dict(in_proj=(d, 2 * inner + 2 * bc + h),
                      conv_w=(inner + 2 * bc, K), conv_b=(inner + 2 * bc,),
                      A_log=(h,), D=(h,), dt_bias=(h,), out_proj=(inner, d)),
                 dtype, seed)
    lp["ssm_norm"] = {"g": jnp.ones((inner,), dtype)}
    run = functools.partial(ssm.mixer, heads=h, head_dim=p, groups=g,
                            state=n, chunk=chunk, eps=1e-5)
    return lp, run


def _value_and_grads(run, lp, x, weight):
    def loss(lp, x):
        out, last = run(lp, x)
        return (out.astype(F32) * weight).sum() + last.sum()
    return jax.value_and_grad(loss, (0, 1))(lp, x)


def _held(got, want, tol):
    """Every leaf within `tol` of the want's norm (tests/test_nemotron.py's
    and tests/test_solar2.py's measure for the scan's and the core's
    kernels)."""
    flat_g, flat_w = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(flat_g) == len(flat_w)
    for a, b in zip(flat_g, flat_w):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert np.linalg.norm(a - b) <= tol * max(np.linalg.norm(b), 1e-30)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_state_space_mixer_on_the_kernels_is_the_mixer(
        conv_kernels_on_cpu, pvar_clean, dtype):
    """`ssm.mixer`'s output and every gradient with the convolution on
    the kernels (the scan on the products, as on the CPU: `[B, T, C]`
    in and out) against the mixer as the CPU runs it."""
    dtype, tol = {"float32": (jnp.float32, 2e-5),
                  "bfloat16": (jnp.bfloat16, 2e-2)}[dtype]
    lp, run = _ssm_mixer(32, 4, 8, 2, 16, 16, dtype)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 256, 32)).astype(dtype)
    weight = jax.random.normal(jax.random.PRNGKey(6), (2, 256, 32))
    want = _value_and_grads(run, lp, x, weight)
    assert pvar.read("conv_shifted_layers") == 1
    conv_kernels_on_cpu()
    got = _value_and_grads(run, lp, x, weight)
    assert pvar.read("conv_kernel_layers") == 1
    _held(got, want, tol)


def test_the_state_space_mixer_with_both_kinds_of_kernel(
        monkeypatch, conv_kernels_on_cpu, pvar_clean):
    """The layout the cell runs: the scan's kernels read `[B, C, T]`,
    so the convolution's run with time in the lanes and hand the scan
    what it reads — against the mixer as the CPU runs it, float32."""
    lp, run = _ssm_mixer(32, 2, 64, 1, 128, 128, jnp.float32, seed=2)
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 256, 32))
    weight = jax.random.normal(jax.random.PRNGKey(8), (1, 256, 32))
    with jax.default_matmul_precision("highest"):
        want = _value_and_grads(run, lp, x, weight)
        rule = ssm.scan_tile
        monkeypatch.setattr(ssm, "scan_tile",
                            lambda backend, *a, **k: rule("tpu", *a, **k))
        monkeypatch.setattr(ssm, "kernel_scan", functools.partial(
            ssm.kernel_scan, interpret=True))
        conv_kernels_on_cpu()
        got = _value_and_grads(run, lp, x, weight)
    assert (pvar.read("ssm_scan_kernel_layers"),
            pvar.read("conv_kernel_layers")) == (1, 1)
    _held(got, want, 2e-4)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_delta_rule_mixer_on_the_kernels_is_the_mixer(
        conv_kernels_on_cpu, pvar_clean, dtype):
    """`kda.mixer`'s output and every gradient with its three
    convolutions a run of heads on the kernels (no bias, `[B, T, h K]`
    in and out; the core as the CPU runs it) against the mixer as the
    CPU runs it."""
    dtype, tol = {"float32": (jnp.float32, 2e-5),
                  "bfloat16": (jnp.bfloat16, 2e-2)}[dtype]
    d, h, width, r = 32, 4, 8, 8
    lp = _leaves(dict(wq=(d, h * width), wk=(d, h * width), wv=(d, h * width),
                      conv_q=(h * width, K), conv_k=(h * width, K),
                      conv_v=(h * width, K), w_fa=(d, r), w_fb=(r, h * width),
                      dt_bias=(h * width,), A_log=(h,), w_b=(d, h),
                      w_ga=(d, r), w_gb=(r, h * width), wo=(h * width, d)),
                 dtype, 4)
    lp["o_norm"] = {"g": jnp.ones((width,), dtype)}
    run = functools.partial(kda.mixer, heads=h, head_dim=width, chunk=16,
                            eps=1e-5)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 128, d)).astype(dtype)
    weight = jax.random.normal(jax.random.PRNGKey(6), (2, 128, d))
    want = _value_and_grads(run, lp, x, weight)
    assert pvar.read("conv_shifted_layers") == 3
    conv_kernels_on_cpu()
    got = _value_and_grads(run, lp, x, weight)
    assert pvar.read("conv_kernel_layers") == 3
    _held(got, want, tol)


@pytest.mark.parametrize("cell, calls", [("nemotron-train-t8192", 1),
                                         ("solar2-train-t8192", 3)])
def test_a_cells_cpu_step_counts_its_convolutions_as_shifted(pvar_clean,
                                                             cell, calls):
    """The rehearsal step of either cell on the CPU: every traced
    convolution under `conv_shifted_layers` (one a state-space layer,
    three a delta-rule layer), none on the kernels —
    `scripts/lower_cmp.py`, which asks the rules as a v5e would be
    asked, reads the reverse at the cells' own shapes."""
    step, *shapes = lower_cmp.step_and_shapes(cell, mf.load(), mf,
                                              rehearsal=True)
    step.lower(*shapes)
    layers = pvar.read("ssm_layers") + pvar.read("kda_layers")
    assert layers > 0
    assert (pvar.read("conv_kernel_layers"),
            pvar.read("conv_shifted_layers")) == (0, calls * layers)


# -- for the chip ----------------------------------------------------------------

CELLS = {"solar2_run_of_heads": (False, 2048, False),
         "nemotron_xbc": (True, 6144, True)}


@pytest.mark.parametrize("cell", CELLS)
def test_the_kernels_compile_for_the_chip(one_chip, cell):
    """What interpret mode cannot show: both kernels at the two cells'
    shapes, bfloat16, at the rule's block, for a described v5e — and
    the backward pass needs no forward kernel (its residuals are the
    operands)."""
    time_last, c, bias = CELLS[cell]
    t = 8192
    tile = ssm.conv_tile("tpu", t, c, K, jnp.bfloat16)

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def run(x, w, b):
        return ck.conv(x, w, b, tile, time_last)

    shape = (1, c, t) if time_last else (1, t, c)
    args = (arg((1, t, c), jnp.bfloat16), arg((c, K)),
            arg((c,)) if bias else None)
    text = jax.jit(run).lower(*args).compile().as_text()
    assert "causal_conv_fwd" in text and "causal_conv_bwd" not in text
    text = jax.jit(lambda x, w, b, dy: jax.vjp(run, x, w, b)[1](dy)).lower(
        *args, arg(shape, jnp.bfloat16)).compile().as_text()
    assert "causal_conv_bwd" in text and "causal_conv_fwd" not in text
