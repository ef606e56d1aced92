"""The Mamba-2 scan's kernels (ops/ssm_scan.py behind `ops/ssm.kernel_scan`
and the rule `scan_tile`) against the `jax.numpy` form `ssm.chunked_scan`
and against the token-by-token recurrence of the reference, here on the
CPU with the kernels in interpret mode at toy sizes.

Tolerances. With float32 operands under `highest` both forms keep
float32 everywhere and differ by the order of the sums (the kernels
carry the state chunk by chunk where the product form multiplies by the
decays between chunk ends): 2e-5 of the largest entry (measured 3e-6).
With bfloat16 operands both round the same operands of the same four
products, but the product form rounds `y` before it adds `D x` and its
gradient's cotangents wherever they pass a bfloat16 value: 2% of the
norm (measured under 1%). A carry dropped between chunks, a `dS` not
carried, a head read from another's rows or a group's B taken for
another's miss these by orders of magnitude; two tests show the first
two do.
"""

import os
import re
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import nemotron_decoder as ref  # noqa: E402
from ompi_tpu.core import pvar  # noqa: E402
from ompi_tpu.ops import ssm  # noqa: E402
from ompi_tpu.ops import ssm_scan as sk  # noqa: E402

#: name -> (B, T, Dims): 1, 2 and many chunks; groups of one head and
#: of several
SHAPES = {
    "one_chunk_g2x1": (1, 16, sk.Dims(2, 8, 2, 16, 16)),
    "two_chunks_g2x2": (2, 16, sk.Dims(4, 8, 2, 16, 8)),
    "many_chunks_g2x2": (2, 48, sk.Dims(4, 8, 2, 16, 8)),
    "many_chunks_g1x4": (1, 64, sk.Dims(4, 8, 1, 32, 8)),
    "many_chunks_g2x4": (1, 64, sk.Dims(8, 4, 2, 16, 16)),
}
DTYPES = {"float32": (jnp.float32, 2e-5), "bfloat16": (jnp.bfloat16, 2e-2)}


def _operands(b, t, dims, dtype, with_d=True, seed=0):
    """(xbc, dt, a_log, d): dt and A as the family initialises them,
    but decays strong enough that a chunk forgets a good part."""
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    width = dims.inner + 2 * dims.groups * dims.state
    xbc = jax.random.normal(k[0], (b, t, width), jnp.float32).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, t, dims.heads)) - 1.0)
    a_log = jax.random.normal(k[2], (dims.heads,)) * 0.5
    d = (jax.random.normal(k[3], (dims.heads,)) if with_d
         else jnp.zeros((dims.heads,)))
    return xbc, dt, a_log, d


def _split(xbc, dims):
    b, t, _ = xbc.shape
    inner, bc = dims.inner, dims.groups * dims.state
    return (xbc[..., :inner].reshape(b, t, dims.heads, dims.head_dim),
            xbc[..., inner:inner + bc].reshape(b, t, dims.groups, dims.state),
            xbc[..., inner + bc:].reshape(b, t, dims.groups, dims.state))


def product_form(xbc, dt, a_log, d, dims):
    """What `ssm.mixer` computes where the rule says None."""
    xs, bm, cm = _split(xbc, dims)
    y, last = ssm.chunked_scan(xs, dt, -jnp.exp(a_log), bm, cm, dims.chunk)
    y = y.astype(jnp.float32) + d[:, None] * xs.astype(jnp.float32)
    return y.astype(xbc.dtype).reshape(xbc.shape[0], xbc.shape[1], -1), last


def kernels(xbc, dt, a_log, d, dims, interpret=True):
    """`ssm.kernel_scan` takes and gives the wide arrays sequence-last."""
    y, last = ssm.kernel_scan(jnp.swapaxes(xbc, 1, 2), dt, -jnp.exp(a_log),
                              d, dims, interpret=interpret)
    return jnp.swapaxes(y, 1, 2), last


def token_by_token(xbc, dt, a_log, d, dims):
    """The reference's recurrence, float32, a sequence at a time."""
    xs, bm, cm = (v.astype(jnp.float32) for v in _split(xbc, dims))
    bm, cm = (jnp.repeat(v, dims.per, axis=2) for v in (bm, cm))
    ys, lasts = zip(*(ref.recurrence(xs[i], dt[i], -jnp.exp(a_log), bm[i],
                                     cm[i], 8) for i in range(xs.shape[0])))
    y = jnp.stack(ys) + d[:, None] * xs
    return y.reshape(xbc.shape[0], xbc.shape[1], -1), jnp.stack(lasts)


def highest(fn, *args):
    with jax.default_matmul_precision("highest"):
        return fn(*args)


def close(got, want, tol):
    """Largest entry in float32 (2e-5), the norm in bfloat16."""
    got, want = (np.asarray(v, np.float64) for v in (got, want))
    if tol < 1e-3:
        assert np.abs(got - want).max() <= tol * np.abs(want).max()
    else:
        assert np.linalg.norm(got - want) <= tol * np.linalg.norm(want)


def gap(got, want):
    got, want = (np.asarray(v, np.float64) for v in (got, want))
    return np.linalg.norm(got - want) / np.linalg.norm(want)


# -- values ------------------------------------------------------------------

@pytest.mark.parametrize("with_d", [True, False], ids=["D", "no_D"])
@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_forward_is_the_product_form_and_the_recurrence(shape, dtype, with_d):
    (b, t, dims), (dtype, tol) = SHAPES[shape], DTYPES[dtype]
    args = _operands(b, t, dims, dtype, with_d)
    y, last = highest(kernels, *args, dims)
    assert y.dtype == dtype and y.shape == (b, t, dims.inner)
    assert last.dtype == jnp.float32 and last.shape == (
        b, dims.heads, dims.head_dim, dims.state)
    for form in (product_form, token_by_token):
        y_want, last_want = highest(form, *args, dims)
        close(y, y_want, tol)
        close(last, last_want, tol)


def test_a_carry_dropped_between_chunks_is_seen():
    """The kernels run on each chunk alone (every chunk from a zero
    state) are nowhere near the scan: what the tolerance above holds."""
    b, t, dims = SHAPES["many_chunks_g2x2"]
    xbc, dt, a_log, d = _operands(b, t, dims, jnp.float32)
    args = xbc, dt, a_log - 2.0, d     # a chunk forgets little
    y_want, last_want = highest(product_form, *args, dims)
    parts = [highest(kernels, xbc[:, at:at + dims.chunk],
                     dt[:, at:at + dims.chunk], *args[2:], dims)
             for at in range(0, t, dims.chunk)]
    assert gap(jnp.concatenate([y for y, _ in parts], axis=1), y_want) > 0.1
    assert gap(parts[-1][1], last_want) > 0.1


# -- gradients ---------------------------------------------------------------

def _value(form, cts, dims):
    def value(*args):
        y, last = form(*args, dims)
        return ((y.astype(jnp.float32) * cts[0]).sum()
                + (last * cts[1]).sum())
    return value


def _cotangents(b, t, dims, last: bool):
    k = jax.random.split(jax.random.PRNGKey(9), 2)
    shape = (b, dims.heads, dims.head_dim, dims.state)
    return (jax.random.normal(k[0], (b, t, dims.inner)),
            jax.random.normal(k[1], shape) if last else jnp.zeros(shape))


@pytest.mark.parametrize("last", [True, False],
                         ids=["cotangent_of_last", "of_y_alone"])
@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_every_gradient_is_autodiffs_of_the_product_form(shape, dtype, last):
    """x, B and C (the three parts of xbc apart), dt, A_log through a
    and through the cumulative sums, D — with a cotangent of the state
    after the last token as well as of y."""
    (b, t, dims), (dtype, tol) = SHAPES[shape], DTYPES[dtype]
    args = _operands(b, t, dims, dtype)
    cts = _cotangents(b, t, dims, last)
    got = highest(jax.grad(_value(kernels, cts, dims), (0, 1, 2, 3)), *args)
    want = highest(jax.grad(_value(product_form, cts, dims), (0, 1, 2, 3)),
                   *args)
    assert got[0].dtype == dtype
    for g, w in zip(_split(got[0], dims) + got[1:],
                    _split(want[0], dims) + want[1:]):
        assert float(jnp.abs(w.astype(jnp.float32)).max()) > 0
        close(g, w, 5 * tol)


def test_a_state_gradient_not_carried_is_seen():
    """The reverse kernel run on each chunk alone — the true entering
    states, but no `dS` from the chunks behind — is nowhere near the
    gradient."""
    b, t, dims = SHAPES["many_chunks_g2x2"]
    xbc, dt, a_log, d = _operands(b, t, dims, jnp.float32)
    a_log = a_log - 2.0                # a chunk forgets little
    cts = _cotangents(b, t, dims, False)
    want = highest(jax.grad(_value(product_form, cts, dims)), xbc, dt, a_log,
                   d)
    per, l = dims.per, dims.chunk
    cum = jnp.cumsum((dt * -jnp.exp(a_log)).reshape(b, t // l, l, -1),
                     axis=2).reshape(dt.shape)
    last = lambda v: jnp.swapaxes(v, 1, 2)  # noqa: E731
    rows = lambda v: last(v).reshape(b, dims.groups, per, -1)  # noqa: E731
    cols = lambda v: v.reshape(b, -1, dims.groups, per).transpose(0, 2, 1, 3)  # noqa: E731,E501
    entering = highest(sk.states, last(xbc), rows(dt), rows(cum), dims, True)
    none = jnp.zeros((b, dims.inner, dims.state))

    def reverse(at, entering, dlast):
        part = slice(at, at + (l if entering.shape[1] == 1 else t))
        return last(highest(
            sk.backward, last(xbc[:, part]), last(cts[0][:, part]), dlast,
            entering, rows(dt[:, part]), rows(cum[:, part]),
            cols(dt[:, part]), cols(cum[:, part]), d, dims, True)[0])

    close(reverse(0, entering, none), want[..., :dims.inner], 1e-4)
    alone = jnp.concatenate([reverse(at, entering[:, at // l:at // l + 1],
                                     none) for at in range(0, t, l)], axis=1)
    assert gap(alone, want[..., :dims.inner]) > 0.1


# -- the rule ----------------------------------------------------------------

#: nemotron-train-t8192's scan: T, H, P, G, N, L
CELL = (8192, 64, 64, 8, 128, 128)


def test_the_rule_reads_backend_and_shapes():
    assert ssm.scan_tile("tpu", *CELL, jnp.bfloat16) == sk.Dims(
        64, 64, 8, 128, 128)
    assert ssm.scan_tile("cpu", *CELL, jnp.bfloat16) is None
    assert ssm.scan_tile("tpu", *CELL, jnp.float32) is not None
    t, h, p, g, n, chunk = CELL
    assert ssm.scan_tile("tpu", 96 * 64, h, p, g, n, 96, jnp.bfloat16) is None
    assert ssm.scan_tile("tpu", t, h, p, g, 48, chunk, jnp.bfloat16) is None
    # a sequence the chunk does not divide; heads that are no whole
    # sublane tiles of the type (8 rows of float32, 16 of bfloat16);
    # B's first row not a whole number of states; a chunk whose [L, L]
    # temporaries do not fit
    assert ssm.scan_tile("tpu", t + 64, h, p, g, n, chunk, jnp.bfloat16) \
        is None
    assert ssm.scan_tile("tpu", t, h, 24, g, n, chunk, jnp.bfloat16) is None
    assert ssm.scan_tile("tpu", t, h, 24, g, 192, chunk, jnp.float32) is None
    assert ssm.scan_tile("tpu", t, 128, 24, g, n, chunk, jnp.float32) \
        is not None
    assert ssm.scan_tile("tpu", t, 24, 96, g, n, chunk, jnp.bfloat16) \
        is not None
    assert ssm.scan_tile("tpu", t, h, p, g, n, 2048, jnp.bfloat16) is None


def _mixer_leaves(d_model, h, p, g, n, conv=4, dtype=jnp.float32):
    inner, bc = h * p, g * n
    shapes = dict(in_proj=(d_model, 2 * inner + 2 * bc + h),
                  conv_w=(inner + 2 * bc, conv), conv_b=(inner + 2 * bc,),
                  A_log=(h,), D=(h,), dt_bias=(h,), out_proj=(inner, d_model))
    lp = {k: jax.ShapeDtypeStruct(s, dtype) for k, s in shapes.items()}
    lp["ssm_norm"] = {"g": jax.ShapeDtypeStruct((inner,), dtype)}
    return lp


def test_the_counters_say_which_form_ran(pvar_clean):
    """Once per traced mixer, whatever asks for it; on the CPU the rule
    says the products."""
    lp = _mixer_leaves(32, 4, 8, 2, 16)
    x = jax.ShapeDtypeStruct((2, 32, 32), jnp.float32)
    jax.eval_shape(lambda lp, x: ssm.mixer(
        lp, x, heads=4, head_dim=8, groups=2, state=16, chunk=8, eps=1e-5),
        lp, x)
    assert (pvar.read("ssm_scan_kernel_layers"),
            pvar.read("ssm_scan_product_layers")) == (0, 1)


@pytest.mark.parametrize("heads, head_dim", [(32, 128), (24, 96), (128, 32)],
                         ids=["heads_of_128", "heads_of_96", "heads_of_32"])
def test_other_heads_compile_for_the_chip(one_chip, heads, head_dim):
    """What interpret mode cannot show, for heads the cell does not
    have: wider than the lanes' 128, no power of two, narrow."""
    t, _, _, g, n, chunk = CELL
    dims = ssm.scan_tile("tpu", t, heads, head_dim, g, n, chunk, jnp.bfloat16)

    def loss(xbc, dt, a_log, d, gy):
        y, last = kernels(xbc, dt, a_log, d, dims, interpret=False)
        return (y.astype(jnp.float32) * gy).sum() + last.sum()

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (arg((1, t, dims.inner + 2 * g * n), jnp.bfloat16),
            arg((1, t, heads)), arg((heads,)), arg((heads,)),
            arg((1, t, dims.inner)))
    text = jax.jit(jax.value_and_grad(loss, (0, 1, 2, 3))).lower(
        *args).compile().as_text()
    for name in ("ssm_scan_fwd", "ssm_scan_states", "ssm_scan_bwd"):
        assert name in text
    # the gradient alone needs no forward kernel: the backward keeps the
    # operands and nothing the forward made (what lets a recomputed
    # layer that kept `ssm_y` drop it)
    text = jax.jit(jax.grad(loss, (0, 1, 2, 3))).lower(
        *args).compile().as_text()
    assert "ssm_scan_fwd" not in text and "ssm_scan_bwd" in text


def test_the_mixer_compiles_for_the_chip_on_the_kernels(one_chip, monkeypatch,
                                                        pvar_clean):
    """nemotron-train-t8192's mixer (the published widths, bfloat16) for
    a described v5e, the rule asked as on the TPU: the scan is one kernel
    forward and two backward under the scope's path, no float32 value of
    the decays' size exists and no loop."""
    t, h, p, g, n, chunk = CELL
    d_model = 2688
    rule = ssm.scan_tile
    monkeypatch.setattr(ssm, "scan_tile",
                        lambda backend, *a, **k: rule("tpu", *a, **k))

    def loss(lp, x, gy):
        with jax.named_scope("ssm"):
            out, _ = ssm.mixer(lp, x, heads=h, head_dim=p, groups=g, state=n,
                               chunk=chunk, eps=1e-5)
        return (out.astype(jnp.float32) * gy).sum()

    def on_chip(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    lp = jax.tree.map(on_chip, _mixer_leaves(d_model, h, p, g, n,
                                             dtype=jnp.bfloat16))
    text = jax.jit(jax.value_and_grad(loss, (0, 1))).lower(
        lp, on_chip(jax.ShapeDtypeStruct((1, t, d_model), jnp.bfloat16)),
        on_chip(jax.ShapeDtypeStruct((1, t, d_model), jnp.float32))
    ).compile().as_text()
    assert (pvar.read("ssm_scan_kernel_layers"),
            pvar.read("ssm_scan_product_layers")) == (1, 0)
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    # .../jvp(ssm)/ssm_scan/... and .../transpose(jvp(ssm))/ssm_scan/...
    assert all(re.search(r"\bssm\)*/ssm_scan/", c) for c in calls), calls
    names = [c.split(" = ")[0].strip().lstrip("%").rsplit(".", 1)[0]
             for c in calls]
    assert sorted(names) == ["ssm_scan_bwd", "ssm_scan_fwd",
                             "ssm_scan_states"], names
    for dims in re.findall(r"f32\[([\d,]+)\]", text):
        dims = [int(v) for v in dims.split(",")]
        assert dims[-2:] != [chunk, chunk] or np.prod(dims) < t * h * chunk, \
            dims
    assert "while" not in text
