"""The Pallas TPU kernels of the Mamba-2 chunked scan: the recurrence of
``ops/ssm.py``'s module docstring over whole sequences from a zero
state, with its ``D x`` term (``ops/ssm.py`` owns the rule,
``scan_tile``, that says when they run, the cumulative log-decays they
are handed and the ``custom_vjp`` around them; ``ssm.chunked_scan`` is
their oracle).

Three kernels on one grid (batch, group, chunk), the chunk axis
sequential and a group's state ``[heads a group * P, N]`` float32 carried
along it in a VMEM scratch. A chunk's ``[L, L]`` decays, scores and
mixing matrix live in VMEM only — float32 exponents, decays and
accumulators, the operands' type (bfloat16) on the MXU, the same four
products with the same operand types as the ``jax.numpy`` form:

* :func:`forward` (``ssm_scan_fwd``): per chunk ``B C^T`` once a group;
  per head the causal decays (masked BEFORE the exponential), ``mixed =
  scores * decay * dt_s`` rounded, ``y = mixed @ x + exp(cum_l) (C @
  S^T) + D x`` with ``S`` rounded for the read-out, then ``S <-
  exp(total) S + (x exp(total - cum_s) dt_s)^T @ B``. The state is
  zeroed at a group's first chunk and written as ``last`` at its last.
* :func:`states` (``ssm_scan_states``): the state ENTERING every chunk,
  by the forward's last product alone (no ``[L, L]`` work). It runs in
  front of the reverse kernel: the ``custom_vjp`` keeps its inputs and
  nothing else, so a recomputed layer whose ``ssm_y`` is kept never runs
  `forward` again.
* :func:`backward` (``ssm_scan_bwd``): the grid walked from the last
  chunk to the first, ``dS`` carried and started from the cotangent of
  ``last``. Per chunk, from its operands, ``dy`` and the entering state:
  ``dx``, ``dB`` and ``dC`` (summed over the group's heads in VMEM),
  ``d dt`` (the direct factor), ``d cum`` (all four exponentials) and
  ``dD`` (summed along the grid in its output block). A float32
  cotangent is rounded to the operands' type before a product, as XLA's
  default precision does.

**Layout: the sequence in the lanes.** Every operand comes TRANSPOSED,
``[B, channels, T]``: that is how XLA lays the mixer's arrays out by
itself (the convolution shifts along the minor dimension), so the
caller's ``swapaxes`` around the kernels are bitcasts and the
convolution, the gate and both projections keep the layouts they have
without the kernels. Kernels on ``[B, T, channels]`` were built first
and measured (PERF.md section 6, PR 41): XLA then re-laid the whole
mixer out row-major and the neighbours lost 10 of the 24 ms the scan
gained. x, B and C are read AS THEY LIE in the convolved ``xbc``: three
``BlockSpec``s over the one ``[B, H P + 2 G N, T]`` array (x at row
block g of ``heads a group * P``, B and C at row blocks of N behind ``H
P``). A head is a run of SUBLANES (``[P, L]``: any P of whole tiles, no
lane select), the ``[L, L]`` matrices are worked ``[s, l]`` (x feeds ``x
@ mixed`` as it lies, ``dy`` ``dy @ mixed^T`` likewise), a token's
``exp(cum_l)`` or ``dt_s exp(total - cum_s)`` is a row broadcast over a
head's sublanes, and the backward's sums over P are sums over sublanes.
The per-head vectors ``dt`` and ``cum`` come in BOTH layouts, ``[B, G,
heads a group, T]`` (a row along the lanes) and ``[B, G, T, heads a
group]`` (a column along the sublanes, for the decays' ``cum_s`` and
``dt_s``): 2 MB each, made by XLA, so that no transposition of them
stands in a kernel; D is read from SMEM.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ompi_tpu.ops.grouped_matmul import VMEM_LIMIT_BYTES
from ompi_tpu.ops.sparse_attention import _NT

F32 = jnp.float32
_TN = (((0,), (0,)), ((), ()))  # A^T B


class Dims(NamedTuple):
    """The scan's static sizes: heads H of `head_dim` P in `groups` G
    of `state` N, chunks of `chunk` L tokens."""
    heads: int
    head_dim: int
    groups: int
    state: int
    chunk: int

    @property
    def per(self) -> int:
        return self.heads // self.groups

    @property
    def inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def width(self) -> int:
        """Rows of a group's heads."""
        return self.per * self.head_dim


def _spread(vals, shape, axis: int):
    """The [.., 1]-wide (or [1, ..]-high) `vals`, one a head of the
    group, as one block of `shape`: val h at index h of `axis`."""
    at = lax.broadcasted_iota(jnp.int32, shape, axis)
    out = jnp.zeros(shape, F32)
    for h, v in enumerate(vals):
        out = jnp.where(at == h, v, out)
    return out


def _col(cols, h: int):
    return cols[:, h:h + 1]                                   # [L, 1]


def _row(rows, h: int):
    return rows[h:h + 1, :]                                   # [1, L]


def _over(one, width: int):
    """A [1, 1] value along `width` lanes, through a select: Mosaic
    broadcasts along the sublanes OR the lanes in one step, and two
    plain broadcasts in a row are folded into one that does both — the
    value that scales a whole [P, N] state takes the lanes here and the
    sublanes in the product."""
    lane = lax.broadcasted_iota(jnp.int32, (1, width), 1)
    return jnp.where(lane >= 0, one, 0.0)


def _to_end(cum_r, dt_r):
    """Of all the group's heads at once, a head a row: (exp(total)
    [heads, 1], exp(total - cum_s), that times dt_s [heads, L])."""
    cum = cum_r[...]
    total = cum[:, -1:]
    left = jnp.exp(total - cum)
    return jnp.exp(total), left, left * dt_r[...]


def _decay_t(cum_row, cum_col):
    """exp(cum_l - cum_s) where s <= l and 0 elsewhere, [s, l]: masked
    BEFORE the exponential. cum_row [1, L] (l), cum_col [L, 1] (s)."""
    l = cum_row.shape[1]
    causal_t = (lax.broadcasted_iota(jnp.int32, (l, l), 0)
                <= lax.broadcasted_iota(jnp.int32, (l, l), 1))
    return jnp.exp(jnp.where(causal_t, cum_row - cum_col, -jnp.inf))


def _params(flops: int, exps: int, nbytes: int):
    return dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(flops=flops, transcendentals=exps,
                                      bytes_accessed=nbytes))


def _check(what: str, xbc, dims: Dims):
    c, t = xbc.shape[1], xbc.shape[2]
    if (t % dims.chunk or dims.heads % dims.groups
            or c != dims.inner + 2 * dims.groups * dims.state
            or dims.inner % dims.state):
        raise ValueError(f"{what}: {dims} does not tile xbc {xbc.shape}")


def _specs(dims: Dims, chunk_of):
    """The block specs of the kernels' operands on the grid (b, g, c);
    `chunk_of` maps the grid's c to the chunk worked."""
    l, n, w = dims.chunk, dims.state, dims.width
    first = dims.inner // n  # B's first row block of N
    return dict(
        x=pl.BlockSpec((None, w, l), lambda b, g, c: (b, g, chunk_of(c))),
        bm=pl.BlockSpec((None, n, l),
                        lambda b, g, c: (b, first + g, chunk_of(c))),
        cm=pl.BlockSpec((None, n, l), lambda b, g, c:
                        (b, first + dims.groups + g, chunk_of(c))),
        #: y, dx as x; dB, dC [B, G N, T]
        bc=pl.BlockSpec((None, n, l), lambda b, g, c: (b, g, chunk_of(c))),
        row=pl.BlockSpec((None, None, dims.per, l),
                         lambda b, g, c: (b, g, 0, chunk_of(c))),
        col=pl.BlockSpec((None, None, l, dims.per),
                         lambda b, g, c: (b, g, chunk_of(c), 0)),
        d=pl.BlockSpec(memory_space=pltpu.SMEM),
        dd=pl.BlockSpec((None, w, l), lambda b, g, c: (b, g, 0)),
        last=pl.BlockSpec((None, w, n), lambda b, g, c: (b, g, 0)),
        state=pl.BlockSpec((None, None, w, n),
                           lambda b, g, c: (b, chunk_of(c), g, 0)))


# -- forward -------------------------------------------------------------------

# A HEAD's work is a jitted function of the head's own operands: the
# kernels call it once a head, and jax traces it once — a kernel body
# written out for eight heads took 1.2 s of the step's 4.9 s of tracing
# (PERF.md section 6, PR 41). Mosaic sees the same unrolled body.

@jax.jit
def _update(x_h, bm, s_h, left_dt, grown):
    """A head's state after the chunk: ``exp(total) S + (x exp(total -
    cum_s) dt_s) @ B``. x_h [P, L]; bm [N, L]; s_h [P, N] float32;
    left_dt [1, L], grown [1, 1]: the head's rows of `_to_end`'s."""
    xw = (x_h.astype(F32) * left_dt).astype(x_h.dtype)
    own = lax.dot_general(xw, bm, _NT, preferred_element_type=F32)
    return _over(grown, s_h.shape[1]) * s_h + own


@jax.jit
def _fwd_head(x_h, s_h, scores_t, cm, cum_row, cum_col, dt_col, grow, d_h):
    """y of one head over the chunk, [P, L] float32; scores_t [s, l];
    cm [N, L]; cum_row, grow = exp(cum) [1, L]; cum_col, dt_col [L, 1];
    d_h a scalar."""
    dtype = x_h.dtype
    mixed_t = (scores_t * _decay_t(cum_row, cum_col) * dt_col).astype(dtype)
    return (jnp.dot(x_h, mixed_t, preferred_element_type=F32)
            + grow * jnp.dot(s_h.astype(dtype), cm,
                             preferred_element_type=F32)
            + d_h * x_h.astype(F32))


def _fwd_kernel(x, bm, cm, dt_r, cum_r, dt_c, cum_c, d, y, last, s_s, *,
                dims: Dims):
    c = pl.program_id(2)
    p, per = dims.head_dim, dims.per

    @pl.when(c == 0)
    def _():
        s_s[...] = jnp.zeros_like(s_s)

    bmv, cmv = bm[...], cm[...]
    scores_t = lax.dot_general(bmv, cmv, _TN, preferred_element_type=F32)
    cum_rows, cum_cols, dt_cols = cum_r[...], cum_c[...], dt_c[...]
    grow = jnp.exp(cum_rows)
    grown, _, left_dt = _to_end(cum_r, dt_r)

    for h in range(per):
        rows = slice(h * p, (h + 1) * p)
        x_h, s_h = x[rows, :], s_s[rows, :]
        y[rows, :] = _fwd_head(
            x_h, s_h, scores_t, cmv, _row(cum_rows, h), _col(cum_cols, h),
            _col(dt_cols, h), _row(grow, h), d[pl.program_id(1) * per + h]
        ).astype(y.dtype)
        s_s[rows, :] = _update(x_h, bmv, s_h, _row(left_dt, h),
                               _row(grown, h))

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        last[...] = s_s[...]


def _counts(xbc, dims: Dims):
    """(batch, chunks, products' operations of one pass over the
    states' update alone, operands' bytes)."""
    b, t = xbc.shape[0], xbc.shape[2]
    update = 2 * b * t * dims.inner * dims.state
    return b, t // dims.chunk, update, b * t * (
        dims.inner + 2 * dims.groups * dims.state) * xbc.dtype.itemsize


@functools.partial(jax.jit, static_argnames=("dims", "interpret"))
def forward(xbc, dt_r, cum_r, dt_c, cum_c, d, dims: Dims,
            interpret: bool = False):
    """xbc [B, H P + 2 G N, T], the convolved ``[x | B | C]`` with the
    sequence last; dt, cum (the cumulative log-decay inside each chunk)
    float32 as rows ``_r`` [B, G, heads a group, T] and as columns
    ``_c`` [B, G, T, heads a group]; d [H] float32. Returns (y [B, H P,
    T] in xbc's type, the state after the last token [B, H P, N]
    float32)."""
    _check("ssm_scan_fwd", xbc, dims)
    b, nc, update, nbytes = _counts(xbc, dims)
    t, l = xbc.shape[2], dims.chunk
    on = _specs(dims, lambda c: c)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, dims=dims),
        name="ssm_scan_fwd",
        out_shape=(jax.ShapeDtypeStruct((b, dims.inner, t), xbc.dtype),
                   jax.ShapeDtypeStruct((b, dims.inner, dims.state), F32)),
        grid=(b, dims.groups, nc),
        in_specs=[on["x"], on["bm"], on["cm"], on["row"], on["row"],
                  on["col"], on["col"], on["d"]],
        out_specs=(on["x"], on["last"]),
        scratch_shapes=[pltpu.VMEM((dims.width, dims.state), F32)],
        interpret=interpret,
        **_params(flops=2 * update + 2 * b * t * l * (
            dims.groups * dims.state + dims.inner),
            exps=b * t * l * dims.heads, nbytes=2 * nbytes),
    )(xbc, xbc, xbc, dt_r, cum_r, dt_c, cum_c, d)


# -- the states entering the chunks ---------------------------------------------

def _states_kernel(x, bm, dt_r, cum_r, entering, s_s, *, dims: Dims):
    p = dims.head_dim

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_s[...] = jnp.zeros_like(s_s)

    entering[...] = s_s[...]
    bmv = bm[...]
    grown, _, left_dt = _to_end(cum_r, dt_r)
    for h in range(dims.per):
        rows = slice(h * p, (h + 1) * p)
        s_s[rows, :] = _update(x[rows, :], bmv, s_s[rows, :],
                               _row(left_dt, h), _row(grown, h))


@functools.partial(jax.jit, static_argnames=("dims", "interpret"))
def states(xbc, dt_r, cum_r, dims: Dims, interpret: bool = False):
    """The state ENTERING every chunk, [B, chunks, H P, N] float32 (the
    first: zeros); operands as `forward`'s."""
    _check("ssm_scan_states", xbc, dims)
    b, nc, update, nbytes = _counts(xbc, dims)
    on = _specs(dims, lambda c: c)
    return pl.pallas_call(
        functools.partial(_states_kernel, dims=dims),
        name="ssm_scan_states",
        out_shape=jax.ShapeDtypeStruct((b, nc, dims.inner, dims.state), F32),
        grid=(b, dims.groups, nc),
        in_specs=[on["x"], on["bm"], on["row"], on["row"]],
        out_specs=on["state"],
        scratch_shapes=[pltpu.VMEM((dims.width, dims.state), F32)],
        interpret=interpret,
        **_params(flops=update, exps=2 * b * xbc.shape[2] * dims.heads,
                  nbytes=nbytes + 4 * b * nc * dims.inner * dims.state),
    )(xbc, xbc, dt_r, cum_r)


# -- backward -------------------------------------------------------------------

@jax.jit
def _bwd_head(x_h, dy_h, s_h, ds_h, scores_t, bm, cm, cum_row, cum_col,
              dt_col, grow, left, left_dt, grown, d_h):
    """One head's part of the reverse step. Operands as `_fwd_head`'s,
    dy_h [P, L], ds_h [P, N] float32 (the cotangent of the state AFTER
    the chunk) and the head's rows of `_to_end`'s. Returns dx_h [P, L]
    float32, the state's cotangent entering the chunk [P, N], what the
    head adds to d scores [s, l], the two rounded operands of the
    group's dC and dB products (``dy exp(cum_l)`` and ``x exp(total -
    cum_s) dt_s``, [P, L]), ds_h rounded, and d dt, d cum in their two
    parts: rows [1, L] and columns [L, 1]."""
    dtype = x_h.dtype
    x_f, dy_f = x_h.astype(F32), dy_h.astype(F32)

    # inside the chunk, [s, l]
    decay_t = _decay_t(cum_row, cum_col)
    weighed = scores_t * decay_t
    dx_h = lax.dot_general(dy_h, (weighed * dt_col).astype(dtype), _NT,
                           preferred_element_type=F32)             # [P, s]
    dmixed_t = lax.dot_general(x_h, dy_h, _TN, preferred_element_type=F32)
    through = dmixed_t * weighed                   # d mixed * scores * decay
    by_s = through.sum(axis=1, keepdims=True)                      # [s, 1]
    by_l = (through * dt_col).sum(axis=0, keepdims=True)           # [1, l]

    # the entering state read out through C
    s_b = s_h.astype(dtype)
    read = dy_f * grow
    dread = (read * jnp.dot(s_b, cm, preferred_element_type=F32)
             ).sum(axis=0, keepdims=True)                          # [1, l]
    read = read.astype(dtype)
    ds_in = lax.dot_general(read, cm, _NT, preferred_element_type=F32)

    # the chunk's own state at its end
    ds_b = ds_h.astype(dtype)
    dxw = jnp.dot(ds_b, bm, preferred_element_type=F32)            # [P, s]
    dw = (dxw * x_f).sum(axis=0, keepdims=True)                    # [1, s]
    dw_left_dt = dw * left_dt
    kept = (ds_h * s_h).sum(axis=1, keepdims=True).sum(axis=0, keepdims=True)
    dtotal = dw_left_dt.sum(axis=1, keepdims=True) + grown * kept
    at_end = lax.broadcasted_iota(jnp.int32, dw.shape, 1) == dw.shape[1] - 1
    return (dx_h + dxw * left_dt + d_h * dy_f,
            _over(grown, ds_h.shape[1]) * ds_h + ds_in,
            dmixed_t * decay_t * dt_col, read, (x_f * left_dt).astype(dtype),
            ds_b, dw * left, by_s,
            by_l + dread - dw_left_dt + jnp.where(at_end, dtotal, 0.0),
            -by_s * dt_col)


def _bwd_kernel(x, bm, cm, dy, dt_r, cum_r, dt_c, cum_c, d, entering, dlast,
                dx, dbm, dcm, ddt_r, dcum_r, ddt_c, dcum_c, dd,
                ds_s, dsb_s, read_s, xw_s, *, dims: Dims):
    c = pl.program_id(2)
    l, p, per = dims.chunk, dims.head_dim, dims.per

    @pl.when(c == 0)
    def _():
        ds_s[...] = dlast[...]
        dd[...] = jnp.zeros_like(dd)

    bmv, cmv = bm[...], cm[...]
    dtype = bmv.dtype
    scores_t = lax.dot_general(bmv, cmv, _TN, preferred_element_type=F32)
    cum_rows, cum_cols, dt_cols = cum_r[...], cum_c[...], dt_c[...]
    grow = jnp.exp(cum_rows)
    grown, left, left_dt = _to_end(cum_r, dt_r)

    dscores_t = jnp.zeros((l, l), F32)
    rows_dt, rows_cum, cols_dt, cols_cum = [], [], [], []
    for h in range(per):
        rows = slice(h * p, (h + 1) * p)
        x_h, dy_h = x[rows, :], dy[rows, :]
        (dx_h, ds_s[rows, :], dscores_h, read_s[rows, :], xw_s[rows, :],
         dsb_s[rows, :], row_dt, col_dt, row_cum, col_cum) = _bwd_head(
            x_h, dy_h, entering[rows, :], ds_s[rows, :], scores_t, bmv, cmv,
            _row(cum_rows, h), _col(cum_cols, h), _col(dt_cols, h),
            _row(grow, h), _row(left, h), _row(left_dt, h), _row(grown, h),
            d[pl.program_id(1) * per + h])
        dx[rows, :] = dx_h.astype(dx.dtype)
        dd[rows, :] += dy_h.astype(F32) * x_h.astype(F32)
        dscores_t += dscores_h
        rows_dt.append(row_dt)
        cols_dt.append(col_dt)
        rows_cum.append(row_cum)
        cols_cum.append(col_cum)

    # over the group's heads at once: dB [N, s], dC [N, l]
    dscores_t = dscores_t.astype(dtype)
    dbm[...] = (lax.dot_general(dsb_s[...], xw_s[...], _TN,
                                preferred_element_type=F32)
                + lax.dot_general(cmv, dscores_t, _NT,
                                  preferred_element_type=F32)
                ).astype(dbm.dtype)
    dcm[...] = (lax.dot_general(entering[...].astype(dtype), read_s[...], _TN,
                                preferred_element_type=F32)
                + jnp.dot(bmv, dscores_t, preferred_element_type=F32)
                ).astype(dcm.dtype)
    ddt_r[...] = _spread(rows_dt, (per, l), 0)
    dcum_r[...] = _spread(rows_cum, (per, l), 0)
    ddt_c[...] = _spread(cols_dt, (l, per), 1)
    dcum_c[...] = _spread(cols_cum, (l, per), 1)


@functools.partial(jax.jit, static_argnames=("dims", "interpret"))
def backward(xbc, dy, dlast, entering, dt_r, cum_r, dt_c, cum_c, d,
             dims: Dims, interpret: bool = False):
    """The cotangents of `forward`'s operands for those of its results,
    dy [B, H P, T] and dlast [B, H P, N] float32; entering: `states`'.
    Returns (dx [B, H P, T], dB, dC [B, G N, T] in xbc's type; d dt and
    d cum, each in two parts the caller adds up — rows [B, G, heads a
    group, T] and columns [B, G, T, heads a group] —, and dD's terms
    [B, H P, L], a chunk's tokens summed over the chunks: float32)."""
    _check("ssm_scan_bwd", xbc, dims)
    b, nc, update, nbytes = _counts(xbc, dims)
    t, l, per = xbc.shape[2], dims.chunk, dims.per
    bc = dims.groups * dims.state
    on = _specs(dims, lambda c: nc - 1 - c)
    rows = jax.ShapeDtypeStruct((b, dims.groups, per, t), F32)
    cols = jax.ShapeDtypeStruct((b, dims.groups, t, per), F32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, dims=dims),
        name="ssm_scan_bwd",
        out_shape=(jax.ShapeDtypeStruct((b, dims.inner, t), xbc.dtype),
                   jax.ShapeDtypeStruct((b, bc, t), xbc.dtype),
                   jax.ShapeDtypeStruct((b, bc, t), xbc.dtype),
                   rows, rows, cols, cols,
                   jax.ShapeDtypeStruct((b, dims.inner, l), F32)),
        grid=(b, dims.groups, nc),
        in_specs=[on["x"], on["bm"], on["cm"], on["x"], on["row"], on["row"],
                  on["col"], on["col"], on["d"], on["state"], on["last"]],
        out_specs=(on["x"], on["bc"], on["bc"], on["row"], on["row"],
                   on["col"], on["col"], on["dd"]),
        scratch_shapes=[pltpu.VMEM((dims.width, dims.state), F32),
                        pltpu.VMEM((dims.width, dims.state), xbc.dtype),
                        pltpu.VMEM((dims.width, l), xbc.dtype),
                        pltpu.VMEM((dims.width, l), xbc.dtype)],
        interpret=interpret,
        **_params(flops=4 * update + 2 * b * t * l * (
            3 * dims.groups * dims.state + 2 * dims.inner),
            exps=b * t * l * dims.heads,
            nbytes=3 * nbytes + 4 * b * nc * dims.inner * dims.state),
    )(xbc, xbc, xbc, dy, dt_r, cum_r, dt_c, cum_c, d, entering, dlast)
