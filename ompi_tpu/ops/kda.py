"""Kimi Delta Attention on one device: a matrix state that is CORRECTED.

The eighth published model of ``models/transformer.py`` (Solar-Open2;
reference ``benchmark/reference/solar2_decoder.py``) has layers whose
token mixer is a linear attention under the gated delta rule (Kimi
Linear, arXiv:2510.26692). Per head of `heads` (keys and values both
`head_dim` = K wide) the layer carries a state ``S`` in ``R^{K x K}``
along the sequence, from zero::

    S'  = Diag(exp(g_t)) S_{t-1}                 g_t <= 0, one a CHANNEL
    S_t = S' + beta_t k_t (v_t - k_t^T S')^T     beta_t in (0, 2)
    o_t = S_t^T q_t

— the state decays per channel of the key, then is corrected towards
``v_t`` along ``k_t`` (a Householder-like factor ``I - beta k k^T``
whose eigenvalue along k is negative where beta > 1). Around it
(:func:`mixer`): q, k, v each a product of the normed input, a causal
depthwise convolution over time and a SiLU (``ops/ssm.causal_conv``
without a bias: on the TPU the kernels of ``ops/causal_conv.py``, one
pass over a run of heads' ``[B, T, h K]`` forward and one backward,
time in the sublanes as the products wrote it and the core's kernels
read it; off it K shifted float32 sums, by the rule
``ssm.conv_tile``), q and k then divided by their norm over a head; the
log-decay ``g = -exp(A_log) softplus((x W_fa) W_fb + dt_bias)`` and
``beta = 2 sigmoid(x W_b)``; after it an RMSNorm over each head with
one gain for all heads, the gate ``sigmoid((x W_ga) W_gb)`` and the
product back to the model's width.

:func:`chunked_delta` computes the recurrence in chunks of C tokens in
the WY form. With ``G_r`` the cumulative sum of g inside a chunk::

    A[r, i] = beta_r sum_c k_r[c] k_i[c] exp(G_r[c] - G_i[c])     i < r
    (I + A) [W | U] = [beta (k * exp(G)) | beta v]
    V' = U - W S                                  S: the state ENTERING
    o_r = (q_r * exp(G_r)) S + sum_{i <= r} P[r, i] V'_i
    P[r, i] = sum_c q_r[c] k_i[c] exp(G_r[c] - G_i[c])
    S <- Diag(exp(G_C)) S + sum_i (k_i * exp(G_C - G_i)) V'_i^T

Every exponent is a DIFFERENCE of cumulative sums that is <= 0, masked
to the causal half BEFORE the exponential (``ops/ssm.py``'s rule: never
a quotient of two exponentials, never ``exp`` of a positive number).
Because the decay is per channel, the pair sums ``A`` and ``P`` are no
product of two decayed operands in general; a chunk is cut into
sub-blocks of `SUB` rows: a pair of rows of ONE sub-block is summed
elementwise over the channels (``[SUB, SUB, K]``, fused by XLA), a pair
of two sub-blocks is a product of operands both referred to the LATER
sub-block's first row (``exp(G_r - G_first) <= 1`` and ``exp(G_first -
G_i) <= 1``). ``(I + A)^{-1}`` is made by forward substitution inside a
sub-block and by the block formula between them, in float32. g, its
sums, the exponentials, the system and the state are float32; the
other products take operands in the activations' type with float32
accumulation. Nothing of ``[T, T]`` and no state per token exists.

**The chunk-to-chunk carry is a true recurrence** — the state entering
a chunk is multiplied by a ``[K, K]`` matrix, ``Diag(exp(G_C)) - Kd^T
W`` — so unlike Mamba-2's (``ops/ssm.py``) it is no single product. The
core runs in one of two forms chosen by the static rule
:func:`carry_tile` from the backend and the shapes. **On the TPU** two
Pallas kernels behind one ``custom_vjp`` (:func:`kernel_delta`):
``kda_delta_fwd``, whose grid walks the chunks of a few heads with S in
VMEM and makes in each step a chunk's cumulative sums, pair sums,
system, W, U, the carry and the output, and the reverse-grid
``kda_delta_bwd``, which makes them again from the kernel's own
operands and the kept entering states and writes all five cotangents.
They read q, k, v, g as ``[B, T, H K]``, where the products and the
convolutions wrote them, norm q and k themselves, and keep every
intermediate in VMEM: the step has no ``while`` (a ``while`` event of a
device trace carries no op path) and no float32 copy of q, k or v in
HBM. **Everywhere else** (the CPU, a rehearsal) and as the kernels'
oracle, :func:`chunked_delta`: batched ``jax.numpy`` around a
``lax.scan`` over the chunks (:func:`scan_carry`), its gradient
autodiff's.

What a recomputed layer may keep (``checkpoint_name``, chosen by
``models/transformer.py``'s rule): :data:`KDA_PROJ` — the three wide
products' results — and :data:`KDA_OUT` — the normed, gated output in
front of the last product; what lies between them is recomputed a run
of heads at a time whatever is kept (:data:`HEADS_A_RUN`), except the
kernels' output and entering states (:data:`KDA_CORE`), which a run
keeps from the layer's recomputation: its own runs the convolutions
and the decays again, not the core.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ompi_tpu.core import pvar
from ompi_tpu.ops.grouped_matmul import VMEM_LIMIT_BYTES
from ompi_tpu.ops.ssm import LANES, causal_conv

KDA_PROJ = "kda_proj"
KDA_OUT = "kda_out"
#: what a run of heads keeps of its own recomputation: the core's
#: kernel's output and the states entering the chunks
KDA_CORE = "kda_core"

F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))  # A B^T
_TN = (((0,), (0,)), ((), ()))  # A^T B

#: rows of a sub-block of a chunk: pairs inside one are summed
#: elementwise over the channels, pairs of two are products
SUB = 16
#: the heads a grid step of the core's kernels takes (what divides the
#: heads: a step's fixed cost is shared, and the heads' chains of small
#: products interleave; scripts/kda_core_probe.py reads 1 / 2 / 4 / 8
#: on the chip)
_HEADS_A_STEP = (4, 2, 1)


def l2norm(x, eps: float):
    """x over the root of its summed squares along the last axis (+
    eps), float32."""
    x = x.astype(F32)
    return x * lax.rsqrt((x * x).sum(-1, keepdims=True) + eps)


# -- inside a chunk ------------------------------------------------------------

def _chunk_sums(g):
    """The cumulative sum of g [.., C, K] float32 inside its chunk,
    each token's own included: one product with the lower triangle of
    ones at the highest precision (the sum of at most C float32)."""
    c = g.shape[-2]
    return jnp.einsum("rs,...sk->...rk", jnp.tril(jnp.ones((c, c), F32)), g,
                      precision=_HIGHEST)


@jax.checkpoint
def _same_block_pairs(q, k, cum):
    """Of rows r >= i of ONE sub-block, [.., S, K] float32 each: (sum_c
    q_r[c] k_i[c] e, sum_c k_r[c] k_i[c] e) with e = exp(cum_r[c] -
    cum_i[c]), [.., S, S]; zero where i > r. The [S, S, K] terms are
    made again in the backward pass, not kept."""
    s = k.shape[-2]
    low = jnp.tril(jnp.ones((s, s), bool))
    e = jnp.exp(jnp.where(low[..., None],
                          cum[..., :, None, :] - cum[..., None, :, :],
                          -jnp.inf))
    ke = k[..., None, :, :] * e
    return ((q[..., :, None, :] * ke).sum(-1),
            (k[..., :, None, :] * ke).sum(-1))


def decayed_pairs(q, k, cum, sub: int = SUB):
    """(P, KK) [.., C, C] float32 of a chunk's q, k [.., C, K] and the
    cumulative log-decay `cum` [.., C, K] float32: ``P[r, i] = sum_c
    q_r[c] k_i[c] exp(cum_r[c] - cum_i[c])`` and KK the same with k_r
    for q_r, where i <= r; zero elsewhere."""
    *lead, c, width = k.shape
    sub = min(sub, c)
    if c % sub:
        raise ValueError(f"a chunk of {c} tokens is no whole number of "
                         f"sub-blocks of {sub}")
    n, dtype = c // sub, k.dtype
    qf, kf = q.astype(F32), k.astype(F32)

    def blocks(a):
        return a.reshape(*lead, n, sub, width)

    q6, k6, g6 = blocks(qf), blocks(kf), blocks(cum)
    same_q, same_k = _same_block_pairs(q6, k6, g6)
    rows_q, rows_k = [], []
    for a in range(n):
        parts_q, parts_k = [], []
        if a:  # against the earlier sub-blocks, by this one's first row
            first = g6[..., a, :1, :]
            rise = jnp.exp(g6[..., a, :, :] - first)
            fall = jnp.exp(first - cum[..., :a * sub, :])
            both = jnp.concatenate([q6[..., a, :, :] * rise,
                                    k6[..., a, :, :] * rise], axis=-2)
            off = jnp.einsum("...rk,...ik->...ri", both.astype(dtype),
                             (kf[..., :a * sub, :] * fall).astype(dtype),
                             preferred_element_type=F32)
            parts_q.append(off[..., :sub, :])
            parts_k.append(off[..., sub:, :])
        parts_q.append(same_q[..., a, :, :])
        parts_k.append(same_k[..., a, :, :])
        if a < n - 1:
            later = jnp.zeros((*lead, sub, c - (a + 1) * sub), F32)
            parts_q.append(later)
            parts_k.append(later)
        rows_q.append(jnp.concatenate(parts_q, axis=-1))
        rows_k.append(jnp.concatenate(parts_k, axis=-1))
    return (jnp.concatenate(rows_q, axis=-2),
            jnp.concatenate(rows_k, axis=-2))


def _contract(x, y):
    """``x @ y`` of matrices [n, m, N] and [m, p, N] with the batch
    LAST (the lanes): elementwise products summed over m, exact in
    float32 — the matrices are 16 or 32 rows, which the MXU's tiles
    would pad eightfold and round to its operand type."""
    return (x[:, :, None, :] * y[None, :, :, :]).sum(1)


def unit_lower_inverse(a, sub: int = SUB):
    """``(I + a)^{-1}`` of a strictly lower triangular a [.., C, C]
    float32: forward substitution row by row inside each diagonal
    sub-block of `sub` rows (unrolled: `sub` - 1 small steps), then
    pairs of blocks merged, ``[[X, 0], [-Y a21 X, Y]]``, until one is
    left (C / sub a power of two). Worked with the batch as the LAST
    axis, [rows, columns, batch]: a [16, 16] matrix in the two minor
    dimensions would be padded to the tiles' 128 lanes."""
    *lead, c, _ = a.shape
    sub = min(sub, c)
    n = c // sub
    if n & (n - 1):
        raise ValueError(f"{n} sub-blocks of {sub} rows in a chunk of {c}: "
                         "expected a power of two")
    a = jnp.moveaxis(a.reshape(-1, c, c), 0, -1)             # [C, C, N]

    def block(rows: int, cols: int, size: int):
        return a[rows * size:(rows + 1) * size,
                 cols * size:(cols + 1) * size]

    parts = []
    for j in range(n):
        low = block(j, j, sub)
        inv = jnp.broadcast_to(jnp.eye(sub, dtype=F32)[..., None], low.shape)
        for r in range(1, sub):  # row r from the rows before it
            inv = inv.at[r].add(-(low[r, :r, None, :] * inv[:r]).sum(0))
        parts.append(inv)
    size = sub
    while len(parts) > 1:
        merged = []
        for p in range(0, len(parts), 2):
            x, y = parts[p], parts[p + 1]
            below = -_contract(_contract(y, block(p + 1, p, size)), x)
            merged.append(jnp.concatenate([
                jnp.concatenate([x, jnp.zeros_like(x)], axis=1),
                jnp.concatenate([below, y], axis=1)], axis=0))
        parts, size = merged, 2 * size
    return jnp.moveaxis(parts[0], -1, 0).reshape(*lead, c, c)


# -- from chunk to chunk -------------------------------------------------------

def scan_carry(w, u, kd, grown):
    """The carry as a ``lax.scan`` over the chunks. w, kd [B, H, chunks,
    C, K], u [B, H, chunks, C, V] in the activations' type; grown =
    ``exp(G_C)`` [B, H, chunks, K] float32 -> (V' = U - W S [B, H,
    chunks, C, V] and the state ENTERING each chunk [B, H, chunks, K,
    V] — both in the activations' type — and the state after the last
    chunk [B, H, K, V] float32, the two states TRANSPOSED, [.., V, K]:
    the decay then scales a state's columns, a row along the lanes).
    The state is float32; a product reads it rounded to the operands'
    type."""
    b, h, _, _, width = w.shape
    dtype = u.dtype

    def chunk(s, now):
        w_c, u_c, kd_c, grown_c = now
        s_b = s.astype(dtype)
        v = (u_c.astype(F32) - jnp.einsum(
            "bhck,bhvk->bhcv", w_c, s_b,
            preferred_element_type=F32)).astype(dtype)
        s = grown_c[..., None, :] * s + jnp.einsum(
            "bhcv,bhck->bhvk", v, kd_c, preferred_element_type=F32)
        return s, (v, s_b)

    last, (vp, entering) = lax.scan(
        chunk, jnp.zeros((b, h, u.shape[-1], width), F32),
        tuple(jnp.moveaxis(a, 2, 0) for a in (w, u, kd, grown)))
    return jnp.moveaxis(vp, 0, 2), jnp.moveaxis(entering, 0, 2), last


def carry_tile(backend: str, t: int, heads: int, head_dim: int, chunk: int,
               dtype):
    """The rule that sends the CORE to the Pallas kernels, made of what
    the call can observe: the heads a grid step takes, or None — off
    the TPU, a sequence the chunk does not divide, a head the lanes do
    not divide, a chunk that is no whole number of the type's sublane
    tiles or of sub-blocks that pair up (the kernels' system merges
    them two by two)."""
    size = jnp.dtype(dtype).itemsize
    blocks = max(chunk // SUB, 1)
    if (backend != "tpu" or t % chunk or head_dim % LANES
            or chunk % (32 // size) or chunk % min(SUB, chunk)
            or blocks & (blocks - 1)):
        return None
    return next(n for n in _HEADS_A_STEP if heads % n == 0)


# -- a chunk in VMEM -----------------------------------------------------------
#
# What follows works on VALUES inside a kernel: one head's chunk, [C, K]
# with the channels along the lanes, [C, C] matrices with a row's pairs
# along the lanes. It is `chunked_delta`'s arithmetic term for term; only
# the order of the steps is the kernel's own (the system's substitution
# rides the loop that makes the pairs' columns).

def _of_each(x, sub: int, of):
    """`of` (a sub-block [sub, N] -> [1, N]) of every sub-block of x
    [C, N], over that sub-block's rows."""
    return jnp.concatenate(
        [jnp.broadcast_to(of(x[a:a + sub]), (sub,) + x.shape[1:])
         for a in range(0, x.shape[0], sub)], axis=0)


def _row_of_each(x, i: int, sub: int):
    """Row `i` of every sub-block of x, over that sub-block's rows."""
    return _of_each(x, sub, lambda block: block[i:i + 1])


def _sum_of_each(x, sub: int):
    """Every sub-block's rows summed, over that sub-block's rows."""
    return _of_each(x, sub, lambda block: block.sum(0, keepdims=True))


def _high(x, y, dims=(((1,), (0,)), ((), ()))):
    """A float32 product at the highest precision (the system's)."""
    return lax.dot_general(x, y, dims, precision=_HIGHEST,
                           preferred_element_type=F32)


def _mxu(x, y, dims=(((1,), (0,)), ((), ()))):
    """A product of operands in the activations' type, float32 sums."""
    return lax.dot_general(x, y, dims, preferred_element_type=F32)


class _Grid:
    """The index arrays of a chunk of `c` rows in sub-blocks of `sub`."""

    def __init__(self, c: int):
        self.c, self.sub = c, min(SUB, c)
        self.row = lax.broadcasted_iota(jnp.int32, (c, c), 0)
        self.col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
        #: a column's place in the ROW's own sub-block (outside 0 ..
        #: sub - 1: another sub-block's column)
        self.place = self.col - (self.row & -self.sub)
        #: a row's place in its sub-block, [C, 1]
        self.inner = lax.broadcasted_iota(jnp.int32, (c, 1), 0) \
            & (self.sub - 1)

    def earlier(self, a: int, cum, kf, dtype):
        """Sub-block `a`'s operands against the sub-blocks before it,
        referred to its first row: (rise [sub, K], fall [a sub, K], the
        earlier rows' decayed k over the whole chunk's rows [C, K] in
        `dtype`, zero from this sub-block on)."""
        lo = a * self.sub
        first = cum[lo:lo + 1]
        rise = jnp.exp(cum[lo:lo + self.sub] - first)
        fall = jnp.exp(first - cum[:lo])
        early = jnp.concatenate(
            [(kf[:lo] * fall).astype(dtype),
             jnp.zeros((self.c - lo, kf.shape[1]), dtype)], axis=0)
        return rise, fall, early


def _same_block_column(grid: _Grid, i: int, cum, kf):
    """(e, k_i e) [C, K] of column `i` of every diagonal sub-block: e =
    exp(cum_r - cum_i) on the rows r >= i of i's sub-block, 0 above."""
    e = jnp.exp(jnp.where(grid.inner >= i,
                          cum - _row_of_each(cum, i, grid.sub), -jnp.inf))
    return e, _row_of_each(kf, i, grid.sub) * e


def _within(q, k, v, g, beta):
    """One head's chunk: q, k [C, K], v [C, V] in the activations'
    type, g [C, K] and beta [C, 1] float32 -> a dict of `chunked_delta`'s
    values of that chunk: cum, decay = exp(cum), fade = exp(cum_C -
    cum), grown = exp(cum_C) [1, K], pairs, kk, solve = (I + beta
    KK)^-1 [C, C], w, u float32 (before their rounding)."""
    c, dtype = k.shape[0], v.dtype
    grid = _Grid(c)
    sub, row, col = grid.sub, grid.row, grid.col
    cum = _high((col <= row).astype(F32), g)
    qf, kf = q.astype(F32), k.astype(F32)
    pairs = kk = jnp.zeros((c, c), F32)
    solve = (row == col).astype(F32)
    for i in range(sub):  # a column of every diagonal sub-block at once
        _, ke = _same_block_column(grid, i, cum, kf)
        p_i = (qf * ke).sum(-1, keepdims=True)
        k_i = (kf * ke).sum(-1, keepdims=True)
        pairs = jnp.where(grid.place == i, p_i, pairs)
        kk = jnp.where(grid.place == i, k_i, kk)
        if i < sub - 1:  # forward substitution: the rows below row i
            solve = solve - jnp.where(grid.inner > i, beta * k_i, 0.0) \
                * _row_of_each(solve, i, sub)
    rows_p, rows_k = [jnp.zeros((sub, c), F32)], [jnp.zeros((sub, c), F32)]
    for a in range(1, c // sub):
        lo = a * sub
        rise, _, early = grid.earlier(a, cum, kf, dtype)
        both = jnp.concatenate([qf[lo:lo + sub] * rise,
                                kf[lo:lo + sub] * rise], axis=0)
        off = _mxu(both.astype(dtype), early, _NT)           # [2 sub, C]
        rows_p.append(off[:sub])
        rows_k.append(off[sub:])
    if len(rows_p) > 1:
        pairs = pairs + jnp.concatenate(rows_p, axis=0)
        kk = kk + jnp.concatenate(rows_k, axis=0)
    system = jnp.where(col < row, beta * kk, 0.0)
    size = sub
    while size < c:  # [[X, 0], [-Y a21 X, Y]] of every pair of blocks
        below = ((row & size) != 0) & ((col & -size) == (row & -size) - size)
        solve = solve - _high(_high(solve, jnp.where(below, system, 0.0)),
                              solve)
        size *= 2
    decay = jnp.exp(cum)
    total = cum[c - 1:c]
    return dict(cum=cum, decay=decay, fade=jnp.exp(total - cum),
                grown=jnp.exp(total), pairs=pairs, kk=kk, solve=solve,
                w=_high(solve, beta * kf * decay),
                u=_high(solve, beta * v.astype(F32)))


def _read(m, q, k, s_b):
    """What the carry's and the read-out's products read, in the
    activations' type, of `_within`'s values m, the chunk's q, k [C, K]
    and the state ENTERING it s_b [V, K] (TRANSPOSED: the decay scales
    its columns): (W, V' = U - W S, Qd = q e^G, Kd, P)."""
    dtype = q.dtype
    w = m["w"].astype(dtype)
    vp = (m["u"].astype(dtype).astype(F32) - _mxu(w, s_b, _NT)).astype(dtype)
    return (w, vp, (q.astype(F32) * m["decay"]).astype(dtype),
            (k.astype(F32) * m["fade"]).astype(dtype),
            m["pairs"].astype(dtype))


def _carry(m, q, k, s):
    """A chunk's carry and read-out, s [V, K] the entering state in
    float32 -> (s as the products read it, o [C, V] float32, the state
    after the chunk)."""
    s_b = s.astype(q.dtype)
    _, vp, qd, kd, pairs = _read(m, q, k, s_b)
    return (s_b, _mxu(qd, s_b, _NT) + _mxu(pairs, vp),
            m["grown"] * s + _mxu(vp, kd, _TN))


def _unit(x, l2, scaled: bool):
    """(x [C, K] as the core reads it, 1 / its rows' norms or None):
    under `l2` = (eps, q's scale) x is divided by its norm over the
    head as :func:`l2norm` does, q then scaled, and rounded to x's
    type; under None x is the core's operand as it is."""
    if l2 is None:
        return x, None
    eps, scale = l2
    xf = x.astype(F32)
    r = lax.rsqrt((xf * xf).sum(-1, keepdims=True) + eps)
    return (xf * r * (scale if scaled else 1.0)).astype(x.dtype), r


def _unit_back(x, r, d, l2, scaled: bool):
    """`_unit` transposed: the cotangent of x of the cotangent d of
    the normed rows (float32)."""
    if l2 is None:
        return d
    n = x.astype(F32) * r
    return (l2[1] if scaled else 1.0) * r * (
        d - n * (n * d).sum(-1, keepdims=True))


def _delta_fwd_kernel(q, k, v, g, beta, o, *rest, per: int, keep: bool, l2):
    """One chunk of `per` heads: the chunk-local values, then the carry
    and the output, the state in `s_s`. `keep`: the entering states are
    written (the backward kernel's residual). `l2`: q and k come as the
    convolutions left them and are normed here (`_unit`)."""
    entering, last, s_s = rest if keep else (None,) + rest
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        s_s[...] = jnp.zeros_like(s_s)

    width, wide = k.shape[1] // per, v.shape[1] // per
    for h in range(per):
        ks, vs = slice(h * width, (h + 1) * width), \
            slice(h * wide, (h + 1) * wide)
        q_h, k_h = _unit(q[:, ks], l2, True)[0], _unit(k[:, ks], l2, False)[0]
        m = _within(q_h, k_h, v[:, vs], g[:, ks], beta[:, h:h + 1])
        s_b, o_h, s_s[h] = _carry(m, q_h, k_h, s_s[h])
        if keep:
            entering[h] = s_b
        o[:, vs] = o_h.astype(o.dtype)

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        last[...] = s_s[...]


def _carry_back(m, q, k, s_b, ds, do):
    """`_carry` transposed: of the entering state as the products read
    it, the cotangent of the state AFTER the chunk ds [V, K] float32
    and of the output do [C, V] -> the cotangents of V' (= of U), W,
    Kd, Qd [C, .] and the pairs [C, C], float32, of exp(G_C) [1, K] and
    of the entering state."""
    c = q.shape[0]
    low = lax.broadcasted_iota(jnp.int32, (c, c), 1) \
        <= lax.broadcasted_iota(jnp.int32, (c, c), 0)
    w, vp, qd, kd, pairs = _read(m, q, k, s_b)
    ds_b = ds.astype(q.dtype)
    dvp = _mxu(pairs, do, _TN) + _mxu(kd, ds_b, _NT)
    dvp_b = dvp.astype(q.dtype)
    return dict(
        vp=dvp, w=-_mxu(dvp_b, s_b), kd=_mxu(vp, ds_b), qd=_mxu(do, s_b),
        pairs=jnp.where(low, _mxu(do, vp, _NT), 0.0),
        grown=(ds * s_b.astype(F32)).sum(0, keepdims=True),
        s=m["grown"] * ds - _mxu(dvp_b, w, _TN) + _mxu(do, qd, _TN))


def _delta_bwd_kernel(q, k, v, g, beta, entering, do, dlast,
                      dq, dk, dv, dg, dbeta, ds_s, *, per: int, l2):
    """The grid walked from the last chunk to the first, the cotangent
    of the state AFTER the chunk carried in `ds_s`; a chunk's values
    are made again from the kernel's own inputs."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_s[...] = dlast[...]

    dtype = v.dtype
    c = k.shape[0]
    width, wide = k.shape[1] // per, v.shape[1] // per
    grid = _Grid(c)
    sub, row, col = grid.sub, grid.row, grid.col
    for h in range(per):
        ks, vs = slice(h * width, (h + 1) * width), \
            slice(h * wide, (h + 1) * wide)
        v_h, b_h = v[:, vs], beta[:, h:h + 1]
        (q_h, r_q), (k_h, r_k) = _unit(q[:, ks], l2, True), \
            _unit(k[:, ks], l2, False)
        m = _within(q_h, k_h, v_h, g[:, ks], b_h)
        qf, kf, vf = q_h.astype(F32), k_h.astype(F32), v_h.astype(F32)
        cum, decay, fade, solve = m["cum"], m["decay"], m["fade"], m["solve"]
        kdf = kf * fade
        d = _carry_back(m, q_h, k_h, entering[h], ds_s[h], do[:, vs])
        ds_s[h] = d["s"]
        dvp, dw, dkd, dqd, dpairs = d["vp"], d["w"], d["kd"], d["qd"], \
            d["pairs"]
        # -- [W | U] = solve rhs, solve = (I + beta KK)^-1
        drk, drv = _high(solve, dw, _TN), _high(solve, dvp, _TN)
        dsystem = jnp.where(col < row, -(_high(drk, m["w"], _NT)
                                         + _high(drv, m["u"], _NT)), 0.0)
        dkk = b_h * dsystem
        d_beta = (dsystem * m["kk"]).sum(-1, keepdims=True) \
            + (drk * kf * decay).sum(-1, keepdims=True) \
            + (drv * vf).sum(-1, keepdims=True)
        dv[:, vs] = (b_h * drv).astype(dtype)
        d_k = b_h * decay * drk + dkd * fade
        lost = dkd * kdf
        d_cum = (b_h * kf * drk + dqd * qf) * decay - lost
        d_q = dqd * decay
        dtotal = lost.sum(0, keepdims=True) + d["grown"] * m["grown"]
        # -- the pairs of one sub-block, column by column
        at_i = jnp.zeros_like(kf)
        for i in range(sub):
            e, ke = _same_block_column(grid, i, cum, kf)
            dp_i = jnp.where(grid.place == i, dpairs, 0.0).sum(
                -1, keepdims=True)
            dk_i = jnp.where(grid.place == i, dkk, 0.0).sum(
                -1, keepdims=True)
            d_q = d_q + dp_i * ke
            d_k = d_k + dk_i * ke
            te = (dp_i * qf + dk_i * kf) * e
            d_cum = d_cum + te * _row_of_each(kf, i, sub)
            at_i = at_i + jnp.where(grid.inner == i, _sum_of_each(te, sub),
                                    0.0)
        d_k = d_k + at_i
        d_cum = d_cum - at_i * kf
        # -- the pairs of two sub-blocks
        n = c // sub
        zero = jnp.zeros((sub, width), F32)
        q_rows, k_rows, cum_rows = [zero] * n, [zero] * n, [zero] * n
        head = lax.broadcasted_iota(jnp.int32, (sub, 1), 0) == 0
        for a in range(1, n):
            lo = a * sub
            rise, fall, early = grid.earlier(a, cum, kf, dtype)
            q_a, k_a = qf[lo:lo + sub], kf[lo:lo + sub]
            both = jnp.concatenate([q_a * rise, k_a * rise],
                                   axis=0).astype(dtype)
            doff = jnp.concatenate([dpairs[lo:lo + sub], dkk[lo:lo + sub]],
                                   axis=0).astype(dtype)    # [2 sub, C]
            dboth = _mxu(doff, early)                        # [2 sub, K]
            dearly = _mxu(doff, both, _TN)[:lo]              # [lo, K]
            risen = (dboth[:sub] * q_a + dboth[sub:] * k_a) * rise
            fallen = dearly * kf[:lo] * fall
            dfirst = fallen.sum(0, keepdims=True) \
                - risen.sum(0, keepdims=True)
            q_rows[a] = dboth[:sub] * rise
            k_rows[a] = k_rows[a] + dboth[sub:] * rise
            cum_rows[a] = cum_rows[a] + risen + jnp.where(head, dfirst, 0.0)
            for j in range(a):
                rows = slice(j * sub, (j + 1) * sub)
                k_rows[j] = k_rows[j] + dearly[rows] * fall[rows]
                cum_rows[j] = cum_rows[j] - fallen[rows]
        if n > 1:
            d_q = d_q + jnp.concatenate(q_rows, axis=0)
            d_k = d_k + jnp.concatenate(k_rows, axis=0)
            d_cum = d_cum + jnp.concatenate(cum_rows, axis=0)
        d_cum = d_cum + jnp.where(
            lax.broadcasted_iota(jnp.int32, (c, 1), 0) == c - 1, dtotal, 0.0)
        dq[:, ks] = _unit_back(q[:, ks], r_q, d_q, l2, True).astype(dtype)
        dk[:, ks] = _unit_back(k[:, ks], r_k, d_k, l2, False).astype(dtype)
        dg[:, ks] = _high((col >= row).astype(F32), d_cum)
        dbeta[:, h:h + 1] = d_beta


def _delta_call(which: str, q, v, heads: int, chunk: int, per: int, l2,
                interpret: bool):
    """The ``pallas_call`` of one of the core's passes — "fwd", "kept"
    (the forward pass that also writes the entering states) or "bwd" —
    for q [B, T, H K] and v [B, T, H V], `per` heads a grid step. beta
    and its cotangent lie [B, H / per, T, per]: a block's last
    dimension is then the array's."""
    b, t, hk = q.shape
    nc, width, wide = t // chunk, hk // heads, v.shape[-1] // heads
    dtype = v.dtype
    back, keep = which == "bwd", which == "kept"

    def chunk_of(c):  # the backward pass walks the chunks from the last
        return nc - 1 - c if back else c

    def rows(n):  # a [B, T, H n] operand's block
        return pl.BlockSpec((None, chunk, per * n),
                            lambda b, h, c: (b, chunk_of(c), h))

    on = dict(
        k=rows(width), g=rows(width), v=rows(wide),
        beta=pl.BlockSpec((None, None, chunk, per),
                          lambda b, h, c: (b, h, chunk_of(c), 0)),
        state=pl.BlockSpec((None, per, None, wide, width),
                           lambda b, h, c: (b, h, chunk_of(c), 0, 0)),
        last=pl.BlockSpec((None, per, wide, width),
                          lambda b, h, c: (b, h, 0, 0)))
    like = dict(k=jax.ShapeDtypeStruct((b, t, hk), dtype),
                v=jax.ShapeDtypeStruct(v.shape, dtype),
                g=jax.ShapeDtypeStruct((b, t, hk), F32),
                beta=jax.ShapeDtypeStruct((b, heads // per, t, per), F32),
                state=jax.ShapeDtypeStruct((b, heads, nc, wide, width),
                                           dtype),
                last=jax.ShapeDtypeStruct((b, heads, wide, width), F32))
    tokens = b * t * heads
    products = tokens * core_flops_per_token(width, chunk)
    moved = tokens * ((2 * width + 2 * wide) * dtype.itemsize
                      + (width + 1) * 4)
    kept = b * heads * nc * wide * width * dtype.itemsize
    if back:
        kernel = functools.partial(_delta_bwd_kernel, per=per, l2=l2)
        ins = ("k", "k", "v", "g", "beta", "state", "v", "last")
        outs = ("k", "k", "v", "g", "beta")
        cost = pl.CostEstimate(
            flops=3 * products, transcendentals=tokens * (2 * SUB + 9) * width,
            bytes_accessed=2 * moved + kept)
    else:
        kernel = functools.partial(_delta_fwd_kernel, per=per, keep=keep,
                                   l2=l2)
        ins = ("k", "k", "v", "g", "beta")
        outs = ("v", "state", "last") if keep else ("v", "last")
        cost = pl.CostEstimate(
            flops=products, transcendentals=tokens * (SUB + 6) * width,
            bytes_accessed=moved + keep * kept)
    return pl.pallas_call(
        kernel, name="kda_delta_bwd" if back else "kda_delta_fwd",
        grid=(b, heads // per, nc),
        in_specs=[on[n] for n in ins], out_specs=tuple(on[n] for n in outs),
        out_shape=tuple(like[n] for n in outs),
        scratch_shapes=[pltpu.VMEM((per, wide, width), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=cost, interpret=interpret)


@functools.lru_cache(maxsize=None)
def _kernel_delta(heads: int, chunk: int, per: int, l2, interpret: bool):
    """The core on the kernels, behind a ``custom_vjp`` whose residuals
    are the operands themselves and the states entering the chunks;
    those and the output carry the name :data:`KDA_CORE`."""
    def grouped(beta):  # [B, T, H] -> [B, H / per, T, per]
        b, t, _ = beta.shape
        return jnp.moveaxis(beta.reshape(b, t, heads // per, per), 2, 1)

    def call(which, q, k, v, g, beta, *more):
        return _delta_call(which, q, v, heads, chunk, per, l2, interpret)(
            q, k, v, g, grouped(beta), *more)

    core = jax.custom_vjp(functools.partial(call, "fwd"))

    def fwd(q, k, v, g, beta):
        o, entering, last = call("kept", q, k, v, g, beta)
        o, entering = checkpoint_name((o, entering), KDA_CORE)
        return (o, last), (q, k, v, g, beta, entering)

    def bwd(res, cts):
        dq, dk, dv, dg, dbeta = call("bwd", *res, *cts)
        b, _, t, _ = dbeta.shape
        return dq, dk, dv, dg, jnp.moveaxis(dbeta, 1, 2).reshape(b, t, heads)

    core.defvjp(fwd, bwd)
    return core


def kernel_delta(q, k, v, g, beta, heads: int, chunk: int, per: int,
                 l2=None, interpret: bool = False):
    """:func:`chunked_delta` on the Pallas kernels, `per` heads a grid
    step (:func:`carry_tile`'s), of operands as the mixer's products
    and convolutions leave them: q, k, g [B, T, H K], v [B, T, H V],
    beta [B, T, H] -> (o [B, T, H V], the state after the last token
    TRANSPOSED, [B, H, V, K] float32). `l2` = (eps, q's scale): q and k
    are normed over each head inside the kernels (:func:`l2norm`, q
    then scaled), forward and backward."""
    return _kernel_delta(heads, chunk, per, l2, interpret)(
        q, k, v, g.astype(F32), beta.astype(F32))


# -- the core ------------------------------------------------------------------

def chunked_delta(q, k, v, g, beta, chunk: int, per=None):
    """The recurrence of the module docstring over whole sequences from
    a zero state. q, k [B, T, H, K] (q scaled), v [B, T, H, V] in the
    activations' type; g [B, T, H, K] float32, <= 0; beta [B, T, H]
    float32 -> (o [B, T, H, V] in v's type, the state after the last
    token [B, H, K, V] float32). T is a multiple of `chunk`. `per`:
    :func:`carry_tile`'s answer — None: ``jax.numpy`` around a
    ``lax.scan``, the kernels' oracle; else :func:`kernel_delta`."""
    b, t, h, width = k.shape
    if t % chunk:
        raise ValueError(f"a sequence of {t} tokens is no whole number of "
                         f"chunks of {chunk}")
    dtype = v.dtype
    if per is not None:
        o, last = kernel_delta(*(a.reshape(b, t, -1) for a in (q, k, v, g)),
                               beta, h, chunk, per)
        return o.reshape(b, t, h, -1), jnp.swapaxes(last, -1, -2)

    def chunks(a):  # [B, T, H, ..] -> [B, H, chunks, C, ..]
        a = jnp.moveaxis(a, 2, 1)
        return a.reshape(b, h, t // chunk, chunk, *a.shape[3:])

    q, k, v, g, beta = (chunks(a) for a in (q, k, v, g, beta))
    cum = _chunk_sums(g.astype(F32))
    total = cum[..., -1:, :]                                 # [B,H,nc,1,K]
    pairs, kk = decayed_pairs(q, k, cum)
    beta = beta.astype(F32)[..., None]
    strictly = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    solve = unit_lower_inverse(jnp.where(strictly, beta * kk, 0.0))
    kf = k.astype(F32)
    rhs = jnp.concatenate([beta * kf * jnp.exp(cum), beta * v.astype(F32)],
                          axis=-1)
    wu = jnp.matmul(solve, rhs, precision=_HIGHEST).astype(dtype)
    w, u = wu[..., :width], wu[..., width:]
    kd = (kf * jnp.exp(total - cum)).astype(dtype)
    grown = jnp.exp(total[..., 0, :])
    vp, entering, last = scan_carry(w, u, kd, grown)
    o = jnp.einsum("bhnck,bhnvk->bhncv",
                   (q.astype(F32) * jnp.exp(cum)).astype(dtype), entering,
                   preferred_element_type=F32) \
        + jnp.einsum("bhnri,bhniv->bhnrv", pairs.astype(dtype), vp,
                     preferred_element_type=F32)
    o = jnp.moveaxis(o.reshape(b, h, t, -1), 1, 2)
    return o.astype(dtype), jnp.swapaxes(last, -1, -2)


def core_flops_per_token(head_dim: int, chunk: int) -> int:
    """The operations of :func:`chunked_delta`'s products a token and
    head, forward, keys and values `head_dim` = K wide, in chunks of C:
    the pair sums P and KK over the causal half (2 x 2 K x (C + 1) / 2),
    the system applied to [W | U] (2 x 2 K x (C + 1) / 2), the carry's
    three products (V' = U - W S, the state's update, the entering
    state read out: 3 x 2 K K) and the pairs applied to V' (2 K x (C +
    1) / 2)."""
    half = (chunk + 1) // 2
    return 10 * head_dim * half + 6 * head_dim * head_dim


def _heads(small, q, k, v, f, gate, beta, head_dim: int, chunk: int,
           eps: float, l2_eps: float, per):
    """The mixer between its products, for a run of whole heads — each
    head's convolution, norms, decay, recurrence, output norm and gate
    read nothing of another's: q, k, v [B, T, h K] as the products made
    them, f, gate [B, T, h K] and beta [B, T, h] float32, `small` the
    run's rows of the mixer's small leaves -> (y [B, T, h K], the state
    after the last token [B, h, K, K])."""
    dt_ = v.dtype
    b, t, _ = v.shape

    def split(a):
        return a.reshape(b, t, -1, head_dim)

    with jax.named_scope("kda_conv"):
        q, k, v = (causal_conv(a, small[name])
                   for a, name in ((q, "conv_q"), (k, "conv_k"),
                                   (v, "conv_v")))
    with jax.named_scope("kda_core"):
        step = jax.nn.softplus(f + small["dt_bias"].astype(F32))
        rate = -jnp.exp(small["A_log"].astype(F32))
        if per is None:
            q = (l2norm(split(q), l2_eps) * head_dim ** -0.5).astype(dt_)
            k = l2norm(split(k), l2_eps).astype(dt_)
            o, last = chunked_delta(q, k, split(v), rate[:, None] * split(
                step), beta, chunk)
        else:  # the kernels read [B, T, h K] and norm q and k themselves:
            # a [.., h, K] view of a [.., h K] array is a relayout there
            o, last = kernel_delta(
                q, k, v, jnp.repeat(rate, head_dim) * step, beta,
                rate.shape[0], chunk, per, (l2_eps, head_dim ** -0.5))
            o, last = split(o), jnp.swapaxes(last, -1, -2)
    with jax.named_scope("kda_gate_norm"):
        o = o.astype(F32)
        o = o * lax.rsqrt((o * o).mean(-1, keepdims=True) + eps) \
            * small["o_norm"].astype(F32)
        return (o.reshape(b, t, -1) * jax.nn.sigmoid(gate)).astype(dt_), last


#: the heads `mixer` works at a time. Between the mixer's products a
#: head reads nothing of another's, and the float32 values that stretch
#: holds (decays, their sums and exponentials, the convolutions' and the
#: norms' inputs: [T, heads, K] each, a dozen of them alive at once in
#: the backward pass) are what a layer's memory peak is made of: a run
#: of heads is one recomputed function (``jax.checkpoint``, keeping
#: :data:`KDA_CORE` alone), so that the backward pass holds ONE run's
#: at a time
HEADS_A_RUN = 16


def mixer(lp, x, *, heads: int, head_dim: int, chunk: int, eps: float,
          l2_eps: float = 1e-6):
    """The Kimi-Delta-Attention mixer of the normed x [B, T, d] -> ([B,
    T, d] in x's type, the state after the last token [B, H, K, K]
    float32: a caller that drops it pays nothing for it). Leaves of
    `lp`: ``wq``, ``wk``, ``wv`` [d, H K]; ``conv_q``, ``conv_k``,
    ``conv_v`` [H K, taps]; ``w_fa`` [d, R], ``w_fb`` [R, H K],
    ``dt_bias`` [H K], ``A_log`` [H]; ``w_b`` [d, H]; ``o_norm`` {"g":
    [K]}; ``w_ga`` [d, R], ``w_gb`` [R, H K]; ``wo`` [H K, d]. Counted
    once per traced call: ``kda_carry_scan_layers``, or
    ``kda_carry_kernel_layers`` and ``kda_core_kernel_layers`` — the
    core's form by :func:`carry_tile` (the kernels hold the carry AND
    the chunk-local work)."""
    dt_ = x.dtype
    t = x.shape[1]

    def low_rank(first, second):  # float32: a gate's or a decay's
        return jnp.dot(x @ lp[first].astype(dt_), lp[second].astype(dt_),
                       preferred_element_type=F32)

    with jax.named_scope("kda_proj"):
        q, k, v = (checkpoint_name(x @ lp[name].astype(dt_), KDA_PROJ)
                   for name in ("wq", "wk", "wv"))
        f, gate = low_rank("w_fa", "w_fb"), low_rank("w_ga", "w_gb")
        beta = 2.0 * jax.nn.sigmoid(jnp.dot(x, lp["w_b"].astype(dt_),
                                            preferred_element_type=F32))
    run = next(n for n in range(min(HEADS_A_RUN, heads), 0, -1)
               if heads % n == 0)
    per = carry_tile(jax.default_backend(), t, run, head_dim, chunk, dt_)
    for name in (("kda_carry_scan_layers",) if per is None else
                 ("kda_carry_kernel_layers", "kda_core_kernel_layers")):
        pvar.record(name)
    heads_of = jax.checkpoint(
        functools.partial(_heads, head_dim=head_dim, chunk=chunk, eps=eps,
                          l2_eps=l2_eps, per=per),
        policy=jax.checkpoint_policies.save_only_these_names(KDA_CORE))
    ys, lasts = [], []
    for first in range(0, heads, run):
        hs = slice(first, first + run)
        cols = slice(first * head_dim, (first + run) * head_dim)
        small = {name: lp[name][cols] for name in (
            "conv_q", "conv_k", "conv_v", "dt_bias")}
        small.update(A_log=lp["A_log"][hs], o_norm=lp["o_norm"]["g"])
        wide = (q[..., cols], k[..., cols], v[..., cols], f[..., cols],
                gate[..., cols], beta[..., hs])
        if ys:  # one run AFTER the other, forward and backward: the
            # barrier hands this run its operands when the run before
            # has its result, and (transposed) that run its cotangent
            # when this one's backward pass is over — left to itself
            # the scheduler interleaves the runs and holds them all
            wide, ys[-1] = lax.optimization_barrier((wide, ys[-1]))
        y, last = heads_of(small, *wide)
        ys.append(y)
        lasts.append(last)
    with jax.named_scope("kda_proj"):
        y = checkpoint_name(jnp.concatenate(ys, axis=-1), KDA_OUT)
        return y @ lp["wo"].astype(dt_), jnp.concatenate(lasts, axis=1)
