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
without a bias), q and k then divided by their norm over a head; the
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
carry alone (``V' = U - W S``, the entering states, the update) runs in
one of two forms chosen by the static rule :func:`carry_tile` from the
backend and the shapes: **on the TPU** a Pallas kernel whose grid walks
the chunks with S in VMEM (:func:`kernel_carry`: ``kda_carry_fwd``,
and behind a ``custom_vjp`` that keeps its operands and results the
reverse-grid ``kda_carry_bwd``), so that the step has no ``while`` (a
``while`` event of a device trace carries no op path); **everywhere
else** (the CPU, a rehearsal) and as the kernels' oracle a ``lax.scan``
over the chunks (:func:`scan_carry`). Everything else is batched
``jax.numpy`` around it, its gradient autodiff's.

What a recomputed layer may keep (``checkpoint_name``, chosen by
``models/transformer.py``'s rule): :data:`KDA_PROJ` — the three wide
products' results — and :data:`KDA_OUT` — the normed, gated output in
front of the last product; what lies between them is recomputed a run
of heads at a time whatever is kept (:data:`HEADS_A_RUN`).
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

F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))  # A B^T
_TN = (((0,), (0,)), ((), ()))  # A^T B

#: rows of a sub-block of a chunk: pairs inside one are summed
#: elementwise over the channels, pairs of two are products
SUB = 16
#: the heads a grid step of the carry's kernels takes (what divides the
#: heads: a step's three products of [C, K] x [K, K] are short, and its
#: fixed cost is shared)
_HEADS_A_STEP = (8, 4, 2, 1)


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
    """The rule that sends the carry to the Pallas kernels, made of
    what the call can observe: the heads a grid step takes, or None —
    off the TPU, a sequence the chunk does not divide, a head the lanes
    do not divide, a chunk that is no whole number of the type's
    sublane tiles."""
    size = jnp.dtype(dtype).itemsize
    if (backend != "tpu" or t % chunk or head_dim % LANES
            or chunk % (32 // size)):
        return None
    return next(n for n in _HEADS_A_STEP if heads % n == 0)


def _fwd_kernel(w, u, kd, grown, vp, entering, last, s_s, *, per: int):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        s_s[...] = jnp.zeros_like(s_s)

    for h in range(per):  # the state lies transposed, [V, K]
        s = s_s[h]
        s_b = s.astype(vp.dtype)
        entering[h] = s_b
        v = (u[h].astype(F32) - lax.dot_general(
            w[h], s_b, _NT, preferred_element_type=F32)).astype(vp.dtype)
        vp[h] = v
        s_s[h] = grown[h] * s + lax.dot_general(
            v, kd[h], _TN, preferred_element_type=F32)

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        last[...] = s_s[...]


def _bwd_kernel(w, kd, grown, entering, vp, dvp, dentering, dlast,
                dw, du, dkd, dgrown, ds_s, *, per: int):
    """The grid walked from the last chunk to the first, the cotangent
    of the state AFTER the chunk carried in `ds_s`."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_s[...] = dlast[...]

    dtype = vp.dtype
    for h in range(per):
        ds = ds_s[h]                                         # [V, K]
        ds_b, s_b = ds.astype(dtype), entering[h]
        dv = dvp[h].astype(F32) + lax.dot_general(
            kd[h], ds_b, _NT, preferred_element_type=F32)
        dv_b = dv.astype(dtype)
        du[h] = dv_b
        dkd[h] = jnp.dot(vp[h], ds_b,
                         preferred_element_type=F32).astype(dtype)
        dw[h] = (-jnp.dot(dv_b, s_b,
                          preferred_element_type=F32)).astype(dtype)
        dgrown[h] = (ds * s_b.astype(F32)).sum(axis=0, keepdims=True)
        ds_s[h] = dentering[h].astype(F32) + grown[h] * ds \
            - lax.dot_general(dv_b, w[h], _TN, preferred_element_type=F32)


def _carry_calls(w, u, per: int, interpret: bool):
    """(the forward call, the backward call) of the carry's kernels for
    operands of these shapes, `per` heads a grid step."""
    b, h, nc, c, width = w.shape
    wide, dtype = u.shape[-1], u.dtype
    grid = (b, h // per, nc)

    def specs(chunk_of):
        def at(*tail):  # a [.., chunks, ...] operand's block
            return pl.BlockSpec(
                (None, per, None) + tail,
                lambda b, h, c: (b, h, chunk_of(c)) + (0,) * len(tail))
        return dict(k=at(c, width), v=at(c, wide), row=at(1, width),
                    state=at(wide, width),
                    last=pl.BlockSpec((None, per, wide, width),
                                      lambda b, h, c: (b, h, 0, 0)))

    def shape(*tail, dt=dtype):
        return jax.ShapeDtypeStruct((b, h, nc) + tail, dt)

    params = dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret)
    products = 2 * b * h * nc * c * width * wide
    moved = b * h * nc * (2 * c * width + 2 * c * wide + width * wide) \
        * dtype.itemsize
    on = specs(lambda c: c)
    forward = pl.pallas_call(
        functools.partial(_fwd_kernel, per=per), name="kda_carry_fwd",
        out_shape=(shape(c, wide), shape(wide, width),
                   jax.ShapeDtypeStruct((b, h, wide, width), F32)),
        grid=grid, in_specs=[on["k"], on["v"], on["k"], on["row"]],
        out_specs=(on["v"], on["state"], on["last"]),
        scratch_shapes=[pltpu.VMEM((per, wide, width), F32)],
        cost_estimate=pl.CostEstimate(flops=2 * products, transcendentals=0,
                                      bytes_accessed=moved),
        **params)
    back = specs(lambda c: nc - 1 - c)
    backward = pl.pallas_call(
        functools.partial(_bwd_kernel, per=per), name="kda_carry_bwd",
        out_shape=(shape(c, width), shape(c, wide), shape(c, width),
                   shape(1, width, dt=F32)),
        grid=grid,
        in_specs=[back["k"], back["k"], back["row"], back["state"],
                  back["v"], back["v"], back["state"], back["last"]],
        out_specs=(back["k"], back["v"], back["k"], back["row"]),
        scratch_shapes=[pltpu.VMEM((per, wide, width), F32)],
        cost_estimate=pl.CostEstimate(flops=4 * products, transcendentals=0,
                                      bytes_accessed=2 * moved),
        **params)
    return forward, backward


@functools.lru_cache(maxsize=None)
def _kernel_carry(per: int, interpret: bool):
    """The carry on the kernels as a function of `scan_carry`'s
    operands, behind a ``custom_vjp`` that keeps w, kd, grown and the
    forward's own results."""
    def run(w, u, kd, grown):
        return _carry_calls(w, u, per, interpret)[0](
            w, u, kd, grown[..., None, :])

    carry = jax.custom_vjp(run)

    def fwd(w, u, kd, grown):
        vp, entering, last = run(w, u, kd, grown)
        return (vp, entering, last), (w, kd, grown, entering, vp)

    def bwd(res, cts):
        w, kd, grown, entering, vp = res
        dw, du, dkd, dgrown = _carry_calls(w, vp, per, interpret)[1](
            w, kd, grown[..., None, :], entering, vp, *cts)
        return dw, du, dkd, dgrown[..., 0, :]

    carry.defvjp(fwd, bwd)
    return carry


def kernel_carry(w, u, kd, grown, per: int, interpret: bool = False):
    """:func:`scan_carry` on the Pallas kernels, `per` heads a grid
    step (:func:`carry_tile`'s)."""
    return _kernel_carry(per, interpret)(w, u, kd, grown)


# -- the core ------------------------------------------------------------------

def chunked_delta(q, k, v, g, beta, chunk: int, per=None):
    """The recurrence of the module docstring over whole sequences from
    a zero state. q, k [B, T, H, K] (q scaled), v [B, T, H, V] in the
    activations' type; g [B, T, H, K] float32, <= 0; beta [B, T, H]
    float32 -> (o [B, T, H, V] in v's type, the state after the last
    token [B, H, K, V] float32). T is a multiple of `chunk`. `per`:
    :func:`carry_tile`'s answer — the carry's form."""
    b, t, h, width = k.shape
    if t % chunk:
        raise ValueError(f"a sequence of {t} tokens is no whole number of "
                         f"chunks of {chunk}")
    dtype = v.dtype

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
    if per is None:
        vp, entering, last = scan_carry(w, u, kd, grown)
    else:
        vp, entering, last = kernel_carry(w, u, kd, grown, per)
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
        q = (l2norm(split(q), l2_eps) * head_dim ** -0.5).astype(dt_)
        k = l2norm(split(k), l2_eps).astype(dt_)
        step = jax.nn.softplus(f + small["dt_bias"].astype(F32))
        g = -jnp.exp(small["A_log"].astype(F32))[:, None] * split(step)
        o, last = chunked_delta(q, k, split(v), g, beta, chunk, per)
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
#: of heads is one recomputed function (``jax.checkpoint``), so that
#: the backward pass holds ONE run's at a time
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
    once per traced call: ``kda_carry_kernel_layers`` /
    ``kda_carry_scan_layers``, the carry's form by :func:`carry_tile`."""
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
    pvar.record("kda_carry_scan_layers" if per is None
                else "kda_carry_kernel_layers")
    heads_of = jax.checkpoint(functools.partial(
        _heads, head_dim=head_dim, chunk=chunk, eps=eps, l2_eps=l2_eps,
        per=per))
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
