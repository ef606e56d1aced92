"""A Mamba-2 state-space mixer on one device, with no loop in the step.

The sixth published model of ``models/transformer.py`` (Nemotron-H's
family; reference ``benchmark/reference/nemotron_decoder.py``) has
layers that are a selective state-space model and nothing else. Per
head h of `heads` (each `head_dim` = P wide, reading group ``h //
(heads / groups)`` of the `groups` pairs B, C of `state` = N numbers)
the layer carries a state ``S`` in ``R^{P x N}`` along the sequence::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T      y_t = S_t C_t + D x_t

with ``dt = softplus(dt_raw + dt_bias) > 0`` and ``A = -exp(A_log) <
0`` one scalar a head. Around it (:func:`mixer`): one product of the
normed input into ``[z | xBC | dt]``, a causal depthwise convolution
over time and a SiLU on ``xBC`` (:func:`causal_conv`), and after it
the gate ``y * silu(z)``, an RMSNorm over each group's channels on its
own (:func:`gated_group_norm`) and the product back to the model's
width.

:func:`causal_conv` — this mixer's and ``ops/kda.py``'s — runs in one
of two forms chosen by the static rule :func:`conv_tile`: **on the
TPU** two Pallas kernels behind one ``custom_vjp``
(``ops/causal_conv.py``: ``causal_conv_fwd`` reads a block and the few
entries in front of it and writes SiLU of the K-tap sum, one pass over
the bytes; ``causal_conv_bwd`` makes the pre-activation again from the
operand, which is all it keeps, and writes ``dx`` and the float32 sums
``dw``, ``db``), each caller's layout read and written as it lies —
here ``[B, C, T]``, the sequence in the lanes, what the scan's kernels
read; **everywhere else**, and as the kernels' oracle,
:func:`shifted_conv`: K shifted slices of a zero-padded float32 copy,
multiplied and summed, the backward pass autodiff's. Both compute in
float32 and round once.

:func:`chunked_scan` computes the recurrence in chunks of L tokens
(the "state-space duality" form of the Mamba-2 paper, arXiv:2405.21060
section 6) and no ``while``, in one of two forms chosen by the static
rule :func:`scan_tile` from the backend and the shapes alone:

**On the TPU, the kernels of** ``ops/ssm_scan.py`` (:func:`kernel_scan`):
a chunk's decays, scores and mixing matrix stay in VMEM, the state is
carried along the grid's chunk axis, x, B and C are read as they lie in
the convolved ``xBC`` — with the SEQUENCE in the lanes, which is how XLA
lays the mixer's arrays out by itself, so nothing around the kernels is
laid out anew — and the ``D x`` term is the kernel's epilogue. Their
gradient is a ``custom_vjp`` of ``(xBC, dt, cum, D)`` that keeps
its inputs and nothing else: a states-only sweep and one reverse kernel
(``A_log``, ``dt_bias`` and the softplus keep autodiff's gradient
through ``dt`` and ``cum``).

**Everywhere else** (the CPU, a rehearsal, a shape the kernels do not
take) **and as the kernels' oracle, the** ``jax.numpy`` **form**: FOUR
batched products and ONE between chunk ends, the backward pass
autodiff's of them —

1. inside a chunk, ``y[l] += sum_{s <= l} exp(a[s+1..l]) (C_l . B_s)
   dt_s x_s`` — the ``[L, L]`` causal matrix of decays times ``C B^T``,
   applied to ``dt x``;
2. each chunk's own state at its end, ``sum_s exp(a[s+1..L-1]) dt_s
   x_s B_s^T``;
3. the states carried from chunk to chunk: ONE product with the
   ``[chunks + 1, chunks]`` matrix of decays between chunk ends — what
   a ``lax.scan`` over the chunks would do one step at a time (and what
   a device trace could not see: a ``while`` event carries no op path;
   the kernels' grid is one custom call with the scope's path); its
   last row is the state after the last token;
4. the carried state read out through ``C`` with the decay from the
   chunk's start.

In BOTH forms ``a = dt A`` and its cumulative sums, and every
exponential, are float32; a decay's exponent is a DIFFERENCE of
cumulative sums, masked to the causal half BEFORE the exponential
(never a quotient of two exponentials, never ``exp`` of a positive
number). The products take operands in the activations' type with
float32 accumulation (the ``jax.numpy`` form's chunk-to-chunk product,
which the kernels' carry replaces, is float32 at the highest
precision). Nothing of ``[T, T]`` or of a state per TOKEN exists. In
the ``jax.numpy`` form the largest arrays are the decays ``[B, chunks,
H, L, L]`` and the states ``[B, chunks, H, P, N]`` in float32, in HBM;
the kernels' largest is the backward's entering states, the same ``[B,
chunks, H, P, N]``, written once and read once.

What a recomputed layer may keep (``jax.ad_checkpoint.checkpoint_name``,
chosen by ``models/transformer.py``'s rule): :data:`SSM_IN` — the first
product's result, the dearest thing the layer makes —, :data:`SSM_CONV`
— the convolved ``xBC`` — and :data:`SSM_Y` — the scan's output before
the gate.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ompi_tpu.core import pvar

SSM_IN = "ssm_in"
SSM_CONV = "ssm_conv"
SSM_Y = "ssm_y"

F32 = jnp.float32


#: the lanes of a VMEM tile
LANES = 128
#: a grid step's block of the convolution's kernels: the largest of
#: these entries along time, of these channels, that divide the
#: operand's (a block of 2048 x 512 bfloat16 is 2 MiB; with time in
#: the lanes the tile in front of a block is 128 entries of each of its
#: channels, a DMA of short rows: the longer block pays it less often)
_CONV_TIME = (2048, 1024, 512, 256, LANES)
_CONV_CHANNELS = (512, 256, LANES)
#: the entries in front of a block that a step of those kernels reads
#: beside it (ops/causal_conv.HALO): K - 1 may not pass them
_CONV_HALO = 8


def conv_tile(backend: str, t: int, channels: int, taps: int, dtype):
    """The rule that sends the convolution to the repo's own kernels
    (ops/causal_conv.py), made of what the call can observe: a grid
    step's block (entries along time, channels), or None — off the
    TPU, a sequence or a channel count the lanes do not divide (a
    block lies with time in the sublanes or in the lanes, as its
    caller's arrays do: either extent has to fill the lanes), K - 1
    over the entries a step reads in front of its block, a type that is
    neither 2 nor 4 bytes wide."""
    if (backend != "tpu" or t % LANES or channels % LANES
            or not 1 <= taps <= _CONV_HALO + 1
            or jnp.dtype(dtype).itemsize not in (2, 4)):
        return None
    return (next(n for n in _CONV_TIME if t % n == 0),
            next(n for n in _CONV_CHANNELS if channels % n == 0))


def causal_conv(xbc, w, b=None, *, time_last: bool = False, within=None):
    """``silu(b + sum_j w[:, j] * xbc[t - (K - 1) + j])``: a depthwise
    convolution over time with zeros before the sequence. xbc [B, T,
    C], w [C, K], b [C] (None: no bias) -> [B, T, C] in xbc's type —
    under `time_last` [B, C, T], the same values transposed, for a
    reader that wants the sequence in the lanes. Computed in float32
    and rounded once, in one of two forms chosen by :func:`conv_tile`:
    on the TPU the kernels of ``ops/causal_conv.py`` (one pass over the
    bytes forward, one backward, each caller's layout read and written
    as it lies); everywhere else, and as the kernels' ORACLE, the K
    shifted sums below with autodiff's backward pass. `within` = (a
    wider array [B, T, wide], the column of it that xbc starts at),
    where xbc is a slice: the kernels then read the columns where they
    lie (a block's index takes the offset; a slice in front of a kernel
    is a copy of its own) if their block divides the offset. Counted
    once per traced call: ``conv_kernel_layers`` /
    ``conv_shifted_layers``."""
    t, k = xbc.shape[1], w.shape[1]
    tile = conv_tile(jax.default_backend(), t, xbc.shape[2], k, xbc.dtype)
    pvar.record("conv_shifted_layers" if tile is None
                else "conv_kernel_layers")
    if tile is not None:
        from ompi_tpu.ops import causal_conv as kernels  # Pallas: as below

        if within is not None and within[1] % tile[1] == 0:
            return kernels.conv(within[0], w, b, tile, time_last,
                                first=within[1])
        return kernels.conv(xbc, w, b, tile, time_last)
    out = shifted_conv(xbc, w, b)
    return jnp.swapaxes(out, 1, 2) if time_last else out


def shifted_conv(xbc, w, b=None):
    """:func:`causal_conv` [B, T, C] -> [B, T, C] as K shifted sums of
    a zero-padded float32 copy: the ``jax.numpy`` form."""
    t, k = xbc.shape[1], w.shape[1]
    padded = jnp.pad(xbc.astype(F32), ((0, 0), (k - 1, 0), (0, 0)))
    w = w.astype(F32)
    taps = (padded[:, j:j + t] * w[:, j] for j in range(k))
    out = sum(taps) if b is None else b.astype(F32) + sum(taps)
    return jax.nn.silu(out).astype(xbc.dtype)


def gated_group_norm(y, z, g, groups: int, eps: float):
    """``RMSNorm_groups(y * silu(z)) * g``: the gate first, then the
    norm over each of `groups` equal runs of channels on its own, one
    gain over all channels. y, z [B, T, C] -> [B, T, C] in y's type."""
    gated = y.astype(F32) * jax.nn.silu(z.astype(F32))
    runs = gated.reshape(*gated.shape[:-1], groups, -1)
    runs = runs * lax.rsqrt((runs * runs).mean(-1, keepdims=True) + eps)
    return (runs.reshape(gated.shape) * g.astype(F32)).astype(y.dtype)


def chunked_scan(x, dt, a, bm, cm, chunk: int):
    """The recurrence of the module docstring over whole sequences
    from a zero state, without its ``D x`` term. x [B, T, H, P]; dt
    [B, T, H] float32, positive; a [H] float32, negative; bm, cm [B,
    T, G, N] -> (y [B, T, H, P] in x's type, the state after the last
    token [B, H, P, N] float32). T is a multiple of `chunk`."""
    b, t, h, p = x.shape
    g, n = bm.shape[2], bm.shape[3]
    if t % chunk:
        raise ValueError(f"a sequence of {t} tokens is no whole number of "
                         f"chunks of {chunk}")
    nc, per, dtype = t // chunk, h // g, x.dtype
    # heads by group, the chunk's tokens innermost but for the widths:
    # [B, chunks, G, heads a group, L, ...]
    x = x.reshape(b, nc, chunk, g, per, p).transpose(0, 1, 3, 4, 2, 5)
    dt = dt.astype(F32).reshape(b, nc, chunk, g, per).transpose(
        0, 1, 3, 4, 2)
    bm = bm.reshape(b, nc, chunk, g, n).transpose(0, 1, 3, 2, 4)
    cm = cm.reshape(b, nc, chunk, g, n).transpose(0, 1, 3, 2, 4)
    # cumulative log-decay inside each chunk, this token's included
    cum = jnp.cumsum(dt * a.astype(F32).reshape(g, per, 1), axis=-1)
    total = cum[..., -1]                                     # [B, nc, G, R]

    # 1. inside a chunk
    seg = cum[..., :, None] - cum[..., None, :]              # [B,nc,G,R,L,S]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    scores = jnp.einsum("bcgln,bcgsn->bcgls", cm, bm,
                        preferred_element_type=F32)
    mixed = (scores[:, :, :, None] * decay * dt[..., None, :]).astype(dtype)
    y = jnp.einsum("bcgrls,bcgrsp->bcgrlp", mixed, x,
                   preferred_element_type=F32)

    # 2. each chunk's own state at its end
    to_end = jnp.exp(total[..., None] - cum) * dt            # [B,nc,G,R,L]
    own = jnp.einsum("bcgrlp,bcgln->bcgrpn",
                     (x.astype(F32) * to_end[..., None]).astype(dtype), bm,
                     preferred_element_type=F32)

    # 3. carried from chunk to chunk: row c of `between` weighs chunk
    # j's own state in the state ENTERING chunk c (row nc: the last)
    ends = jnp.concatenate(
        [jnp.zeros_like(total[:, :1]), jnp.cumsum(total, axis=1)], axis=1)
    ends = ends.transpose(0, 2, 3, 1)                        # [B,G,R,nc+1]
    span = ends[..., :, None] - ends[..., None, 1:]          # [B,G,R,nc+1,nc]
    earlier = jnp.tril(jnp.ones((nc + 1, nc), bool), -1)
    between = jnp.exp(jnp.where(earlier, span, -jnp.inf))
    entering = jnp.einsum("bgrcj,bjgrpn->bcgrpn", between, own,
                          precision=lax.Precision.HIGHEST)

    # 4. the carried state read out through C
    y = y + jnp.einsum("bcgln,bcgrpn->bcgrlp", cm,
                       entering[:, :-1].astype(dtype),
                       preferred_element_type=F32) * jnp.exp(cum)[..., None]
    y = y.transpose(0, 1, 4, 2, 3, 5).reshape(b, t, h, p)
    return y.astype(dtype), entering[:, -1].reshape(b, h, p, n)


#: what a grid step's working set may take of VMEM (of the kernels'
#: limit of 96 MiB: the blocks twice, the carried states and a head's
#: [L, L] float32 temporaries; the cell's shapes take 4 MiB)
_SCAN_VMEM_BYTES = 48 * 1024 * 1024


def scan_tile(backend: str, t: int, heads: int, head_dim: int, groups: int,
              state: int, chunk: int, dtype):
    """The rule that sends the scan to the repo's own kernels
    (ops/ssm_scan.py), made of what the call can observe: their sizes
    (an ``ssm_scan.Dims``), or None — off the TPU, a sequence the chunk
    does not divide, a chunk or a state the lanes do not divide, a head
    that is no whole number of the type's sublane tiles (or B's first
    row not a whole number of states), a chunk's working set over the
    VMEM budget."""
    size = jnp.dtype(dtype).itemsize
    if (backend != "tpu" or t % chunk or heads % groups or chunk % LANES
            or state % LANES or head_dim % (32 // size)
            or heads * head_dim % state):
        return None
    width = heads // groups * head_dim
    blocks = 2 * chunk * (3 * width + 4 * state) * size
    carried = 5 * width * state * 4 + 2 * width * chunk * size
    decays = 10 * chunk * chunk * 4
    if blocks + carried + decays > _SCAN_VMEM_BYTES:
        return None
    from ompi_tpu.ops import ssm_scan  # Pallas: only where it will run

    return ssm_scan.Dims(heads, head_dim, groups, state, chunk)


@functools.lru_cache(maxsize=None)
def _scan_kernels(dims, interpret: bool):
    """The scan kernels for one set of sizes as a function (xbc [B, H P
    + 2 G N, T], dt, cum [B, T, H] float32, d [H] float32) -> (y [B, H
    P, T], last [B, H, P, N] float32): `ssm_scan.forward`, and behind a
    ``custom_vjp`` that keeps the four operands (the convolved ``xbc``
    under its name, :data:`SSM_CONV`) `ssm_scan.states` and
    `ssm_scan.backward`. The per-head vectors' two layouts are made and
    added up here."""
    from ompi_tpu.ops import ssm_scan as sk

    on = dict(dims=dims, interpret=interpret)
    g, per = dims.groups, dims.per

    def rows(v):     # [B, T, H] -> [B, G, heads a group, T]
        return jnp.swapaxes(v, 1, 2).reshape(v.shape[0], g, per, v.shape[1])

    def cols(v):     # [B, T, H] -> [B, G, T, heads a group]
        return v.reshape(*v.shape[:2], g, per).transpose(0, 2, 1, 3)

    def run(xbc, dt, cum, d):
        y, last = sk.forward(xbc, rows(dt), rows(cum), cols(dt), cols(cum),
                             d, **on)
        return y, last.reshape(xbc.shape[0], dims.heads, dims.head_dim,
                               dims.state)

    scan = jax.custom_vjp(run)

    def bwd(res, cts):
        xbc, dt, cum, d = res
        dy, dlast = cts
        b, t = dt.shape[:2]
        dt_r, cum_r = rows(dt), rows(cum)
        dx, dbm, dcm, ddt_r, dcum_r, ddt_c, dcum_c, dd = sk.backward(
            xbc, dy, dlast.reshape(b, dims.inner, dims.state),
            sk.states(xbc, dt_r, cum_r, **on), dt_r, cum_r, cols(dt),
            cols(cum), d, **on)

        def back(r, c):  # the two layouts' parts -> [B, T, H]
            return (jnp.swapaxes(r.reshape(b, dims.heads, t), 1, 2)
                    + c.transpose(0, 2, 1, 3).reshape(b, t, dims.heads))

        return (jnp.concatenate([dx, dbm, dcm], axis=1), back(ddt_r, ddt_c),
                back(dcum_r, dcum_c),
                dd.reshape(b, dims.heads, -1).sum((0, 2)))

    def fwd(xbc, *small):
        # The convolved xBC takes its name HERE, on the residual alone.
        # Named in front of the kernel it would be a kept value that the
        # forward pass reads too, and jax's remat rounds such a value
        # behind its producer (``reduce_precision``): behind a kernel
        # that is a pass of its own over the array (0.2 ms a layer).
        return run(xbc, *small), (checkpoint_name(xbc, SSM_CONV), *small)

    scan.defvjp(fwd, bwd)
    return scan


def kernel_scan(xbc, dt, a, d, dims, interpret: bool = False):
    """:func:`chunked_scan` WITH its ``D x`` term on the kernels, the
    wide operands with the sequence LAST: xbc [B, H P + 2 G N, T], the
    convolved ``[x | B | C]`` whole; dt [B, T, H] float32, positive; a,
    d [H] float32 -> (y [B, H P, T] in xbc's type, the state after the
    last token [B, H, P, N] float32). `dims`: :func:`scan_tile`'s. The
    cumulative log-decay inside each chunk is made here, by XLA."""
    b, t, h = dt.shape
    cum = jnp.cumsum((dt * a).reshape(b, t // dims.chunk, dims.chunk, h),
                     axis=2).reshape(b, t, h)
    return _scan_kernels(dims, interpret)(xbc, dt, cum, d)


def _scan_by_products(lp, xbc, dt, *, heads, head_dim, groups, state, chunk):
    """The mixer's scan in the ``jax.numpy`` form (PR 39's lines in PR
    39's order: a step that takes it lowers to the text it had)."""
    b, t, _ = xbc.shape
    inner, bc = heads * head_dim, groups * state
    with jax.named_scope("ssm_scan"):
        xs = xbc[..., :inner].reshape(b, t, heads, head_dim)
        bm = xbc[..., inner:inner + bc].reshape(b, t, groups, state)
        cm = xbc[..., inner + bc:].reshape(b, t, groups, state)
        dt = jax.nn.softplus(dt.astype(F32) + lp["dt_bias"].astype(F32))
        y, last = chunked_scan(xs, dt, -jnp.exp(lp["A_log"].astype(F32)),
                               bm, cm, chunk)
        y = y.astype(F32) + lp["D"].astype(F32)[:, None] * xs.astype(F32)
        return y.astype(xbc.dtype).reshape(b, t, inner), last


def _scan_by_kernels(lp, xbc, dt, dims):
    """The mixer's scan on the kernels; xbc [B, H P + 2 G N, T] and the
    result [B, H P, T]: the sequence last."""
    with jax.named_scope("ssm_scan"):
        dt = jax.nn.softplus(dt.astype(F32) + lp["dt_bias"].astype(F32))
        return kernel_scan(xbc, dt, -jnp.exp(lp["A_log"].astype(F32)),
                           lp["D"].astype(F32), dims)


def mixer(lp, x, *, heads: int, head_dim: int, groups: int, state: int,
          chunk: int, eps: float):
    """The Mamba-2 mixer of the normed x [B, T, d] -> ([B, T, d] in
    x's type, the scan's state after the last token [B, H, P, N]
    float32: a caller that drops it pays nothing for it). Leaves of
    `lp`: ``in_proj``
    [d, 2 * H * P + 2 * G * N + H] (columns ``[z | x B C | dt]``),
    ``conv_w`` [H * P + 2 * G * N, K], ``conv_b``, ``A_log``, ``D``,
    ``dt_bias`` [H], ``ssm_norm`` {"g": [H * P]}, ``out_proj`` [H * P,
    d]. Counted once per traced call: ``ssm_scan_kernel_layers`` /
    ``ssm_scan_product_layers``, the scan's form by :func:`scan_tile`."""
    dt_ = x.dtype
    sizes = dict(heads=heads, head_dim=head_dim, groups=groups, state=state,
                 chunk=chunk)
    inner, bc = heads * head_dim, groups * state
    with jax.named_scope("ssm_proj"):
        zxd = checkpoint_name(x @ lp["in_proj"].astype(dt_), SSM_IN)
        z, xbc, dt = (zxd[..., :inner], zxd[..., inner:2 * inner + 2 * bc],
                      zxd[..., 2 * inner + 2 * bc:])
    dims = scan_tile(jax.default_backend(), x.shape[1], dtype=dt_, **sizes)
    pvar.record("ssm_scan_product_layers" if dims is None
                else "ssm_scan_kernel_layers")
    # The kernels take the sequence LAST, as XLA lays these arrays out by
    # itself: the ``swapaxes`` below and the convolution's own are
    # bitcasts. Each stands in the scope of the operation it is fused
    # with (XLA names a fusion for its last operation: in the scan's
    # scope they would book the convolution and the gate's backward pass
    # to the scan), and the convolution's in front of its name: XLA
    # makes a value that is kept under one shape and read under another
    # TWICE (1.24 ms a layer on the chip).
    with jax.named_scope("ssm_conv"):
        xbc = causal_conv(xbc, lp["conv_w"], lp["conv_b"],
                          time_last=dims is not None, within=(zxd, inner))
        if dims is None:  # the scan's kernels name what they keep of it
            xbc = checkpoint_name(xbc, SSM_CONV)
    y, last = (_scan_by_products(lp, xbc, dt, **sizes) if dims is None
               else _scan_by_kernels(lp, xbc, dt, dims))
    with jax.named_scope("ssm_gate_norm"):
        if dims is not None:
            y = jnp.swapaxes(y, 1, 2)
        y = gated_group_norm(checkpoint_name(y, SSM_Y), z,
                             lp["ssm_norm"]["g"], groups, eps)
    with jax.named_scope("ssm_proj"):
        return y @ lp["out_proj"].astype(dt_), last
