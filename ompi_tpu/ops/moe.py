"""Mixture-of-experts routing and the two ways the model runs experts.

**One device (``ax.ep`` is None): the drop-free sorted path.**
:func:`topk_routing` (float32 softmax over all experts, top-k, the
weights as they are unless the config renormalises them, and the
ingredients of the two router losses; OLMoE's) or
:func:`sigmoid_routing` (float32 sigmoid scores, top-k of score +
a selection bias without gradient, renormalised and scaled weights:
DeepSeek-V3's ``noaux_tc``, as GLM-5 — the third published model of
``models/transformer.py``, reference
``benchmark/reference/glm5_decoder.py`` — has it), optionally
:func:`held_share` (the routing as ONE chip of an expert-parallel
deployment sees it: it holds some of the experts, the router scored
them all, and the other chips' assignments sort past the last group
where nobody computes them), and :func:`sorted_moe_ffn`: the
``T * k`` token-expert assignments are sorted by expert (stable), the
rows gathered into expert order, the experts run as ONE grouped matmul
per expert matrix over the ragged groups (:func:`grouped_matmul`: on
the TPU, where the static rule :func:`grouped_tiles` gives tiles, the
Pallas kernels of ops/grouped_matmul.py, forward and both transposes;
``lax.ragged_dot`` everywhere else; an expert width the kernels'
lanes do not divide enters them padded with zero columns,
:func:`expert_width_pad`), and each token's k rows fetched back by
the sort's inverse, weighted and summed in float32 with one rounding.
Static shapes, no capacity, no token ever dropped, any ``k``,
gated (``w3``) or plain ReLU experts; differentiable with respect to
the rows, the expert weights and, through the weights, the router.
Rows move in three ways and no other, each a gather in the forward
AND the backward pass (``custom_vjp``; a scatter-add is the slow way
to transpose a gather on a TPU): :func:`_take_held` (a token to each
of its places in the sort), its transpose :func:`_sum_held` (a token's
k places fetched, places leading, and added) and :func:`_weigh_held`
(the same with the router's weights; its transpose keeps the products
in the sort's order and reads the cotangent from the TOKENS — on the
chip a gather from the ``[T, D]`` tokens ran at 650 GB/s of rows
written and one from the ``[T k, D]`` rows at ~122, PERF.md 5 —, and
the weights and their gradient change order as scalars, by a sort:
:func:`_by_key`). The two REDUCE forms — a token's k rows fetched from
the ``[bound, D]`` rows and added — are XLA's gather and reduction, or,
where the rule :func:`row_reduce_kernel` says so (the TPU, places whose
rows are more bytes than XLA's gather moves cheaply), a Pallas kernel of
one DMA a held row (ops/grouped_matmul.row_reduce) reading a packed
layout that the ``w2`` product and the rows' gradient write in their
epilogue (:func:`_reduced_rows`). **A chip that holds a
share of the experts** carries a static BOUND of rows instead of all
``T * k`` (the rule :func:`held_rows_bound`: a few times its share):
the first `bound` rows of the same sort are taken, multiplied by the
same kernels and summed back per token by the same three functions —
the full layer is the bounded one at ``bound = T * k``, one body
(:func:`_held_rows`) — or, where the bound is a small part of
``T * k``, by a 0/1 product on the MXU (:func:`_sum_rows`; the rule
:func:`row_sum_gathers`), and a batch that sends the chip more rows
than that takes the layer over all rows instead, inside one
``lax.cond`` a direction — exact, counted, never a drop.

**Expert parallel (``ax.ep``): capacity-based top-1 over all_to_all.**
BASELINE.md config #5 is the MPI_Alltoall(v) MoE expert-dispatch
pattern; the reference implements the transport (bruck/pairwise/linear
alltoall, coll_base_alltoall.c:180-616) and leaves the model math to the
application. Here the two fuse: dispatch = one-hot matmul (MXU) +
``lax.all_to_all`` over the expert axis (ICI), experts run their FFN
on dense [E_local, n*C, D] blocks, and combine is the inverse
all_to_all weighted by the gates. Switch-Transformer routing
(:func:`top1_routing`): static shapes, overflow tokens dropped. The
drop is METERED: :class:`MoEDispatch` carries the drop count and the
per-expert routed histogram, and an eager (non-traced) routing call
records ``serve_dropped_tokens`` (``ompi_tpu.serve`` adds the
overflow-handling policies on top of this router). ROADMAP R1b moves
this path onto the sort above with an exchange in the middle.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ompi_tpu.core import pvar
from ompi_tpu.util import jaxcompat


class MoEDispatch(NamedTuple):
    combine: jnp.ndarray   # [T, E, C] combine weights (gate at slot)
    dispatch: jnp.ndarray  # [T, E, C] 0/1 dispatch assignment
    counts: jnp.ndarray    # [E] routed tokens per expert (pre-capacity)
    dropped: jnp.ndarray   # [] tokens past capacity (drop-metered)


def record_dispatch_stats(route: MoEDispatch) -> None:
    """Meter one routing decision on the pvar plane — a no-op under a
    jit trace (abstract values cannot be read back; the serve loop
    meters its compiled dispatches from the program's stats outputs
    instead)."""
    try:
        dropped = int(route.dropped)
        counts = [int(c) for c in route.counts]
    except Exception:  # noqa: BLE001 — traced values: caller meters
        return
    if dropped:
        pvar.record("serve_dropped_tokens", dropped)
    from ompi_tpu import monitoring as _monitoring

    _monitoring.expert_load(counts)


def top1_routing(logits, capacity: int) -> MoEDispatch:
    """Switch top-1 router. logits: [T, E]; C slots per expert."""
    t, e = logits.shape
    gates = logits.astype(jnp.float32)
    gates = jnp.exp(gates - lax.stop_gradient(
        gates.max(-1, keepdims=True)))
    gates = gates / gates.sum(-1, keepdims=True)          # softmax [T,E]
    expert = jnp.argmax(gates, axis=-1)                   # [T]
    onehot = jnp.eye(e, dtype=jnp.float32)[expert]        # [T,E]
    # position of each token within its expert's queue (arrival order)
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0       # [T,E]
    keep = (pos >= 0) & (pos < capacity)                  # [T,E]
    pos = jnp.clip(pos, 0, capacity - 1).astype(jnp.int32)
    posmask = jnp.eye(capacity, dtype=jnp.float32)[pos]   # [T,E,C]
    dispatch = posmask * keep[..., None]                  # [T,E,C]
    gate1 = (gates * onehot).sum(-1)                      # [T]
    combine = dispatch * gate1[:, None, None]
    counts = onehot.sum(0).astype(jnp.int32)              # [E]
    dropped = (t - dispatch.sum()).astype(jnp.int32)      # []
    route = MoEDispatch(combine=combine, dispatch=dispatch,
                        counts=counts, dropped=dropped)
    record_dispatch_stats(route)
    return route


def ep_apply(route: MoEDispatch, x, w1, w2, axis: str):
    """The EP dispatch→FFN→combine leg on an already-decided routing:
    pack tokens into per-expert slots, all_to_all over the expert
    axis, run the local experts, inverse-exchange and combine. Split
    from :func:`moe_ffn` so the serve plane's overflow policies can
    swap the routing while keeping this op sequence bit-identical to
    the training path."""
    n = jaxcompat.axis_size(axis)
    t, d = x.shape
    e_local = w1.shape[0]
    cap = route.dispatch.shape[-1]
    e_total = e_local * n
    # pack tokens into per-expert slots: [E_total, C, D] (one-hot matmul
    # -> MXU; also what makes dispatch differentiable w.r.t. x)
    slots = jnp.einsum("tec,td->ecd", route.dispatch, x)
    # exchange over the expert axis: dim0 split by destination device,
    # received stacked by source -> [n_src, E_local, C, D]
    slots = slots.reshape(n, e_local, cap, d)
    slots = lax.all_to_all(slots, axis, split_axis=0, concat_axis=0)
    slots = slots.transpose(1, 0, 2, 3).reshape(e_local, n * cap, d)
    # local experts' FFN on dense blocks
    hidden = jnp.maximum(jnp.einsum("ekd,edf->ekf", slots, w1), 0.0)
    out = jnp.einsum("ekf,efd->ekd", hidden, w2)
    # inverse exchange: back to the source devices
    out = out.reshape(e_local, n, cap, d).transpose(1, 0, 2, 3)
    out = lax.all_to_all(out, axis, split_axis=0, concat_axis=0)
    # [n_expert_group, E_local, C, D] == [E_total, C, D] for this device
    out = out.reshape(e_total, cap, d)
    return jnp.einsum("tec,ecd->td", route.combine, out).astype(x.dtype)


def moe_ffn(x, wg, w1, w2, axis: str, capacity_factor: float = 1.25):
    """Expert-parallel MoE FFN layer inside ``shard_map``.

    x: local tokens [T, D]; wg: router [D, E_total] (replicated);
    w1/w2: this device's experts [E_local, D, F], [E_local, F, D].
    E_total = E_local * axis_size(axis). Returns [T, D].
    """
    n = jaxcompat.axis_size(axis)
    t, d = x.shape
    e_local = w1.shape[0]
    e_total = e_local * n
    cap = max(int(capacity_factor * t / e_total), 1)

    route = top1_routing(x @ wg, cap)
    return ep_apply(route, x, w1, w2, axis)


# -- one device: top-k routing and the drop-free sorted dispatch -------------

class TopKRoute(NamedTuple):
    experts: jnp.ndarray    # [T, k] int32, the chosen experts
    weights: jnp.ndarray    # [T, k] float32, their routing weights
    counts: jnp.ndarray     # [E] int32 assignments per expert
    mean_prob: jnp.ndarray  # [E] P_e: mean router probability
    lse: jnp.ndarray        # [T] logsumexp of the router logits

    @property
    def frac(self):
        """[E] f_e: each expert's share of the T*k assignments."""
        return self.counts.astype(jnp.float32) / self.experts.size


def topk_routing(logits, k: int, renormalize: bool = False) -> TopKRoute:
    """Softmax over ALL experts in float32, then the k largest.
    logits: [T, E]. The weights are the k probabilities as they are
    (they sum to less than 1) unless `renormalize`."""
    logits = logits.astype(jnp.float32)
    t, e = logits.shape
    lse = jax.nn.logsumexp(logits, axis=-1)
    probs = jnp.exp(logits - lse[:, None])
    weights, experts = lax.top_k(probs, k)
    if renormalize:
        weights = weights / weights.sum(-1, keepdims=True)
    counts = (experts.reshape(t * k, 1)
              == jnp.arange(e, dtype=experts.dtype)).sum(0, dtype=jnp.int32)
    return TopKRoute(experts=experts, weights=weights, counts=counts,
                     mean_prob=probs.mean(0), lse=lse)


def sigmoid_routing(logits, bias, k: int, renormalize: bool = True,
                    scale: float = 1.0) -> TopKRoute:
    """The auxiliary-loss-free router (DeepSeek-V3's `noaux_tc`; GLM-5):
    float32 sigmoid scores of ALL experts; the k experts are the k
    largest of score + `bias` ([E], a correction buffer that carries no
    gradient, or None), their weights the scores themselves (the bias
    only chooses), renormalised to sum to 1 and multiplied by `scale`.
    logits: [T, E]."""
    logits = logits.astype(jnp.float32)
    t, e = logits.shape
    probs = jax.nn.sigmoid(logits)
    choose = probs if bias is None else probs + lax.stop_gradient(
        bias.astype(jnp.float32))
    experts = lax.top_k(choose, k)[1]
    weights = jnp.take_along_axis(probs, experts, axis=-1)
    if renormalize:
        weights = weights / weights.sum(-1, keepdims=True)
    counts = (experts.reshape(t * k, 1)
              == jnp.arange(e, dtype=experts.dtype)).sum(0, dtype=jnp.int32)
    return TopKRoute(experts=experts, weights=weights * scale,
                     counts=counts, mean_prob=probs.mean(0),
                     lse=jax.nn.logsumexp(logits, axis=-1))


def load_balance_loss(route: TopKRoute):
    """``E * sum_e f_e P_e`` (Switch / OLMoE): 1 when routing is
    uniform; the gradient reaches the router through P_e alone."""
    return route.counts.shape[0] * jnp.sum(route.frac * route.mean_prob)


def router_z_loss(route: TopKRoute):
    """``mean(logsumexp(logits) ** 2)`` (ST-MoE)."""
    return jnp.mean(route.lse ** 2)


_ACT = {"relu": lambda x: jnp.maximum(x, 0), "silu": jax.nn.silu,
        "gelu": jax.nn.gelu,
        "relu2": lambda x: jnp.square(jnp.maximum(x, 0))}


def activation(name: str):
    """The FFN / expert activation a config names."""
    if name not in _ACT:
        raise ValueError(f"activation {name!r}: expected one of "
                         f"{sorted(_ACT)}")
    return _ACT[name]


# -- the grouped matmul of the sorted path ------------------------------------

class GroupedTiles(NamedTuple):
    """The tiles of the three products a grouped matmul is made of, as
    the kernels in ops/grouped_matmul.py take them."""
    fwd: tuple     # (tm, sub, tn)       [m, k] x [E, k, n]
    drows: tuple   # (tm, sub, tn)       [m, n] x [E, k, n]^T
    dw: tuple      # (tm, sub, tk, tn)   [m, k]^T x [m, n]


#: Rows of a tile, and of the blocks a tile with a group edge in it is
#: worked in (v5e, one layer's experts alone at olmoe-train-t4096's
#: shapes and group sizes, PERF.md section 6, PR 29: 512 / 128 is the
#: fastest of 11 row products and of 9 weight gradients, on the cell's
#: groups and on uniform ones).
_TM, _SUB = 512, 128
#: What a kernel's blocks may take of VMEM, both buffers of each
#: counted (the kernels ask the compiler for 96 MiB of v5e's 128).
_VMEM_BLOCKS = 64 * 1024 * 1024


def _blocks(x: int):
    """The 128-multiples that divide x, largest first."""
    return [d for d in range(x, 0, -128) if x % d == 0]


def _row_product_tiles(k: int, n: int, size: int, pairs: int = 1,
                       packed: bool = False) -> Optional[tuple]:
    """(tm, sub, tn) of ``[m, k] x [k, n]`` with the whole of K in one
    block: the widest block of columns whose weights, rows and result
    fit, or None. `pairs`: that many products summed in one tile (each
    pair's rows and weights held). `packed`: the result in the packed
    layout (ops/grouped_matmul.packed_shape), whose blocks of columns
    are whole tiles of 8 sublanes unless the block is all of N."""
    for tn in _blocks(n):
        if packed and tn < n and tn % 1024:
            continue
        if (2 * size * (pairs * (k * tn + _TM * k) + _TM * tn)
                + 4 * _TM * tn <= _VMEM_BLOCKS):
            return _TM, _SUB, tn
    return None


def _dw_tiles(k: int, n: int, size: int) -> Optional[tuple]:
    """(tm, sub, tk, tn) of the weights' gradient: the largest
    [tk, tn] block whose float32 sum, result and operand tiles fit, or
    None."""
    fit = [(tk * tn, tn, tk) for tk in _blocks(k) for tn in _blocks(n)
           if (4 + 2 * size) * tk * tn + 2 * size * _TM * (tk + tn)
           <= _VMEM_BLOCKS]
    if not fit:
        return None
    _, tn, tk = max(fit)
    return _TM, _SUB, tk, tn


def grouped_tiles(backend: str, m: int, k: int, n: int,
                  dtype) -> Optional[GroupedTiles]:
    """The rule that sends a grouped matmul ``[m, k] x [E, k, n]`` to
    the Pallas kernels, made of what the caller can observe: each
    product's tiles, or None where it stays ``lax.ragged_dot`` — off
    the TPU, K or N that are not multiples of the 128 lanes, rows the
    row tile does not divide, anything but bfloat16 or float32 operands
    of one type (`dtype` None: two types), matrices so wide that a
    whole-K block does not fit in VMEM."""
    if (backend != "tpu" or k % 128 or n % 128 or m % _TM or dtype is None
            or jnp.dtype(dtype) not in (jnp.bfloat16, jnp.float32)):
        return None
    size = jnp.dtype(dtype).itemsize
    tiles = GroupedTiles(fwd=_row_product_tiles(k, n, size),
                         drows=_row_product_tiles(n, k, size),
                         dw=_dw_tiles(k, n, size))
    return tiles if all(tiles) else None


@functools.lru_cache(maxsize=None)
def _grouped_kernels(tiles: GroupedTiles, interpret: bool):
    """`grouped_matmul`'s kernel path for one tile set: the product and
    its two transposes, each its own Pallas kernel with its own tiles.
    Built per tile set (the tiles are static), imported late: Pallas is
    1.3 s of Python that only a TPU run needs."""
    from ompi_tpu.ops import grouped_matmul as gk

    @jax.custom_vjp
    def product(rows, w, counts):
        return gk.gmm(rows, w, counts, tiles.fwd, interpret=interpret)

    def fwd(rows, w, counts):
        return product(rows, w, counts), (rows, w, counts)

    def bwd(res, g):
        rows, w, counts = res
        return (gk.gmm(g, w, counts, tiles.drows, transpose_rhs=True,
                       out_dtype=rows.dtype, interpret=interpret),
                gk.tgmm(rows, g, counts, tiles.dw, out_dtype=w.dtype,
                        interpret=interpret), None)

    product.defvjp(fwd, bwd)
    return product


def _tiles_of(rows, w) -> Optional[GroupedTiles]:
    """The rule, asked about these operands on this backend (operands
    of two types promote in ``lax.ragged_dot``, not in the kernels)."""
    return grouped_tiles(jax.default_backend(), *rows.shape, w.shape[2],
                         rows.dtype if w.dtype == rows.dtype else None)


def grouped_matmul(rows, w, counts, interpret: bool = False):
    """``lax.ragged_dot(rows, w, counts)``: rows [M, K] sorted by
    group, w [E, K, N], counts [E] int32 rows a group -> [M, N] in the
    rows' type, float32 accumulation; rows past the last group
    (``sum(counts) < M``) come out zero. Where :func:`grouped_tiles`
    gives tiles (the TPU) the product and both its transposes are
    Pallas kernels (ops/grouped_matmul.py); everywhere else it IS
    ``lax.ragged_dot``, jax's own transposes included (on the TPU with
    the tail zeroed around it: :func:`_ragged_dot_zero_tail`)."""
    tiles = _tiles_of(rows, w)
    if tiles is None:
        return (_ragged_dot_zero_tail if jax.default_backend() == "tpu"
                else lax.ragged_dot)(rows, w, counts)
    return _grouped_kernels(tiles, interpret)(rows, w, counts)


def _ragged_dot_zero_tail(rows, w, counts):
    """``lax.ragged_dot`` whose rows past the last group are ZERO, in
    the product and in the rows' gradient: libtpu's kernels leave there
    what memory held (read on the chip, PR 33: a 31/32 tail through
    them gave the rows' gradient a relative error of 24; OLMoE, their
    only user before, has no tail)."""
    inside = (jnp.arange(rows.shape[0]) < counts.sum())[:, None]
    return jnp.where(inside, lax.ragged_dot(
        jnp.where(inside, rows, 0), w, counts), 0)


def held_share(route: TopKRoute, first: int, count: int) -> TopKRoute:
    """The routing as the chip that holds experts `first` ..
    `first + count - 1` of a layer sees it: the router chose among ALL
    experts; an assignment to a held expert keeps its weight and takes
    the expert's local number, every other one takes number `count` —
    it sorts after the held ones and weighs nothing — and `counts` are
    the held experts' alone. The shapes stay the router's (``T * k``
    assignments); how many rows of them the layer then carries is
    :func:`held_rows_bound`'s to say. Nothing stands in for the chips
    that hold the rest."""
    local = route.experts - first
    here = (local >= 0) & (local < count)
    return route._replace(
        experts=jnp.where(here, local, count),
        weights=jnp.where(here, route.weights, 0.0),
        counts=route.counts[first:first + count])


#: How many times its mean share of a layer's assignments a chip's
#: rows are bounded at: the smallest power of two that clears twice
#: over the most the probe has read. On the chip (PR 33,
#: glm5-train-t4096, 8 of 256 experts held: `transformer.route_counts`
#: over 24 seeds — the cell's calibration seeds among them — x 8
#: batches x 4 layers): 685 ... 1,579 held rows a layer and batch
#: around the share's 1,024, so twice the most is 3,158 of 4 x 1,024.
SLACK = 4


def held_rows_bound(t: int, k: int, count: int, n_experts: int) -> int:
    """The static number of rows :func:`sorted_moe_ffn` works on for a
    chip that holds `count` of a layer's `n_experts`: `SLACK` times
    the share ``count / n_experts`` of the ``t * k`` assignments,
    rounded up to whole row tiles, and never more than all of them
    (the whole of them at share 1 and wherever `SLACK` shares cover the
    layer: no second path exists there). A batch that sends the chip
    more takes the layer's full path, counted
    (`moe_over_bound_layers`)."""
    rows = t * k
    want = -(-SLACK * count * rows // n_experts)
    return min(rows, -(-want // _TM) * _TM)


#: Operations of a 0/1 product on the MXU that take the time of one
#: gathered byte: the v5e's 197 TFLOP/s over the ~100 GB/s at which
#: XLA's row gather and the sum behind it run — XLA's, not the rate of
#: ops/grouped_matmul.row_reduce, which fetches the held rows alone
#: (:func:`row_reduce_kernel`; PR 51 left this rule and its constant as
#: they were: PERF.md 7 has the kernel's reading at glm5-train-t4096's
#: shape for the PR that fits them again). On the chip (PR 40, one
#: bounded layer, forward + backward, bfloat16): at nemotron-train-
#: t8192's shapes (24,576 of 49,152 rows of 2,688) the product form
#: 38.9 ms, the gather 18.8 — its two gathers of 264 MB 2.07 ms each
#: and the sums 0.45; at glm5-train-t4096's (4,096 of 32,768 rows of
#: 6,144) the product 12.6, the gather 15.6 — 403 MB in 3.09 ms, twice,
#: against four passes of 1.1-1.2. The rule's ratio (below) reads 8,192
#: and 1,024 operations a byte there.
ROW_SUM_OPS_PER_BYTE = 2000


def row_sum_gathers(t: int, k: int, bound: int, d: int, dtype) -> bool:
    """The rule that says how a bounded layer adds a token's rows,
    made of what the caller can observe: the 0/1 product
    (:func:`_sum_rows`) costs ``2 * t * bound * d`` operations a pass
    — three passes for the combine's float32 rows, and for the
    dispatch's transpose one (bfloat16 rows) or three —, the gather by
    the sort's inverse (:func:`_weigh_held`, :func:`_sum_held`) moves
    ``t * k`` rows of `d` a direction. True where the product's
    operations are more than `ROW_SUM_OPS_PER_BYTE` a byte of those."""
    dtype = jnp.dtype(dtype)
    passes = 3 + (1 if dtype == jnp.bfloat16 else 3)
    return (passes * 2 * t * bound * d
            > ROW_SUM_OPS_PER_BYTE * 2 * t * k * d * dtype.itemsize)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _take_rows(x, token, t: int):
    """x[token]: the rows of x [t, D] that `token` [B] names, where
    the per-token sums are products. Its transpose is
    :func:`_sum_rows`, and that one's is this."""
    return x[token]


def _take_rows_fwd(x, token, t):
    return x[token], token


def _take_rows_bwd(t, token, g):
    return _sum_rows(g, token, t), None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _sum_rows(v, token, t: int):
    """[t, D] in v's type: row i is the sum of the rows of v [B, D]
    whose `token` is i (at most k of them), summed in float32 whatever
    v's type. On the MXU, which the expert layer of a chip with a
    small share leaves idle: the 0/1 matrix ``[t, B]`` is exact in
    bfloat16 and so is each of the three bfloat16 pieces a float32 v is
    cut into, so every product is exact and only the float32 sum's
    order is the hardware's. Its work grows with ``t * B``: the form
    of a B that is a small part of ``t * k``
    (:func:`row_sum_gathers`; :func:`_sum_held` is the other)."""
    hot = (jnp.arange(t, dtype=token.dtype)[:, None]
           == token[None, :]).astype(jnp.bfloat16)
    total, rest = 0.0, v.astype(jnp.float32)
    for _ in range(1 if v.dtype == jnp.bfloat16 else 3):
        # reduce_precision, not a cast there and back: the compiler
        # may keep excess precision through a pair of converts
        piece = lax.reduce_precision(rest, exponent_bits=8, mantissa_bits=7)
        total = total + jnp.dot(hot, piece.astype(jnp.bfloat16),
                                preferred_element_type=jnp.float32)
        rest = rest - piece
    return total.astype(v.dtype)


def _sum_rows_fwd(v, token, t):
    return _sum_rows(v, token, t), token


def _sum_rows_bwd(t, token, g):
    return _take_rows(g, token, t), None


_sum_rows.defvjp(_sum_rows_fwd, _sum_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _take_head(flat, order, inv, bound: int):
    """flat[order[:bound]] for a permutation and its inverse — a gather
    both ways."""
    return flat[order[:bound]]


def _take_head_fwd(flat, order, inv, bound):
    return flat[order[:bound]], inv


def _take_head_bwd(bound, inv, g):
    return jnp.where(inv < bound, g[jnp.minimum(inv, bound - 1)], 0), \
        None, None


_take_head.defvjp(_take_head_fwd, _take_head_bwd)


def _experts(rows, counts, w1, w3, w2, act: str, product=None):
    """The experts on rows in expert order, [M, D] -> [M, D]; the
    products are `product`'s (None: :func:`grouped_matmul`'s)."""
    product = product or grouped_matmul
    with jax.named_scope("moe_experts"):
        hidden = activation(act)(product(rows, w1, counts))
        if w3 is not None:
            hidden = hidden * product(rows, w3, counts)
        return product(hidden, w2, counts)


def _expert_order(experts):
    """The stable sort of the ``T * k`` assignments by expert, as a
    permutation and its inverse."""
    order = jnp.argsort(experts.reshape(-1), stable=True)
    return order, jnp.argsort(order)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _take_held(x, order, inv, k: int, bound: int):
    """x[order[:bound] // k]: the tokens of the first `bound`
    assignments of the sort. Its transpose is :func:`_sum_held`, and
    that one's is this: a gather both ways."""
    return x[order[:bound] // k]


def _take_held_fwd(x, order, inv, k, bound):
    return x[order[:bound] // k], (order, inv)


def _take_held_bwd(k, bound, res, g):
    return _sum_held(g, *res, k, bound), None, None


_take_held.defvjp(_take_held_fwd, _take_held_bwd)


def _places(v, inv, k: int, bound: int, held):
    """[k, t, ...] in v's type: the rows of v [bound, ...] at each
    token's k places in the sort, place by place, and zero at a place
    that is not under `held` (<= bound). Under a bound a place past it
    reads some row — the rows one after another, not one row for all
    of them: most places of a small share are such — and counts for
    nothing; with all ``t * k`` rows (the full layer) every place has
    its row, and by the static shape no such index is built. The mask
    stands either way: without it XLA left the rows' conversion to
    float32 out of the weighted sum's fusion (the chip, PR 44, the full
    layer at mellum2-train-t16384: a float32 copy of the ``[8, 16384,
    2304]`` rows written and read, 2.85 + 1.66 ms a layer beside the
    gather's 4.93, where the einsum this replaced took 0.87). The
    places lead: ``[k * t, D]`` splits into ``[k, t, D]`` for nothing
    on the TPU, where ``[t, k, D]`` is a copy into tiles of 16 rows
    that k does not fill (the chip, PR 40, a layer and direction at
    nemotron-train-t8192: that copy 0.97 ms, the sum over its 2.7
    times the rows 1.07 for 0.45, the gather 2.31 for 2.07 with every
    such place on the last row)."""
    at = inv.reshape(-1, k).T
    read = at
    if bound < at.size:
        walk = (jnp.arange(at.size, dtype=at.dtype) % bound).reshape(at.shape)
        read = jnp.where(at < bound, at, walk)
    rows = v[read]
    return jnp.where(jnp.expand_dims(at < held, tuple(range(2, rows.ndim))),
                     rows, 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _sum_held(v, order, inv, k: int, bound: int):
    """[t, D] in v's type: row i is the sum of the rows of v [bound, D]
    that stand at token i's places in the sort (``inv[i * k + j] <
    bound``: at most k of them), fetched by the sort's inverse in the
    type they have and summed in float32."""
    return _places(v, inv, k, bound, bound).astype(jnp.float32).sum(
        0).astype(v.dtype)


def _sum_held_fwd(v, order, inv, k, bound):
    return _sum_held(v, order, inv, k, bound), (order, inv)


def _sum_held_bwd(k, bound, res, g):
    return _take_held(g, *res, k, bound), None, None


_sum_held.defvjp(_sum_held_fwd, _sum_held_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _weigh_held(out, weights, order, inv, held, bound: int):
    """[t, D] in out's type: ``sum_j weights[i, j] * out[inv[i * k +
    j]]`` over token i's places under `held` (an int32 scalar, <=
    bound: the held assignments), the products and the sum in float32
    and ONE rounding. A place past `held` weighs nothing whatever its
    row holds. The transpose keeps `out` as it stands, in the sort's
    order, and reads `bound` rows of the cotangent FROM THE TOKENS
    (:func:`_take_held`): nothing of the size of `out` is permuted in
    it; the weights go into the sort's order and their gradient comes
    back as scalars (:func:`_by_key` where every row exists)."""
    return (_places(out, inv, weights.shape[1], bound, held).astype(
        jnp.float32) * weights.T[..., None]).sum(0).astype(out.dtype)


def _weigh_held_fwd(out, weights, order, inv, held, bound):
    return (_weigh_held(out, weights, order, inv, held, bound),
            (out, weights, order, inv, held))


def _by_key(keys, values):
    """`values` in the order of their `keys`, a permutation: with the
    sort's inverse as keys that is ``values[order]``, with the sort
    itself ``values[inv]``. ONE two-operand sort where a gather of
    scalars walks them one by one (the chip, PR 44, 131,072 float32:
    0.93-1.13 ms a gather, 0.13 a sort)."""
    return lax.sort((keys, values), num_keys=1)[1]


def _weigh_held_bwd(bound, res, g):
    out, weights, order, inv, held = res
    k = weights.shape[1]
    g = _take_held(g, order, inv, k, bound).astype(jnp.float32)
    flat = weights.reshape(-1)
    # all ``T * k`` scalars change order by a sort; under a bound the
    # first `bound` of them are fetched, as a bounded layer's always
    # were (there a sort stood between two fusions: compiled for a
    # v5e, PR 44, nemotron-train-t8192's `dout` left its fusion)
    full = bound == flat.size
    dout = g * (_by_key(inv, flat) if full else flat[order[:bound]])[:, None]
    rowsum = (out.astype(jnp.float32) * g).sum(-1)
    dweight = (_by_key(order, rowsum).reshape(weights.shape) if full
               else _places(rowsum, inv, k, bound, held).T)
    return dout.astype(out.dtype), dweight, None, None, None


_weigh_held.defvjp(_weigh_held_fwd, _weigh_held_bwd)


class ReduceTiles(NamedTuple):
    """The tiles of a layer whose per-token sums are the kernel's
    (:func:`row_reduce_kernel`): the up and down products' own, and
    those of the two products that WRITE the packed layout."""
    up: GroupedTiles    # [bound, D] x [E, D, F]: w1, w3
    down: GroupedTiles  # [bound, F] x [E, F, D]: w2
    out: tuple          # (tm, sub, tn) the w2 product, packed
    drows: tuple        # (tm, sub, tn) the rows' gradient, packed


#: Bytes of the ``t * k`` places' rows — what XLA's gather moves, held
#: or not, at ~100-125 GB/s whatever the shape (a reduce's source is a
#: kernel's output: never in VMEM) — above which the kernel takes the
#: sums. On the chip (PR 51, `scripts/row_reduce_probe.py`, one reduce,
#: XLA's ms against the kernel's with the packed epilogue's; PERF.md
#: 6): under a bound the kernel walks the held places alone — 805 MB
#: of places (kexaone-train-t8192) 7.24 against 0.72, 537 MB (solar2-)
#: 4.95 against 1.24, 264 MB (nemotron-) 2.56 against 0.73 —; with
#: every place held it pays ~24 ns a copy's descriptor — 604 MB
#: (mellum2-train-t16384) 6.13 against 5.67, 134 MB (olmoe-train-
#: t4096) 1.38 against 1.67, 100 MB (kimivl-) 0.80 against 1.27. The
#: geometric middle of the largest loss and the smallest gain.
ROW_REDUCE_MIN_BYTES = 192 << 20


def row_reduce_kernel(backend: str, t: int, k: int, bound: int, d: int,
                      dtype) -> bool:
    """The rule that says who adds a token's k rows where they are
    fetched by the sort's inverse (:func:`row_sum_gathers`; the full
    layer always), made of what the caller can observe: True where
    ops/grouped_matmul.row_reduce does — one DMA a held row from the
    packed layout the grouped matmuls write for it, the sum in VMEM —,
    False where XLA's gather and float32 reduction stay
    (:func:`_weigh_held`, :func:`_sum_held`): off the TPU, a width the
    128 lanes do not divide, tokens that are not whole tiles of the
    kernel's 128, rows the kernels' row tile does not divide, anything
    but bfloat16 or float32, and places whose rows are
    `ROW_REDUCE_MIN_BYTES` or less: XLA's gather is paid by the byte
    of ALL ``t * k`` places, the kernel by the held row and by the
    packed products' epilogue, and under that size the two draw. The
    grouped kernels' own tiles must exist too (:func:`reduce_tiles`):
    ``lax.ragged_dot`` cannot write the layout."""
    return (backend == "tpu" and d % 128 == 0 and t % 128 == 0
            and bound % _TM == 0
            and jnp.dtype(dtype) in (jnp.bfloat16, jnp.float32)
            and t * k * d * jnp.dtype(dtype).itemsize > ROW_REDUCE_MIN_BYTES)


def reduce_tiles(backend: str, bound: int, d: int, f: int, dtype,
                 gated: bool) -> Optional[ReduceTiles]:
    """The tiles of the layer's six products where the kernel adds the
    rows, or None where one of them has none."""
    up = grouped_tiles(backend, bound, d, f, dtype)
    down = grouped_tiles(backend, bound, f, d, dtype)
    if not (up and down):
        return None
    size = jnp.dtype(dtype).itemsize
    tiles = ReduceTiles(
        up, down, _row_product_tiles(f, d, size, packed=True),
        _row_product_tiles(f, d, size, pairs=1 + gated, packed=True))
    return tiles if all(tiles) else None


@functools.lru_cache(maxsize=None)
def _reduced_units(tiles: ReduceTiles, k: int, bound: int, interpret: bool):
    """The two differentiable units of a layer whose sums are the
    kernel's, for one tile set. The packed array is uint32 words and
    carries no cotangent, so it never leaves a unit: the dispatch goes
    with the up products (whose rows' gradient is packed and summed
    per token), the ``w2`` product with the combine (whose result is).
    The transposes are the plain path's (:func:`_take_held_bwd`,
    :func:`_weigh_held_bwd`, :func:`_grouped_kernels`)."""
    from ompi_tpu.ops import grouped_matmul as gk

    def places(inv):
        return inv.reshape(-1, k).T

    def held(counts):
        return jnp.minimum(counts.sum(), bound)

    @jax.custom_vjp
    def taken_up(x, w1, w3, counts, order, inv):
        return taken_up_fwd(x, w1, w3, counts, order, inv)[0]

    def taken_up_fwd(x, w1, w3, counts, order, inv):
        with jax.named_scope("moe_dispatch"):
            rows = x[order[:bound] // k]
        with jax.named_scope("moe_experts"):
            up = tuple(None if w is None else gk.gmm(
                rows, w, counts, tiles.up.fwd, interpret=interpret)
                for w in (w1, w3))
        return up, (rows, w1, w3, counts, inv)

    def taken_up_bwd(res, g):
        rows, w1, w3, counts, inv = res
        pairs = [(d, w) for d, w in zip(g, (w1, w3)) if w is not None]
        with jax.named_scope("moe_experts"):
            # both pairs' products summed in the float32 tile and
            # rounded once, in the layout the sum below reads
            drows = gk.gmm(*pairs[0], counts, tiles.drows, transpose_rhs=True,
                           out_dtype=rows.dtype, interpret=interpret,
                           packed=True, **(dict(zip(("lhs2", "rhs2"),
                                                    pairs[1]))
                                           if len(pairs) > 1 else {}))
            dw = [None if w is None else gk.tgmm(
                rows, d, counts, tiles.up.dw, out_dtype=w.dtype,
                interpret=interpret) for d, w in zip(g, (w1, w3))]
        with jax.named_scope("moe_dispatch"):
            # the rows past the held ones are zeros (the product's
            # tail): under a bound they are not fetched either
            dx = gk.row_reduce(drows, places(inv), None, held(counts),
                               rows.shape[1], tiles.drows[2], rows.dtype,
                               compact=bound < inv.size, interpret=interpret)
        return dx, *dw, None, None, None

    taken_up.defvjp(taken_up_fwd, taken_up_bwd)

    @jax.custom_vjp
    def down_weighed(hidden, w2, weights, counts, order, inv):
        with jax.named_scope("moe_experts"):
            out = gk.gmm(hidden, w2, counts, tiles.out, interpret=interpret,
                         packed=True)
        with jax.named_scope("moe_combine"):
            return gk.row_reduce(
                out, places(inv), weights.T, held(counts), w2.shape[2],
                tiles.out[2], hidden.dtype, compact=bound < inv.size,
                interpret=interpret)

    def down_weighed_fwd(hidden, w2, weights, counts, order, inv):
        return (down_weighed(hidden, w2, weights, counts, order, inv),
                (hidden, w2, weights, counts, order, inv))

    def down_weighed_bwd(res, g):
        hidden, w2, weights, counts, order, inv = res
        # the packed product is never kept: the weights' gradient reads
        # `out` row against row of the cotangent in the PLAIN layout, so
        # the backward pass makes the product again, plain — the one
        # product a recomputed layer (every expert cell's) made again
        # anyway, and dead code in its recomputed forward
        with jax.named_scope("moe_experts"):
            out = gk.gmm(hidden, w2, counts, tiles.down.fwd,
                         interpret=interpret)
        with jax.named_scope("moe_combine"):
            dout, dweights = _weigh_held_bwd(
                bound, (out, weights, order, inv, held(counts)), g)[:2]
        with jax.named_scope("moe_experts"):
            return (gk.gmm(dout, w2, counts, tiles.down.drows,
                           transpose_rhs=True, out_dtype=hidden.dtype,
                           interpret=interpret),
                    gk.tgmm(hidden, dout, counts, tiles.down.dw,
                            out_dtype=w2.dtype, interpret=interpret),
                    dweights, None, None, None)

    down_weighed.defvjp(down_weighed_fwd, down_weighed_bwd)
    return taken_up, down_weighed


def _reduced_rows(x, experts, weights, counts, w1, w3, w2, act: str,
                  bound: int, interpret: bool = False):
    """:func:`_held_rows` where the rule :func:`row_reduce_kernel` says
    yes: the same rows through the same products, each token's sum —
    the combine's and the dispatch's transpose — the kernel's."""
    k = experts.shape[1]
    tiles = reduce_tiles("tpu", bound, x.shape[1], w1.shape[2], x.dtype,
                         w3 is not None)
    taken_up, down_weighed = _reduced_units(tiles, k, bound, interpret)
    with jax.named_scope("moe_dispatch"):
        order, inv = _expert_order(experts)
    up, gate = taken_up(x, w1, w3, counts, order, inv)
    with jax.named_scope("moe_experts"):
        hidden = activation(act)(up)
        if gate is not None:
            hidden = hidden * gate
    return down_weighed(hidden, w2, weights, counts, order, inv)


def _held_rows(x, experts, weights, counts, w1, w3, w2, act: str,
               bound: int, gather: bool, product=None):
    """The layer over the first `bound` assignments of the sort: all
    ``T * k`` of them in the full layer, all the held ones of a bounded
    layer where ``counts.sum() <= bound``. Under a bound nothing is of
    the size of ``T * k`` rows but, where `gather`
    (:func:`row_sum_gathers`; the full layer always), the rows a
    token's sum fetches."""
    t, k = experts.shape
    with jax.named_scope("moe_dispatch"):
        order, inv = _expert_order(experts)
        if gather:
            rows = _take_held(x, order, inv, k, bound)
        else:
            token = order[:bound] // k
            rows = _take_rows(x, token, t)
    out = _experts(rows, counts, w1, w3, w2, act, product)
    with jax.named_scope("moe_combine"):
        if gather:
            y = _weigh_held(out, weights, order, inv,
                            jnp.minimum(counts.sum(), bound), bound)
            # under a bound the barrier keeps the sum INSIDE its branch
            # of the conditional: the full layer ends in the same
            # float32 products and sum, and XLA moved both branches'
            # out of it, whose result was then the float32 ``[T, k,
            # D]`` rows (compiled for a v5e, PR 40: 0.53 GB a layer at
            # nemotron-train-t8192)
            return lax.optimization_barrier(y) if bound < t * k else y
        weight = _take_head(weights.reshape(t * k), order, inv, bound)
        return _sum_rows(out.astype(jnp.float32) * weight[:, None], token,
                         t).astype(x.dtype)


def _fallback_rows(x, experts, weights, counts, w1, w3, w2, act: str):
    """The full layer as a bounded layer's second branch: the same
    layer with its products through ``lax.ragged_dot`` on every
    backend. A kernel's code is not shared between its call sites, and
    this branch's twelve a layer would be paid for by every run that
    compiles or loads the step — in set-up time, for a branch the
    bound is chosen never to take (PERF.md 6, PR 33: compiled for a
    v5e, glm5-train-t4096's step is 0.94 GB of code and 193 s with the
    kernels here, 0.85 GB and 147 s so, 0.78 GB and 145 s without the
    branch)."""
    return _held_rows(x, experts, weights, counts, w1, w3, w2, act,
                      experts.size, True, product=_ragged_dot_zero_tail)


def _branches(act: str, bound: int, gather: bool, kernel: bool):
    return (functools.partial(_reduced_rows, act=act, bound=bound) if kernel
            else functools.partial(_held_rows, act=act, bound=bound,
                                   gather=gather),
            functools.partial(_fallback_rows, act=act))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _held_or_all_rows(x, experts, weights, counts, w1, w3, w2, act: str,
                      bound: int, gather: bool, kernel: bool):
    """:func:`_held_rows` where the batch's held assignments fit the
    bound, else :func:`_fallback_rows`: ONE conditional a direction.
    The backward pass makes the taken branch's forward again inside its
    own conditional, so that neither branch's residuals are outputs of
    a conditional (jax fills the other branch's with zeros: ``T * k``
    rows of them)."""
    return lax.cond(counts.sum() <= bound,
                    *_branches(act, bound, gather, kernel),
                    x, experts, weights, counts, w1, w3, w2)


def _held_or_all_rows_fwd(x, experts, weights, counts, w1, w3, w2, act,
                          bound, gather, kernel):
    return (_held_or_all_rows(x, experts, weights, counts, w1, w3, w2, act,
                              bound, gather, kernel),
            (x, experts, weights, counts, w1, w3, w2))


def _held_or_all_rows_bwd(act, bound, gather, kernel, res, g):
    x, experts, weights, counts, w1, w3, w2 = res

    def transposed(body):
        return lambda *floats: jax.vjp(
            lambda x, weights, *w: body(x, experts, weights, counts, *w),
            *floats)[1](g)

    # the barrier keeps what follows out of the branches: XLA moved the
    # update's float32 copy of each weight gradient INTO them, and the
    # conditional then held three float32 gradients where the update
    # reads three bfloat16 ones (the chip, PR 33: 14.6 ms a step of
    # converts and 0.6 GB)
    dx, dweights, dw1, dw3, dw2 = lax.optimization_barrier(lax.cond(
        counts.sum() <= bound,
        *map(transposed, _branches(act, bound, gather, kernel)),
        x, weights, w1, w3, w2))
    return dx, None, dweights, None, dw1, dw3, dw2


_held_or_all_rows.defvjp(_held_or_all_rows_fwd, _held_or_all_rows_bwd)


def expert_width_pad(backend: str, width: int) -> int:
    """The rule that says how many ZERO columns the experts' width
    takes on its way into the grouped matmuls, made of what the caller
    can observe: on the TPU, up to the next multiple of the kernels'
    128 lanes (1856 -> 1920: without them :func:`grouped_tiles`
    refuses the width and the products are libtpu's ``ragged-dot``
    kernels, whose time follows the rows that are real — seed by seed
    — and which ran at 6% of the experts' roofline: PERF.md section 6,
    PR 39); none elsewhere, and none for a width the lanes divide."""
    return -width % 128 if backend == "tpu" else 0


def _lane_padded(w1, w3, w2):
    """The experts' matrices with their width padded by
    :func:`expert_width_pad`'s zeros: every activation here maps 0 to
    0 and the padded rows of `w2` are zero, so the layer computes the
    same function and the gradient of the padding is dropped."""
    pad = expert_width_pad(jax.default_backend(), w1.shape[2])
    if not pad:
        return w1, w3, w2

    def wider(w):
        return jnp.pad(w, ((0, 0), (0, 0), (0, pad)))

    return (wider(w1), None if w3 is None else wider(w3),
            jnp.pad(w2, ((0, 0), (0, pad), (0, 0))))


def sorted_moe_ffn(x, route: TopKRoute, w1, w3: Optional[jnp.ndarray],
                   w2, act: str = "relu", bound: Optional[int] = None):
    """Drop-free MoE FFN on one device. x: [T, D] tokens; w1 (and the
    gate's w3, or None for an ungated expert): [E, D, F]; w2:
    [E, F, D]. Returns ``sum_k weight_k * expert_k(x)``, [T, D] in
    x's type: ``act(x W1_e) * (x W3_e)`` through ``W2_e`` when gated,
    ``act(x W1_e) W2_e`` when not. The products are
    :func:`grouped_matmul`'s; which path they took is counted once per
    traced call (pvars ``moe_grouped_kernel_layers`` /
    ``moe_ragged_dot_layers``). An assignment whose expert number is
    ``E`` or more (:func:`held_share`: another chip's expert) sorts
    past the last group and is computed by nobody.

    `bound` (:func:`held_rows_bound`; None: ``T * k``) is the static
    number of rows the layer carries. Under ``T * k`` only the first
    `bound` rows of the sort exist — gathered, multiplied and summed
    back per token — and a batch whose held assignments exceed it
    takes the layer over all ``T * k`` rows instead, exactly, inside
    one ``lax.cond``: no assignment is ever dropped. Counted once per
    traced call: ``moe_bounded_layers`` (a bound and its fallback) /
    ``moe_full_layers`` (all the rows, no second path: the same body
    at ``bound = T * k``, a token's rows fetched by the sort's inverse
    and added); and of the bounded ones ``moe_row_sum_gather_layers``
    (fetched and added likewise) / ``moe_row_sum_product_layers``
    (summed by a 0/1 product: :func:`row_sum_gathers`); and of those
    that fetch, ``moe_row_reduce_kernel_layers`` (the sums are
    ops/grouped_matmul.row_reduce's, from the packed layout the ``w2``
    product and the rows' gradient write: :func:`row_reduce_kernel`,
    :func:`_reduced_rows`) / ``moe_row_reduce_xla_layers`` (XLA's gather
    and reduction). On the TPU a width the kernels' lanes do not divide
    is padded with zeros on the way in (:func:`expert_width_pad`)."""
    t, k = route.experts.shape
    rows = t * k if bound is None else min(bound, t * k)
    w1, w3, w2 = _lane_padded(w1, w3, w2)
    # the rule reads K and N alike: what it says of w1 holds for w2
    pvar.record("moe_grouped_kernel_layers" if _tiles_of(
        jax.ShapeDtypeStruct((rows, x.shape[-1]), x.dtype), w1)
        else "moe_ragged_dot_layers")
    pvar.record("moe_bounded_layers" if rows < t * k else "moe_full_layers")
    gather = rows == t * k or row_sum_gathers(t, k, rows, x.shape[-1],
                                              x.dtype)
    backend = jax.default_backend()
    kernel = (gather and row_reduce_kernel(backend, t, k, rows, x.shape[-1],
                                           x.dtype)
              and w1.dtype == x.dtype == w2.dtype
              and reduce_tiles(backend, rows, x.shape[-1], w1.shape[2],
                               x.dtype, w3 is not None) is not None)
    if gather:
        pvar.record("moe_row_reduce_kernel_layers" if kernel
                    else "moe_row_reduce_xla_layers")
    if rows < t * k:
        pvar.record("moe_row_sum_gather_layers" if gather
                    else "moe_row_sum_product_layers")
        return _held_or_all_rows(x, route.experts, route.weights,
                                 route.counts, w1, w3, w2, act, rows, gather,
                                 kernel)
    return (_reduced_rows if kernel else functools.partial(
        _held_rows, gather=True))(x, route.experts, route.weights,
                                  route.counts, w1, w3, w2, act=act,
                                  bound=rows)
