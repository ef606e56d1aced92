"""What a Config's layers are made of, described once.

Three questions of models/transformer.py have ONE answer here: which
kind each layer is (`_is_moe`, `_layer_kind`, `_mtp_kind`: the block's
two — each under a sliding window or not, or with a delta-rule mixer
in attention's place, where a config mixes kinds of attention, `Block`
—, a pattern's three letters); which sub-layers a layer of a kind has
(`layout`: the rows `layer_forward` walks, the recomputation rule sums
over and the parameter tree is built from); and every leaf of the
parameter tree — its path, its shape, how it is initialised and how an
axis shards it (`Leaf`, in the order the seed's generator is drawn
from). `init_params`, `param_specs` and `grad_extra_axes` are three
readers of that one description. A `cfg` is a models/transformer.py
`Config`, an `ax` its `Axes`: read by attribute, neither imported.
"""

from __future__ import annotations

import math
from typing import Dict, NamedTuple, Optional, Tuple

import numpy as np

from ompi_tpu.models import vision
from ompi_tpu.models.remat import ATTN_PROJ_OUT, MLP_OUT


def _is_moe(cfg, layer: int) -> bool:
    if cfg.first_dense is not None:
        return layer >= cfg.first_dense
    return cfg.moe_every > 0 and (layer + 1) % cfg.moe_every == 0


#: the letters of `Config.layer_pattern`: a layer's kind beside the
#: block's two (False: attention then a dense FFN, True: attention
#: then a mixture of experts)
SSM, EXPERTS, ATTENTION = "M", "E", "*"


#: the letters of `Config.attn_layers`: a block's attention attends
#: inside the sliding window, or over the whole causal triangle — or
#: the block's first sub-layer is no softmax attention but the
#: delta-rule linear mixer (ops/kda.py)
WINDOWED, FULL, DELTA = "w", "f", "d"


class Block(NamedTuple):
    """A block's kind where a config mixes kinds of attention
    (`Config.attn_layers`): whether its feed-forward part is a mixture
    of experts, whether its attention is under the window, and whether
    the delta-rule mixer stands in attention's place."""
    moe: bool
    windowed: bool
    delta: bool = False


def _layer_kind(cfg, layer: int):
    """What layer `layer` is: its letter where the config has a
    pattern, else whether the block's feed-forward part is a mixture
    of experts — and, where the config mixes kinds of attention, a
    `Block` that says which kind this layer's is besides."""
    if cfg.layer_pattern is not None:
        _check_pattern(cfg)
        return cfg.layer_pattern[layer]
    if cfg.attn_layers is None:
        return _is_moe(cfg, layer)
    _check_attn_layers(cfg)
    letter = cfg.attn_layers[layer]
    return Block(_is_moe(cfg, layer), letter == WINDOWED, letter == DELTA)


def _mtp_kind(cfg):
    """What the layer of a multi-token-prediction module is: an expert
    layer or a dense one as a layer after the last would be — and,
    where the config mixes kinds of attention, a `Block` whose
    attention is the kind `Config.mtp_attn` names (the source's
    mtp_layer_types), whatever the trunk's last layer is."""
    moe = _is_moe(cfg, cfg.n_layers)
    if cfg.attn_layers is None:
        return moe
    _check_attn_layers(cfg)
    return Block(moe, cfg.mtp_attn == WINDOWED)


#: `Config.qk_norm` where each head of q and of k is normed alone
#: (True: OLMoE's, over the whole projection)
PER_HEAD = "head"


def _check_attn_layers(cfg):
    kinds = cfg.attn_layers
    if cfg.mtp_layers and cfg.mtp_attn not in (WINDOWED, FULL):
        raise ValueError(
            f"mtp_attn={cfg.mtp_attn!r}: where a config mixes kinds of "
            "attention (Config.attn_layers) the multi-token-prediction "
            f"module's own is {WINDOWED!r} or {FULL!r} (the source's "
            "mtp_layer_types; a delta-rule module is not written)")
    if len(kinds) != cfg.n_layers or set(kinds) - {WINDOWED, FULL, DELTA}:
        raise ValueError(
            f"attn_layers={kinds!r}: expected n_layers = {cfg.n_layers} "
            f"letters of {WINDOWED!r} (attention inside the sliding "
            f"window), {FULL!r} (over the whole causal triangle) and "
            f"{DELTA!r} (the delta-rule linear mixer)")
    if DELTA in kinds and not (cfg.kda_heads and cfg.kda_head_dim):
        raise ValueError(
            f"attn_layers={kinds!r} has delta-rule layers and kda_heads="
            f"{cfg.kda_heads} heads of kda_head_dim={cfg.kda_head_dim} "
            "are none")
    if WINDOWED in kinds and cfg.attn_window < 1:
        raise ValueError(
            f"attn_layers={kinds!r} has layers under a sliding window "
            f"and attn_window={cfg.attn_window} keys is none")


def _check_pattern(cfg):
    pattern = cfg.layer_pattern
    if len(pattern) != cfg.n_layers or set(pattern) - {SSM, EXPERTS,
                                                       ATTENTION}:
        raise ValueError(
            f"layer_pattern={pattern!r}: expected n_layers = "
            f"{cfg.n_layers} letters of {SSM!r} (a Mamba-2 mixer), "
            f"{EXPERTS!r} (experts) and {ATTENTION!r} (attention); a "
            "dense FFN alone ('-') is not written")


def _held_count(cfg) -> int:
    return cfg.held_experts[1] if cfg.held_experts else cfg.n_experts


def _check_indexer(cfg):
    if cfg.attn == "mla" and cfg.index_topk and not cfg.q_lora_rank:
        raise NotImplementedError(
            "the sparse-attention indexer (index_topk) reads the query "
            "latent, which a config with q_lora_rank 0 does not have")


# -- a layer is a short list of sub-layers -----------------------------------

class Sub(NamedTuple):
    """One sub-layer, ``h + [norm](mixer(norm(h)))``: the mixer (a key
    of `MIXERS`, of transformer.py's `_COSTS` and of the forward
    functions its `_sublayer` chooses among), the leaf names of the
    norm in front and of the norm on the output (None: none), the name
    the output carries for the recomputation rule (None: nothing reads
    it again, so it has none) and the scopes (``jax.named_scope``,
    outermost first) the residual add lies in."""
    mixer: str
    pre: str
    post: Optional[str]
    name: Optional[str]
    scopes: Tuple[str, ...]


def layout(cfg, kind) -> Tuple[Sub, ...]:
    """The sub-layers of a layer of `kind` (`_layer_kind`). The block
    is attention (of the config's `attn`; under the sliding window, or
    the delta-rule mixer in its place, where a `Block` says so) then a
    dense FFN or the experts, each output named and added under its
    mixer's scope. A
    pattern's letter is ONE sub-layer; its output is the layer's, which
    the next layer's input holds anyway, so it has no name, and its add
    lies where PR 39 measured it: the experts' under ``mlp``, the other
    two at the layer's own level."""
    if cfg.layer_pattern is not None:
        mixer = {SSM: "ssm", ATTENTION: "attention", EXPERTS: "experts"}[kind]
        return (Sub(mixer, "ln", None, None,
                    ("mlp",) if kind == EXPERTS else ()),)
    moe, windowed, delta = kind if isinstance(kind, Block) \
        else (kind, False, False)
    post = ("ln1_post", "ln2_post") if cfg.post_norm else (None, None)
    return (Sub("mla", "ln1", post[0], ATTN_PROJ_OUT, ("attn_proj", "mla_o"))
            if cfg.attn == "mla" else
            Sub("kda", "ln1", post[0], ATTN_PROJ_OUT, ("kda", "kda_proj"))
            if delta else
            Sub("window_attention" if windowed else "attention", "ln1",
                post[0], ATTN_PROJ_OUT, ("attn_proj",)),
            Sub("experts" if moe else "ffn", "ln2", post[1], MLP_OUT,
                ("mlp",)))


# -- the leaves ---------------------------------------------------------------

#: how an axis shards a leaf. REPLICATED: not at all. COLUMN / ROW: a
#: matrix of the tp region by its output / its input dimension
#: (Megatron's pair). EXPERT_COLUMN / EXPERT_ROW: the same behind a
#: leading [held experts] dimension, which ep shards. ROUTER: the MoE
#: router, replicated yet INSIDE the tp region (`grad_extra_axes`)
REPLICATED, COLUMN, ROW = "replicated", "column", "row"
EXPERT_COLUMN, EXPERT_ROW, ROUTER = "expert_column", "expert_row", "router"


class Leaf(NamedTuple):
    """One leaf of the parameter tree: its path from the subtree's
    root, its shape, its initialisation — a float: standard normal
    times that scale; ONES, ZEROS; A_LOG, DT_BIAS: the Mamba-2 family's
    own draws — and its sharding role."""
    path: Tuple[str, ...]
    shape: Tuple[int, ...]
    init: object
    role: str = REPLICATED


ONES, ZEROS, A_LOG, DT_BIAS = "ones", "zeros", "a_log", "dt_bias"


def _norm_leaves(cfg, name: str):
    """The config's norm: a gain, and a bias where it is a LayerNorm."""
    yield Leaf((name, "g"), (cfg.d_model,), ONES)
    if cfg.norm != "rmsnorm":
        yield Leaf((name, "b"), (cfg.d_model,), ZEROS)


def _attention_leaves(cfg):
    d, s_emb = cfg.d_model, 1.0 / math.sqrt(cfg.d_model)
    wide = cfg.n_heads * cfg.head_dim
    narrow = (cfg.n_kv_heads or cfg.n_heads) * cfg.head_dim
    yield Leaf(("wq",), (d, wide), s_emb, COLUMN)
    yield Leaf(("wk",), (d, narrow), s_emb, COLUMN)
    yield Leaf(("wv",), (d, narrow), s_emb, COLUMN)
    yield Leaf(("wo",), (wide, d), 1.0 / math.sqrt(wide)
               / math.sqrt(2 * cfg.n_layers), ROW)
    if cfg.qk_norm == PER_HEAD:  # one gain for every head, gain only
        yield Leaf(("q_norm", "g"), (cfg.head_dim,), ONES)
        yield Leaf(("k_norm", "g"), (cfg.head_dim,), ONES)
    elif cfg.qk_norm:  # over the WHOLE projection, gain only
        yield Leaf(("q_norm", "g"), (wide,), ONES)
        yield Leaf(("k_norm", "g"), (narrow,), ONES)
    if cfg.attn_gate:  # the output gate, one number a head and channel
        yield Leaf(("wa",), (d, wide), s_emb, COLUMN)


def _mla_leaves(cfg):  # replicated: no tp path yet
    _check_indexer(cfg)
    d, s_emb = cfg.d_model, 1.0 / math.sqrt(cfg.d_model)
    h, rq, rkv = cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    if not rq:
        yield Leaf(("wq",), (d, h * qk), s_emb)
    else:
        yield Leaf(("wq_a",), (d, rq), s_emb)
        yield Leaf(("q_a_norm", "g"), (rq,), ONES)
        yield Leaf(("wq_b",), (rq, h * qk), 1.0 / math.sqrt(rq))
    yield Leaf(("wkv_a",), (d, rkv + cfg.qk_rope_dim), s_emb)
    yield Leaf(("kv_a_norm", "g"), (rkv,), ONES)
    yield Leaf(("wkv_b",), (rkv, h * (cfg.qk_nope_dim + cfg.v_head_dim)),
               1.0 / math.sqrt(rkv))
    yield Leaf(("wo",), (h * cfg.v_head_dim, d),
               1.0 / math.sqrt(h * cfg.v_head_dim)
               / math.sqrt(2 * cfg.n_layers))
    if cfg.index_topk:
        yield Leaf(("wi_q",), (rq, cfg.index_heads * cfg.index_dim),
                   1.0 / math.sqrt(rq))
        yield Leaf(("wi_k",), (d, cfg.index_dim), s_emb)
        yield Leaf(("wi_k_norm", "g"), (cfg.index_dim,), ONES)
        yield Leaf(("wi_k_norm", "b"), (cfg.index_dim,), ZEROS)
        yield Leaf(("wi_w",), (d, cfg.index_heads), s_emb)


def _ffn_leaves(cfg, width: int, prefix: str = "w", held=(),
                roles=(COLUMN, ROW)):
    """A feed-forward part's matrices `prefix`1 [, `prefix`3 where the
    config gates] and `prefix`2, behind `held`'s dimension."""
    d, s_emb = cfg.d_model, 1.0 / math.sqrt(cfg.d_model)
    yield Leaf((prefix + "1",), (*held, d, width), s_emb, roles[0])
    if cfg.mlp_gated:
        yield Leaf((prefix + "3",), (*held, d, width), s_emb, roles[0])
    yield Leaf((prefix + "2",), (*held, width, d), 1.0 / math.sqrt(width),
               roles[1])


def _experts_leaves(cfg):
    yield Leaf(("wg",), (cfg.d_model, cfg.n_experts),
               1.0 / math.sqrt(cfg.d_model), ROUTER)
    if cfg.router_bias:
        yield Leaf(("wg_bias",), (cfg.n_experts,), 0.01)
    # experts carry a leading [held experts] dimension
    yield from _ffn_leaves(cfg, cfg.expert_d_ff, held=(_held_count(cfg),),
                           roles=(EXPERT_COLUMN, EXPERT_ROW))
    if cfg.shared_width:  # a dense FFN inside the tp region
        yield from _ffn_leaves(cfg, cfg.shared_width, prefix="ws")


def _ssm_leaves(cfg):  # replicated: no tp, sp, ep or pp path
    d, heads, inner = cfg.d_model, cfg.ssm_heads, cfg.ssm_inner
    k = cfg.ssm_conv
    # the family's initialisation: decays of a trained model, not all
    # ~1 or ~0. The step sizes are drawn FIRST, as they always were
    yield Leaf(("dt_bias",), (heads,), DT_BIAS)
    yield Leaf(("in_proj",), (d, inner + cfg.ssm_conv_width + heads),
               1.0 / math.sqrt(d))
    yield Leaf(("conv_w",), (cfg.ssm_conv_width, k), 1.0 / math.sqrt(k))
    yield Leaf(("conv_b",), (cfg.ssm_conv_width,), 1.0 / math.sqrt(k))
    yield Leaf(("A_log",), (heads,), A_LOG)
    yield Leaf(("D",), (heads,), ONES)
    yield Leaf(("ssm_norm", "g"), (inner,), ONES)
    yield Leaf(("out_proj",), (inner, d), 1.0 / math.sqrt(inner))


def _kda_leaves(cfg):  # replicated: no tp, sp, ep or pp path
    d, s_emb = cfg.d_model, 1.0 / math.sqrt(cfg.d_model)
    heads, wide = cfg.kda_heads, cfg.kda_heads * cfg.kda_head_dim
    rank, k = cfg.kda_rank or cfg.kda_head_dim, cfg.kda_conv
    for name in ("q", "k", "v"):
        yield Leaf(("w" + name,), (d, wide), s_emb)
        yield Leaf(("conv_" + name,), (wide, k), 1.0 / math.sqrt(k))
    # the decay through its bottleneck, initialised as the scan's of a
    # state-space layer: decays of a trained model, not all ~1 or ~0
    yield Leaf(("w_fa",), (d, rank), s_emb)
    yield Leaf(("w_fb",), (rank, wide), 1.0 / math.sqrt(rank))
    yield Leaf(("dt_bias",), (wide,), DT_BIAS)
    yield Leaf(("A_log",), (heads,), A_LOG)
    yield Leaf(("w_b",), (d, heads), s_emb)
    yield Leaf(("o_norm", "g"), (cfg.kda_head_dim,), ONES)
    yield Leaf(("w_ga",), (d, rank), s_emb)
    yield Leaf(("w_gb",), (rank, wide), 1.0 / math.sqrt(rank))
    yield Leaf(("wo",), (wide, d), 1.0 / math.sqrt(wide)
               / math.sqrt(2 * cfg.n_layers))


#: a mixer's leaves, by `Sub.mixer`
MIXERS = {"attention": _attention_leaves,
          "window_attention": _attention_leaves, "mla": _mla_leaves,
          "kda": _kda_leaves,
          "ffn": lambda cfg: _ffn_leaves(cfg, cfg.d_ff),
          "experts": _experts_leaves, "ssm": _ssm_leaves}


def _layer_leaves(cfg, kind):
    """A layer's leaves: per sub-layer of its `layout` the norm in
    front, the norm on the output where there is one, the mixer's."""
    for sub in layout(cfg, kind):
        yield from _norm_leaves(cfg, sub.pre)
        if sub.post:
            yield from _norm_leaves(cfg, sub.post)
        yield from MIXERS[sub.mixer](cfg)


def _top_leaves(cfg):
    d, s_emb = cfg.d_model, 1.0 / math.sqrt(cfg.d_model)
    yield Leaf(("embed",), (cfg.vocab, d), s_emb)
    if cfg.pos == "learned":
        yield Leaf(("pos",), (cfg.max_seq, d), 0.02)
    if not cfg.tie_head:
        yield Leaf(("head",), (cfg.vocab, d), s_emb)
    yield from _norm_leaves(cfg, "ln_f")
    if cfg.exit_gate:
        yield Leaf(("exit_gate", "w"), (d,), s_emb)
        yield Leaf(("exit_gate", "b"), (1,), ZEROS)


def _mtp_leaves(cfg):
    """A multi-token-prediction module: one more layer (`_mtp_kind`),
    the two norms and the merging product in front."""
    d = cfg.d_model
    yield from _layer_leaves(cfg, _mtp_kind(cfg))
    yield from _norm_leaves(cfg, "enorm")
    yield from _norm_leaves(cfg, "hnorm")
    yield Leaf(("eh_proj",), (2 * d, d), 1.0 / math.sqrt(2 * d))


def _tree(cfg, value, tower) -> Dict:
    """init_params' tree: `value(leaf)` at every leaf of the decoder,
    asked in the order the leaves are described in, and `tower()` (of
    models/vision.py's own description) as the vision tower's subtree."""
    def nested(leaves):
        tree: Dict = {}
        for leaf in leaves:
            node = tree
            for key in leaf.path[:-1]:
                node = node.setdefault(key, {})
            node[leaf.path[-1]] = value(leaf)
        return tree

    tree = nested(_top_leaves(cfg))
    tree["layers"] = [nested(_layer_leaves(cfg, _layer_kind(cfg, i)))
                      for i in range(cfg.n_layers)]
    if cfg.mtp_layers:
        tree["mtp"] = [nested(_mtp_leaves(cfg))
                       for _ in range(cfg.mtp_layers)]
    if cfg.vision is not None:
        tree["vision"] = tower()
    return tree


# -- the three readers --------------------------------------------------------

def init_params(rng: np.random.Generator, cfg) -> Dict:
    """Full (unsharded) parameters, host-side numpy. Sharding happens at
    the jit boundary via param_specs (the driver of HtoD layout)."""
    pdt = np.dtype(cfg.param_dtype)

    def draw(leaf: Leaf):
        if leaf.init == ONES:
            return np.ones(leaf.shape, pdt)
        if leaf.init == ZEROS:
            return np.zeros(leaf.shape, pdt)
        if leaf.init == A_LOG:
            return np.log(rng.uniform(1.0, 16.0, leaf.shape)).astype(pdt)
        if leaf.init == DT_BIAS:  # the inverse softplus of the step size
            dt = np.exp(rng.uniform(math.log(1e-3), math.log(0.1),
                                    leaf.shape))
            return (dt + np.log(-np.expm1(-dt))).astype(pdt)
        return np.asarray(rng.standard_normal(leaf.shape) * leaf.init,
                          dtype=pdt)

    return _tree(cfg, draw, lambda: vision.init_params(
        rng, cfg.vision, cfg.d_model, pdt))


def param_specs(cfg, ax):
    """PartitionSpec pytree matching init_params' structure.

    tp shards: wq/wk/wv on output dim (column parallel), wo on input dim
    (row parallel), dense w1/w2 likewise. ep shards MoE experts on dim 0.
    Everything else replicated.
    """
    from jax.sharding import PartitionSpec as P

    specs = {REPLICATED: P(), ROUTER: P(),
             COLUMN: P(None, ax.tp), ROW: P(ax.tp, None),
             EXPERT_COLUMN: P(ax.ep, None, ax.tp),
             EXPERT_ROW: P(ax.ep, ax.tp, None)}
    return _tree(cfg, lambda leaf: specs[leaf.role],
                 lambda: vision.like_params(cfg.vision, P()))


def grad_extra_axes(cfg, ax):
    """Extra grad-psum axes per param, same structure as init_params.

    The MoE router wg is replicated yet lives *inside* the tp region
    (its cotangent arrives partial, via the combine-weights path through
    the tp-sharded expert outputs), so unlike other replicated params it
    needs an explicit psum over tp."""
    # leaves are axis-name strings ("" = none): strings are pytree
    # leaves, so the tree composes with tree.flatten_up_to cleanly
    none = ""
    return _tree(
        cfg, lambda leaf: (ax.tp or none) if leaf.role == ROUTER else none,
        lambda: vision.like_params(cfg.vision, none))
