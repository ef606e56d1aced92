"""Runner `mellum2_train`: a closed loop of single-chip train steps of
the `mellum2-12b-a2.5b` configuration.

The system under test is the program's own step,
`jax.jit(ompi_tpu.models.transformer.make_train_step(...), donate)` —
the entry every train cell uses — with the `Config` the published
config describes: kinds of attention mixed by layer (`layer_types`:
three layers of four attend inside a sliding window of 1,024 keys with
plain RoPE, the fourth over the whole causal triangle with YaRN's
blended frequencies and attention factor), 32 query heads over 4 shared
key heads of 128, every layer's feed-forward part a softmax top-8 of 64
SiLU-gated experts with the chosen probabilities renormalised, an
untied head, every layer recomputed in the backward pass. Everything
else is the benchmark's: weights and batches from --seed, the window,
the plain reference (reference/mellum2_decoder.py) and the comparison.
The window, the trace window named `train` and the first steps are
nemotron_train.py's, written again here because that file builds
Nemotron's `Config`, tree, reference and operation count by name.

What decides `correct`: olmoe_train.py's comparison (the losses of the
first steps; per-leaf movement after the first step and after the last
over the leaves that are not a router's; the routers' own gap against
gross faults; the first layer's routing compared as sets; no
assignment dropped, no loss that is not finite, the seed's tree made
again bit for bit) and what the mechanism adds, read on the first batch
from the seed's state:
- `swa_out_gap` and `full_out_gap`: the relative error of the first
  windowed layer's and of the first full layer's attention mixer (its
  norm, weights, mask and RoPE parameters) on the embedded batch
  (`transformer.attn_probe`) against the reference's. A window ignored,
  YaRN's frequencies or its factor on the wrong kind of layer, or key
  heads paired wrongly read far over any bfloat16 tolerance here. Both
  layers are read on the stream entering layer 0: in place, the full
  layer's reading carried the re-routed tokens of the three expert
  layers in front of it (0.05-0.26 over 13 sound toy seeds, the fp8
  control only 1.7 x above) and held no fault of its own.
- `window_leak_rows` (must read 0) and `window_edge_seen` (must read
  the number of positions tried, `window_edge_wanted`): a window one
  key too wide or too narrow moves a row by ~1/1,024 of its weight and
  hides inside every tolerance above, so it is held EXACTLY. The
  program's own attention entry (`ops.attention.attention` with the
  window: the kernels the step runs, at the timed shape [batch, T,
  heads, head_dim]) is run on seeded q, k, v, then again with v at one
  position p replaced by a large marker: the rows t >= p + W that differ
  in any bit are counted (none may), and row p + W - 1 must differ.
  Three positions p: inside a tile, on a tile's first row, on its last.
The reference starts only when the program's state is freed.
"""

from __future__ import annotations

import math
import os
import time

from benchmark import compare, flops_mellum2, weights, weights_mellum2
from benchmark.common import compile_requests, memory_stats, say
from benchmark.runners.glm5_train import (chosen_numbers,  # noqa: F401
                                          route_disagreement)
from benchmark.runners.kimivl_train import rows_gap as rel_err
from benchmark.runners.train_step import _stolen_s

SLIDING, FULL = "sliding_attention", "full_attention"

#: what the step's one trace counts of itself (the program's pvars)
TRACED = ("attn_blockwise_layers", "attn_reference_layers",
          "attn_gqa_layers", "attn_window_layers", "attn_full_layers",
          "attn_window_tiles", "attn_causal_tiles",
          "remat_kept_applications", "remat_whole_applications",
          "remat_kept_bytes", "moe_grouped_kernel_layers",
          "moe_ragged_dot_layers", "moe_full_layers", "moe_bounded_layers")


def model_sizes(config: dict) -> dict:
    """The configuration file speaks the source's language (Hugging
    Face keys, and the benchmark's own for what the source has none);
    the program, the reference and the counts want these."""
    ropes = config["rope_parameters"]
    return {
        "vocab": config["vocab_size"], "d_model": config["hidden_size"],
        "n_layers": config["num_hidden_layers"],
        # the source's list is kept whole: the cell holds the layers
        # its first `num_hidden_layers` entries name
        "layer_types": tuple(
            config["layer_types"][:config["num_hidden_layers"]]),
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "window": config["sliding_window"],
        "moe_d_ff": config["moe_intermediate_size"],
        "max_seq": config["max_position_embeddings"],
        "n_experts": config["num_experts"],
        "top_k": config["num_experts_per_tok"],
        "norm_topk_prob": config["norm_topk_prob"],
        "act": config["hidden_act"], "rms_eps": config["rms_norm_eps"],
        "tie_head": config["tie_word_embeddings"],
        "sliding_theta": float(ropes[SLIDING]["rope_theta"]),
        "yarn": tuple(float(ropes[FULL][k]) for k in (
            "rope_theta", "factor", "original_max_position_embeddings",
            "beta_fast", "beta_slow", "attention_factor")),
        "balance_weight": config["router_aux_loss_coef"],
        "z_weight": config["router_z_loss_coef"],
        "param_dtype": config["param_dtype"],
    }


def program_config(sizes: dict):
    """The program's description of this model (one of the imports of
    the system under test in this file). A program that lacks any of
    these fields cannot run the configuration and says so here, before
    anything is placed on the device."""
    import jax.numpy as jnp

    from ompi_tpu.models import transformer as tfm

    theta, factor, original, fast, slow, attention_factor = sizes["yarn"]
    letters = {SLIDING: tfm.WINDOWED, FULL: tfm.FULL}
    return tfm.Config(
        vocab=sizes["vocab"], d_model=sizes["d_model"],
        n_layers=sizes["n_layers"], n_heads=sizes["n_heads"],
        n_kv_heads=sizes["n_kv_heads"], head_width=sizes["head_dim"],
        max_seq=sizes["max_seq"], moe_every=1, moe_d_ff=sizes["moe_d_ff"],
        n_experts=sizes["n_experts"], top_k=sizes["top_k"],
        norm_topk_prob=sizes["norm_topk_prob"], mlp_act=sizes["act"],
        mlp_gated=True, norm="rmsnorm", norm_eps=sizes["rms_eps"],
        pos="rope", tie_head=sizes["tie_head"],
        attn_layers="".join(letters[k] for k in sizes["layer_types"]),
        attn_window=sizes["window"],
        rope_window=tfm.Rope(theta=sizes["sliding_theta"]),
        rope_full=tfm.Rope(theta=theta, factor=factor,
                           original_max=int(original), beta_fast=fast,
                           beta_slow=slow,
                           attention_factor=attention_factor),
        router_aux_weight=sizes["balance_weight"],
        router_z_weight=sizes["z_weight"], remat=True,
        param_dtype=jnp.dtype(sizes["param_dtype"]))


def build_step(sizes: dict, lr: float):
    """The program's jitted train step."""
    import jax

    from ompi_tpu.models import transformer as tfm

    cfg, ax = program_config(sizes), tfm.Axes()
    return jax.jit(
        tfm.make_train_step(cfg, ax, tfm.param_specs(cfg, ax), lr=lr),
        donate_argnums=(0,))


def reference_spec(sizes: dict):
    from benchmark.reference import mellum2_decoder as ref

    theta, factor, original, fast, slow, attention_factor = sizes["yarn"]
    return ref.Spec(
        layer_types=sizes["layer_types"], n_heads=sizes["n_heads"],
        n_kv_heads=sizes["n_kv_heads"], top_k=sizes["top_k"],
        window=sizes["window"], sliding_theta=sizes["sliding_theta"],
        yarn=ref.Yarn(theta, factor, int(original), fast, slow,
                      attention_factor),
        rms_eps=sizes["rms_eps"], norm_topk_prob=sizes["norm_topk_prob"])


def probed_layers(sizes: dict) -> dict:
    """The first layer of each kind of attention."""
    return {kind: sizes["layer_types"].index(kind)
            for kind in (SLIDING, FULL)}


def router_leaves(sizes: dict) -> list:
    """Per leaf of the parameter tree, in its flattened order: is it a
    router's matrix?"""
    import jax

    return ["'wg'" in jax.tree_util.keystr(path) for path, _ in
            jax.tree_util.tree_leaves_with_path(
                weights_mellum2.plan(sizes),
                is_leaf=lambda t: isinstance(t, tuple))]


def checks_against(prog, reference, limits, sizes) -> list:
    """`prog`, `reference`: (losses, per-leaf movement after the first
    step, after the last)."""
    import numpy as np

    (p_loss, p_first, p_last), (r_loss, r_first, r_last) = prog, reference
    routers = np.array(router_leaves(sizes))
    rest = ~routers

    def gap(how, a, b, which):
        return how(np.asarray(a)[which], np.asarray(b)[which])

    return [
        ("loss_gap", max(compare.rel_gap(a, b)
                         for a, b in zip(p_loss, r_loss)),
         limits["loss_gap"]),
        ("first_grad_norm_gap",
         gap(compare.worst_leaf_gap, p_first, r_first, rest),
         limits["first_grad_norm_gap"]),
        ("first_grad_norm_rms_gap",
         gap(compare.rms_leaf_gap, p_first, r_first, rest),
         limits["first_grad_norm_rms_gap"]),
        ("param_change_norm_gap",
         gap(compare.worst_leaf_gap, p_last, r_last, rest),
         limits["param_change_norm_gap"]),
        ("router_grad_norm_gap",
         gap(compare.worst_leaf_gap, p_first, r_first, routers),
         limits["router_grad_norm_gap"]),
    ]


def first_batch_checks(probe, reference_first, limits) -> list:
    """The first batch's three: the first windowed and the first full
    layer's attention output, layer 0's routing."""
    chosen, swa, full = reference_first
    return [
        ("swa_out_gap", rel_err(probe["swa_out"], swa),
         limits["swa_out_gap"]),
        ("full_out_gap", rel_err(probe["full_out"], full),
         limits["full_out_gap"]),
        ("route_disagreement", route_disagreement(probe["experts"], chosen),
         limits["route_disagreement"])]


def marker_positions(seq: int, window: int, tile) -> list:
    """Three positions p with p + window < seq: inside a tile of the
    kernels (of the window's own length where no kernel runs), on a
    tile's first row, on its last."""
    span = tile or window
    if 4 * span - 1 + window >= seq:
        span = max((seq - window) // 4, 1)
    return [2 * span + span // 2 + 1, 3 * span, 4 * span - 1]


def window_probe(sizes: dict, batch: int, seq: int, seed: int) -> dict:
    """The program's own attention entry under the window, at the timed
    shape, on seeded q, k, v — then with v at one position replaced by
    a marker: `window_leak_rows`, the rows at or past position + window
    that differ in any bit (over the positions tried), and
    `window_edge_seen`, the positions whose row position + window - 1
    differs, of `window_edge_wanted` tried."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ompi_tpu.ops import attention as att

    h, dh, w = sizes["n_heads"], sizes["head_dim"], sizes["window"]
    tile = att.blockwise_tile(jax.default_backend(), seq, seq, dh,
                              window=w)
    keys = jax.random.split(jax.random.fold_in(weights.seed_key(seed), 2), 3)
    q, k, v = (jax.random.normal(kk, (batch, seq, h, dh),
                                 jnp.float32).astype(jnp.bfloat16)
               for kk in keys)
    attend = jax.jit(lambda q, k, v: att.attention(q, k, v, causal=True,
                                                   window=w))
    differs = jax.jit(lambda a, b: (a != b).any((0, 2, 3)))
    mark = jax.jit(lambda v, p: v.at[:, p].set(jnp.asarray(3e4, v.dtype)))
    plain = attend(q, k, v)
    leak = seen = 0
    positions = marker_positions(seq, w, tile)
    for p in positions:
        rows = np.asarray(differs(plain, attend(q, k, mark(v, p))))
        leak += int(rows[p + w:].sum()) + int(rows[:p].sum())
        seen += int(rows[p + w - 1])
    return {"window_leak_rows": leak, "window_edge_seen": seen,
            "window_edge_wanted": len(positions),
            "window_probe_tile": tile or 0, "window_probe_at": positions}


def probes(sizes: dict, params, toks, steps: int, seed: int) -> dict:
    """The program's set-up probes on the seed's state. `route_counts`
    on the first `steps` batches: the worst shortfall of a layer's
    assignments against tokens x top_k, the fullest expert over the
    mean (worst layer, worst batch), what the program's counters
    gained. On the first batch: layer 0's choices, `attn_probe`'s
    output for the first layer of each kind. And `window_probe`."""
    import numpy as np

    from ompi_tpu.core import pvar
    from ompi_tpu.models import transformer as tfm

    cfg = program_config(sizes)
    names = ("moe_assignments", "moe_dropped_assignments")
    before = {n: pvar.read(n) for n in names}
    short, load = 0, 0.0
    for i in range(steps):
        counts = np.asarray(tfm.route_counts(params, toks[i], cfg))
        want = toks[i].size * sizes["top_k"]
        short = max(short, int(np.abs(counts.sum(1) - want).max()))
        load = max(load, float((counts.max(1) / counts.mean(1)).max()))
    out = {"route_counts_short": short, "load_max_over_mean": load,
           **{n: pvar.read(n) - v for n, v in before.items()}}
    out["experts"] = np.asarray(tfm.route_experts(params, toks[0], cfg)[0])
    at = probed_layers(sizes)
    out["swa_out"] = tfm.attn_probe(params, toks[0], cfg, at[SLIDING])
    out["full_out"] = tfm.attn_probe(params, toks[0], cfg, at[FULL])
    out.update(window_probe(sizes, *toks[0].shape, seed))
    return out


#: a probe's arrays, which no line prints
ARRAYS = ("experts", "swa_out", "full_out")


def reference_first_batch(sizes, toks, seed, quantize=None):
    """(layer 0's chosen experts [T, E], the first windowed layer's
    attention mixer output, the first full layer's) of the plain
    reference on the first batch, from the seed's state."""
    from benchmark.reference import mellum2_decoder as ref

    spec = reference_spec(sizes)
    params = weights_mellum2.device_init(sizes, seed)
    at = probed_layers(sizes)
    return (ref.chosen_experts(params, toks[0], spec, quantize),
            ref.attention_out(params, toks[0], at[SLIDING], spec, quantize),
            ref.attention_out(params, toks[0], at[FULL], spec, quantize))


def first_steps(step, params, toks, labs, sizes, seed, steps):
    """Drive the compiled step through its first steps from the seed's
    state: (state, (losses, per-leaf movement after the first step,
    after the last))."""
    losses, moved = [], {}
    for i in range(steps):
        params, loss = step(params, toks[i], labs[i])
        losses.append(float(loss))
        if i in (0, steps - 1):
            moved[i] = weights_mellum2.delta_norms(sizes, seed, params)
    return params, (losses, moved[0], moved[steps - 1])


def reference_steps(sizes, toks, labs, seed, lr, steps, quantize=None):
    """The plain reference through the same first steps: losses, and
    per-leaf movement after the first step and after the last."""
    from benchmark.reference import mellum2_decoder as ref

    spec = reference_spec(sizes)
    params = weights_mellum2.device_init(sizes, seed)
    losses, moved = [], {}
    for i in range(steps):
        params, val = ref.sgd_step(params, toks[i], labs[i], lr, spec,
                                   quantize)
        losses.append(float(val))
        if i in (0, steps - 1):
            moved[i] = weights_mellum2.delta_norms(sizes, seed, params)
    return losses, moved[0], moved[steps - 1]


def run(ctx) -> dict:
    import jax

    from ompi_tpu.core import pvar

    traffic = ctx.traffic
    sizes = model_sizes(ctx.config)
    program_config(sizes)  # a program without these fields stops here
    batch, seq, lr = traffic["batch"], traffic["seq"], traffic["lr"]
    n_check = traffic["check_steps"]
    tokens_per_step = batch * seq
    spans, counters = {}, {}

    # -- set-up: state, batches, the compiled step -------------------
    t = time.perf_counter()
    params = weights_mellum2.device_init(sizes, ctx.seed)
    toks, labs = weights.batches(sizes["vocab"], traffic["n_batches"],
                                 batch, seq, ctx.seed)
    jax.block_until_ready((params, toks, labs))
    # what the comparison rests on: the seed's tree made again leaf by
    # leaf is THIS tree, bit for bit
    remade = float(weights_mellum2.delta_norms(sizes, ctx.seed,
                                               params).max())
    spans["weights_s"] = time.perf_counter() - t
    n_params = sum(x.size for x in jax.tree.leaves(params))
    live = memory_stats().get("bytes_in_use", 0)
    say(f"config {sizes}; B={batch} T={seq} "
        f"tokens/step={tokens_per_step} params={n_params:,}")

    requests = compile_requests()
    traced = {n: pvar.read(n) for n in TRACED}
    t = time.perf_counter()
    step = build_step(sizes, lr).lower(params, toks[0], labs[0]).compile()
    spans["compile_s"] = time.perf_counter() - t
    for name, was in traced.items():  # what the step's ONE trace counted
        counters[name] = pvar.read(name) - was
    mem = step.memory_analysis()
    temp = int(getattr(mem, "temp_size_in_bytes", 0) or 0)
    say(f"step executable: temporaries {temp:,} B beside "
        f"{live:,} B live; compile {spans['compile_s']:.2f}s; its trace "
        f"counted {counters}")

    # -- the probes, on the seed's state -------------------------------
    t = time.perf_counter()
    probe = probes(sizes, params, toks, n_check, ctx.seed)
    spans["probes_s"] = time.perf_counter() - t
    counters["compile_requests_setup"] = requests[0]
    counters["moe_load_max_over_mean"] = probe["load_max_over_mean"]
    counters["moe_assignments"] = probe["moe_assignments"]
    say(f"probes on {n_check} batches: "
        f"{ {k: v for k, v in probe.items() if k not in ARRAYS} } "
        f"({spans['probes_s']:.2f}s)")

    # -- the first steps, through the window's own call and feed -----
    t = time.perf_counter()
    params, program = first_steps(step, params, toks, labs, sizes,
                                  ctx.seed, n_check)
    spans["first_steps_s"] = time.perf_counter() - t
    say(f"first {n_check} losses: {program[0]}")

    # -- the window ---------------------------------------------------
    nb = len(toks)
    trace_steps = traffic["trace_steps"] if ctx.trace else 0
    window_requests = requests[0]
    losses, ready, parts = [], [], []
    state = {"params": params, "i": n_check, "pending": None,
             "dispatch_s": 0.0}
    del params

    def dispatch():
        """Enqueue one step; return the loss of the one before it."""
        t = time.perf_counter()
        i = state["i"]
        state["params"], loss = step(state["params"], toks[i % nb],
                                     labs[i % nb])
        state["i"] = i + 1
        before, state["pending"] = state["pending"], loss
        state["dispatch_s"] = time.perf_counter() - t
        return before

    def settle(loss) -> None:
        """A step is complete when its loss is ready."""
        if loss is not None:
            t = time.perf_counter()
            jax.block_until_ready(loss)
            losses.append(loss)
            ready.append(time.perf_counter())
            # where the host spent the interval that ended here
            parts.append((state["dispatch_s"], ready[-1] - t))
            state["dispatch_s"] = 0.0

    def drain() -> None:
        settle(state["pending"])
        state["pending"] = None

    tr = ctx.tracer
    load0, cpu0, stolen0 = os.getloadavg(), time.process_time(), _stolen_s()
    t0 = time.perf_counter()
    ctx.window_opens()
    while True:
        if trace_steps and len(losses) == 2 and not tr.taken:
            drain()  # trace a few steps from an idle device
            tr.start()
            with tr.window("train"):
                for _ in range(trace_steps):
                    with tr.span("dispatch step"):
                        before = dispatch()
                    with tr.span("wait for loss"):
                        settle(before)
                with tr.span("wait for loss"):
                    drain()
            tr.stop()
        settle(dispatch())
        if time.perf_counter() - t0 >= ctx.seconds:
            drain()
            break
    window_s = time.perf_counter() - t0
    cpu_s, stolen_s = time.process_time() - cpu0, _stolen_s() - stolen0
    done = len(losses)
    counters["compiles_in_window"] = requests[0] - window_requests
    losses = [float(x) for x in losses]
    failed = sum(1 for x in losses if not math.isfinite(x))
    stats = memory_stats()
    peak = max(stats.get("peak_bytes_in_use", 0), live + temp)
    say(f"window: {done} steps in {window_s:.4f}s, "
        f"{tokens_per_step * done} tokens; loss {losses[0]:.4f} -> "
        f"{losses[-1]:.4f}; compile requests in window "
        f"{counters['compiles_in_window']}; memory_stats {stats}")
    gaps = [b - a for a, b in zip(ready, ready[1:])]
    if gaps and not ctx.trace:  # a stalled host or chip shows here
        at = max(range(len(gaps)), key=gaps.__getitem__)
        say(f"seconds between losses: median "
            f"{sorted(gaps)[len(gaps) // 2]:.4f} longest {gaps[at]:.4f} "
            f"(before loss {at + 2} of {done}: "
            f"{parts[at + 1][0]:.4f} in the dispatch of the next step, "
            f"{parts[at + 1][1]:.4f} waiting for the loss); this process used "
            f"{cpu_s:.2f}s of CPU in the window, {stolen_s:.2f}s of CPU "
            f"were stolen from the machine; host load average "
            f"{load0[0]:.2f} at its start, {os.getloadavg()[0]:.2f} at "
            "its end (information)")
    fl = flops_mellum2.train_flops_per_step(sizes, batch, seq)
    rate = tokens_per_step * done / window_s
    if ctx.peaks:
        say(f"{fl:.6g} FLOP/step required; model FLOP/s utilization "
            f"{100 * rate / tokens_per_step * fl / ctx.peaks['bf16_flops_per_s']:.2f}% of "
            f"{ctx.peaks['bf16_flops_per_s']:.3g} (information; "
            "end to end, not a kernel's roofline share)")

    # -- the reference, once the program's state is freed ------------
    state.clear()
    del step
    t = time.perf_counter()
    reference = reference_steps(sizes, toks, labs, ctx.seed, lr, n_check)
    first = reference_first_batch(sizes, toks, ctx.seed)
    spans["reference_s"] = time.perf_counter() - t
    say(f"reference losses: {reference[0]} "
        f"({spans['reference_s']:.1f}s, not in setup_s)")
    checks = checks_against(program, reference, ctx.limits, sizes) \
        + first_batch_checks(probe, first, ctx.limits)
    checks += [("window_leak_rows", probe["window_leak_rows"], 0),
               ("window_edge_missed",
                probe["window_edge_wanted"] - probe["window_edge_seen"], 0),
               ("seed_tree_remade_gap", remade, 0),
               ("nonfinite_window_losses", failed, 0),
               ("route_counts_short", probe["route_counts_short"], 0),
               ("moe_dropped_assignments",
                probe["moe_dropped_assignments"], 0)]

    return {
        "end_to_end": {"tokens_per_s": rate},
        "attempted": done, "failed": failed, "checks": checks,
        "spans": spans, "counters": counters,
        "memory_peak_bytes": peak,
        "facts": {"flops_per_step": fl,
                  "flops_per_token": fl / tokens_per_step,
                  "moe_experts_flops_per_step":
                      flops_mellum2.expert_flops_per_step(sizes, batch, seq),
                  "swa_attn_flops_per_step":
                      flops_mellum2.swa_attn_flops_per_step(sizes, batch,
                                                            seq),
                  "full_attn_flops_per_step":
                      flops_mellum2.full_attn_flops_per_step(sizes, batch,
                                                             seq),
                  "swa_kept_pairs_per_layer":
                      flops_mellum2.window_pairs(seq, sizes["window"])
                      * batch,
                  "seq": seq, "steps": done,
                  "tokens_per_step": tokens_per_step, "window_s": window_s},
    }
