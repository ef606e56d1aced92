"""Runner `kexaone_train`: a closed loop of single-chip train steps of
the `k-exaone-236b-a23b` configuration.

The system under test is the program's own step,
`jax.jit(ompi_tpu.models.transformer.make_train_step(...), donate)` —
the entry every train cell uses — with the `Config` the published
config describes: kinds of attention mixed by layer (`layer_types`:
three layers of four attend inside a sliding window of 128 keys and
turn by RoPE, the fourth over the whole causal triangle WITHOUT
positions), 64 query heads over 8 shared key heads of 128 with an
RMSNorm on every head of q and of k, a leading dense layer then expert
layers whose sigmoid router scores all 128 experts while this chip
holds 8 of them and the shared one, a multi-token-prediction module
whose layer is of a kind of its own (`mtp_layer_types`: full attention,
behind a trunk whose last layer is windowed), every layer recomputed in
the backward pass. Everything else is the benchmark's: weights and
batches from --seed, the window, the plain reference
(reference/kexaone_decoder.py) and the comparison. The window, the
trace window named `train` and the first steps are mellum2_train.py's,
written again here because that file builds Mellum2's `Config`, tree,
reference and operation count by name.

What decides `correct`: glm5_train.py's comparison (the losses of the
first steps, main + MTP; per-leaf movement after the first step and
after the last over the leaves that are not a router's; the routers'
own gap against gross faults; the first expert layer's routing compared
as sets; no assignment dropped, no loss that is not finite, the seed's
tree made again bit for bit) and what the mechanism adds, read on the
first batch from the seed's state:
- `swa_out_gap`, `full_out_gap`, `mtp_out_gap`: the relative error of
  the first windowed layer's, the first full layer's and the MTP
  module's attention mixer (its norm, weights, per-head QK-norm, mask
  and rotation or none) on the embedded batch (`transformer.attn_probe`,
  through the timed kernels) against the reference's. A window ignored,
  a rotation on the kind that takes none (or none on the kind that
  rotates), a norm over the whole projection in place of one per head,
  or the module under the window read far over any bfloat16 tolerance
  here.
- `window_leak_rows` (must read 0) and `window_edge_missed` (must read
  0): mellum2_train.py's exact check of the window's two edges
  (`window_probe`, imported: it reads the heads, their width and the
  window from `sizes` and builds no configuration) through the
  program's own attention entry at the timed shape, at three positions
  for the tile the rule chose: inside a tile, on a tile's first row,
  on its last.
The reference starts only when the program's state is freed.
"""

from __future__ import annotations

import math
import os
import time

from benchmark import compare, flops_kexaone, weights, weights_kexaone
from benchmark.common import compile_requests, memory_stats, say
from benchmark.runners.glm5_train import (chosen_numbers,  # noqa: F401
                                          route_disagreement)
from benchmark.runners.kimivl_train import rows_gap as rel_err
from benchmark.runners.mellum2_train import (marker_positions,  # noqa: F401
                                             window_probe)
from benchmark.runners.train_step import _stolen_s

SLIDING, FULL = "sliding_attention", "full_attention"

#: what the step's one trace counts of itself (the program's pvars),
#: every one copied BY NAME into the run's `counters`
TRACED = ("attn_blockwise_layers", "attn_reference_layers",
          "attn_gqa_layers", "attn_window_layers", "attn_full_layers",
          "attn_window_tiles", "attn_causal_tiles",
          "attn_head_norm_layers", "attn_unrotated_layers",
          "mtp_full_layers", "mtp_window_layers",
          "remat_kept_applications", "remat_whole_applications",
          "remat_kept_bytes", "moe_grouped_kernel_layers",
          "moe_ragged_dot_layers", "moe_full_layers", "moe_bounded_layers",
          "moe_row_sum_gather_layers", "moe_row_sum_product_layers")


def model_sizes(config: dict) -> dict:
    """The configuration file speaks the source's language (Hugging
    Face keys, and the benchmark's own for what the source has none);
    the program, the reference and the counts want these."""
    n = config["num_hidden_layers"]
    (mtp_type,) = config["mtp_layer_types"]
    return {
        "vocab": config["vocab_size"], "d_model": config["hidden_size"],
        "n_layers": n,
        # the source's lists are kept whole: the cell holds the layers
        # their first `num_hidden_layers` entries name
        "layer_types": tuple(config["layer_types"][:n]),
        "first_dense": config["first_k_dense_replace"],
        "mtp_layer_type": mtp_type,
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "window": config["sliding_window"],
        "d_ff": config["intermediate_size"],
        "moe_d_ff": config["moe_intermediate_size"],
        "max_seq": config["max_position_embeddings"],
        # the router scores the published number of experts; this chip
        # holds `num_experts` of them, from `held_first`
        "n_experts": config["router_experts"],
        "held_first": config["held_first"],
        "held_count": config["num_experts"],
        "top_k": config["num_experts_per_tok"],
        "norm_topk_prob": config["norm_topk_prob"],
        "routed_scale": config["routed_scaling_factor"],
        "n_shared_experts": config["num_shared_experts"],
        "router_bias": config["router_bias"],
        "act": config["hidden_act"], "rms_eps": config["rms_norm_eps"],
        "tie_head": config["tie_word_embeddings"],
        "rope_theta": float(config["rope_parameters"]["rope_theta"]),
        # the benchmark's own keys for what the source has none
        "qk_norm": config["qk_norm"], "rope_on": tuple(config["rope_on"]),
        "mtp_layers": config["num_nextn_predict_layers"],
        "mtp_weight": config["mtp_loss_weight"],
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

    letters = {SLIDING: tfm.WINDOWED, FULL: tfm.FULL}
    ropes = {kind: None if kind in sizes["rope_on"] else tfm.NO_ROPE
             for kind in letters}
    return tfm.Config(
        vocab=sizes["vocab"], d_model=sizes["d_model"],
        n_layers=sizes["n_layers"], n_heads=sizes["n_heads"],
        n_kv_heads=sizes["n_kv_heads"], head_width=sizes["head_dim"],
        d_ff=sizes["d_ff"], max_seq=sizes["max_seq"],
        first_dense=sizes["first_dense"], moe_d_ff=sizes["moe_d_ff"],
        n_experts=sizes["n_experts"], top_k=sizes["top_k"],
        norm_topk_prob=sizes["norm_topk_prob"], router_score="sigmoid",
        router_bias=sizes["router_bias"], routed_scale=sizes["routed_scale"],
        n_shared_experts=sizes["n_shared_experts"],
        held_experts=(sizes["held_first"], sizes["held_count"]),
        mlp_act=sizes["act"], mlp_gated=True, norm="rmsnorm",
        norm_eps=sizes["rms_eps"], pos="rope", rope_theta=sizes["rope_theta"],
        qk_norm=sizes["qk_norm"], tie_head=sizes["tie_head"],
        attn_layers="".join(letters[k] for k in sizes["layer_types"]),
        attn_window=sizes["window"], rope_window=ropes[SLIDING],
        rope_full=ropes[FULL], mtp_layers=sizes["mtp_layers"],
        mtp_weight=sizes["mtp_weight"],
        mtp_attn=letters[sizes["mtp_layer_type"]],
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
    from benchmark.reference import kexaone_decoder as ref

    if sizes["qk_norm"] != "head" or sizes["rope_on"] != (SLIDING,):
        raise ValueError("the reference is written for the family's "
                         "convention: qk_norm 'head', rope_on sliding")
    return ref.Spec(
        layer_types=sizes["layer_types"],
        mtp_layer_type=sizes["mtp_layer_type"], n_heads=sizes["n_heads"],
        n_kv_heads=sizes["n_kv_heads"], top_k=sizes["top_k"],
        window=sizes["window"], theta=sizes["rope_theta"],
        held_first=sizes["held_first"], routed_scale=sizes["routed_scale"],
        rms_eps=sizes["rms_eps"], mtp_weight=sizes["mtp_weight"])


def probed_layers(sizes: dict) -> dict:
    """The first layer of each kind of attention, and the MTP module
    (the layer after the last)."""
    return {"swa": sizes["layer_types"].index(SLIDING),
            "full": sizes["layer_types"].index(FULL),
            "mtp": sizes["n_layers"]}


def router_leaves(sizes: dict) -> list:
    """Per leaf of the parameter tree, in its flattened order: is it a
    router's (its matrix or its bias)?"""
    import jax

    return ["'wg" in jax.tree_util.keystr(path) for path, _ in
            jax.tree_util.tree_leaves_with_path(
                weights_kexaone.plan(sizes),
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


#: the three attention mixers read on the embedded batch
OUTS = ("swa", "full", "mtp")
#: a probe's arrays, which no line prints
ARRAYS = ("experts",) + tuple(k + "_out" for k in OUTS)


def first_batch_checks(probe, reference_first, limits) -> list:
    """The first batch's four: the first windowed layer's, the first
    full layer's and the MTP module's attention output, the first
    expert layer's routing."""
    chosen, outs = reference_first
    return [(k + "_out_gap", rel_err(probe[k + "_out"], outs[k]),
             limits[k + "_out_gap"]) for k in OUTS] + [
        ("route_disagreement", route_disagreement(probe["experts"], chosen),
         limits["route_disagreement"])]


def probes(sizes: dict, params, toks, steps: int, seed: int) -> dict:
    """The program's set-up probes on the seed's state. `route_counts`
    on the first `steps` batches: the worst shortfall of a layer's
    assignments against tokens x top_k, the fullest expert over the
    mean (worst layer, worst batch), the assignments that fell to the
    held experts a batch (all expert layers of the trunk), what the
    program's counters gained. On the first batch: the first expert
    layer's choices, `attn_probe`'s output for the first layer of each
    kind and for the MTP module. And `window_probe`."""
    import numpy as np

    from ompi_tpu.core import pvar
    from ompi_tpu.models import transformer as tfm

    cfg = program_config(sizes)
    names = ("moe_assignments", "moe_dropped_assignments",
             "moe_held_assignments", "moe_over_bound_layers")
    before = {n: pvar.read(n) for n in names}
    short, load = 0, 0.0
    for i in range(steps):
        counts = np.asarray(tfm.route_counts(params, toks[i], cfg))
        want = toks[i].size * sizes["top_k"]
        short = max(short, int(np.abs(counts.sum(1) - want).max()))
        load = max(load, float((counts.max(1) / counts.mean(1)).max()))
    out = {"route_counts_short": short, "load_max_over_mean": load,
           **{n: pvar.read(n) - v for n, v in before.items()}}
    out["held_rows_per_batch"] = out["moe_held_assignments"] / steps
    out["experts"] = np.asarray(tfm.route_experts(params, toks[0], cfg)[0])
    for kind, layer in probed_layers(sizes).items():
        out[kind + "_out"] = tfm.attn_probe(params, toks[0], cfg, layer)
    out.update(window_probe(sizes, *toks[0].shape, seed))
    return out


def reference_first_batch(sizes, toks, seed, quantize=None):
    """(the first expert layer's chosen experts [T, E], the three
    attention mixers' outputs by name) of the plain reference on the
    first batch, from the seed's state."""
    from benchmark.reference import kexaone_decoder as ref

    spec = reference_spec(sizes)
    params = weights_kexaone.device_init(sizes, seed)
    return (ref.chosen_experts(params, toks[0], spec, quantize),
            {kind: ref.attention_out(params, toks[0], layer, spec, quantize)
             for kind, layer in probed_layers(sizes).items()})


def first_steps(step, params, toks, labs, sizes, seed, steps):
    """Drive the compiled step through its first steps from the seed's
    state: (state, (losses, per-leaf movement after the first step,
    after the last))."""
    losses, moved = [], {}
    for i in range(steps):
        params, loss = step(params, toks[i], labs[i])
        losses.append(float(loss))
        if i in (0, steps - 1):
            moved[i] = weights_kexaone.delta_norms(sizes, seed, params)
    return params, (losses, moved[0], moved[steps - 1])


def reference_steps(sizes, toks, labs, seed, lr, steps, quantize=None):
    """The plain reference through the same first steps: losses, and
    per-leaf movement after the first step and after the last."""
    from benchmark.reference import kexaone_decoder as ref

    spec = reference_spec(sizes)
    params = weights_kexaone.device_init(sizes, seed)
    losses, moved = [], {}
    for i in range(steps):
        params, val = ref.sgd_step(params, toks[i], labs[i], lr, spec,
                                   quantize)
        losses.append(float(val))
        if i in (0, steps - 1):
            moved[i] = weights_kexaone.delta_norms(sizes, seed, params)
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
    params = weights_kexaone.device_init(sizes, ctx.seed)
    toks, labs = weights.batches(sizes["vocab"], traffic["n_batches"],
                                 batch, seq, ctx.seed)
    jax.block_until_ready((params, toks, labs))
    # what the comparison rests on: the seed's tree made again leaf by
    # leaf is THIS tree, bit for bit
    remade = float(weights_kexaone.delta_norms(sizes, ctx.seed,
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
    dense, moe = flops_kexaone.layer_counts(sizes)
    # the probe runs the trunk: the MTP module's expert layer is taken
    # to get the trunk's mean
    held_rows = probe["held_rows_per_batch"] * moe \
        / max(moe - sizes["mtp_layers"], 1)
    counters["compile_requests_setup"] = requests[0]
    counters["moe_load_max_over_mean"] = probe["load_max_over_mean"]
    for name in ("moe_assignments", "moe_held_assignments",
                 "moe_over_bound_layers"):
        counters[name] = probe[name]
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
    fl = flops_kexaone.train_flops_per_step(sizes, batch, seq, held_rows)
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
                      flops_kexaone.expert_flops_per_step(sizes, held_rows),
                  "swa_attn_flops_per_step":
                      flops_kexaone.swa_attn_flops_per_step(sizes, batch,
                                                            seq),
                  "full_attn_flops_per_step":
                      flops_kexaone.full_attn_flops_per_step(sizes, batch,
                                                             seq),
                  "mtp_flops_per_step":
                      flops_kexaone.mtp_flops_per_step(sizes, batch, seq,
                                                       held_rows),
                  "swa_kept_pairs_per_layer":
                      flops_kexaone.window_pairs(seq, sizes["window"])
                      * batch,
                  "held_rows_per_step": held_rows,
                  "seq": seq, "steps": done,
                  "tokens_per_step": tokens_per_step, "window_s": window_s},
    }
