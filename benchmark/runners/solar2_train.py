"""Runner `solar2_train`: a closed loop of single-chip train steps of
the `solar-open2-250b` configuration.

The system under test is the program's own step,
`jax.jit(ompi_tpu.models.transformer.make_train_step(...), donate)` —
the entry every train cell uses — with the `Config` the published
config describes: blocks whose first sub-layer is gated grouped-query
attention without positions on the layers of `gqa_layers` and Kimi
Delta Attention on the others (a per-channel gated delta rule, chunked,
its chunk-to-chunk carry a Pallas kernel on the TPU so that the step
has no loop), every layer's second sub-layer a sigmoid `noaux_tc` top-8
of 320 SiLU-gated experts of which this chip holds 40 and the shared
one — every layer recomputed in the backward pass. Everything else is
the benchmark's: weights and batches from --seed, the window, the plain
reference (reference/solar2_decoder.py) and the comparison. The window,
the trace window named `train` and the first steps are
nemotron_train.py's, written again here because that file builds
Nemotron's `Config`, tree, reference and operation count by name.

What decides `correct`: glm5_train.py's comparison (losses; per-leaf
movement after the first step and after the last, over the leaves that
are neither a router's nor a delta-rule layer's small ones; the
routers' own gap against gross faults; layer 0's routing compared as
sets; no assignment dropped, no loss that is not finite, the seed's
tree made again bit for bit) and what the mechanism adds, read by two
probes of the program on the first batch from the seed's state, each a
mixer on the EMBEDDED batch (the stream entering layer 0, so that a
reading carries nothing of the expert layers in front: PR 43's
finding): `kda_out_gap` and `kda_state_gap` — the relative error of
layer 1's delta-rule mixer output and of its state after the last
token (`transformer.kda_probe`, through the carry's timed form: a decay
ignored, the correction term left out, a chunk boundary handled
wrongly or the output gate missing read far over any bfloat16
tolerance) — and `gqa_out_gap` — layer 0's gated attention
(`transformer.attn_probe`: query heads paired with the wrong key heads
or the gate left out read ~1). The delta-rule layers' small leaves
(`A_log`, `dt_bias`, the three convolutions, the output norm's gain)
move by less than bfloat16 resolves in one step of lr x their
gradient, as nemotron-train-t8192's scan leaves do; their gradients are
held at toy widths in float32 by tests/test_solar2.py (every leaf
against the token-by-token recurrence, both forms of the carry) and on
the chip only through the leaves they feed. The reference starts only
when the program's state is freed.
"""

from __future__ import annotations

import math
import os
import time

from benchmark import compare, flops_solar2, weights, weights_solar2
from benchmark.common import compile_requests, memory_stats, say
from benchmark.runners.glm5_train import (chosen_numbers,  # noqa: F401
                                          route_disagreement)
from benchmark.runners.kimivl_train import rows_gap as rel_err
from benchmark.runners.train_step import _stolen_s

#: what the step's one trace counts of itself (the program's pvars)
TRACED = ("attn_blockwise_layers", "attn_reference_layers",
          "attn_gqa_layers", "attn_gated_layers", "attn_full_layers",
          "kda_layers", "kda_chunks", "kda_carry_kernel_layers",
          "kda_carry_scan_layers", "remat_kept_applications",
          "remat_whole_applications", "remat_kept_bytes",
          "moe_grouped_kernel_layers", "moe_ragged_dot_layers",
          "moe_full_layers", "moe_bounded_layers")


def model_sizes(config: dict) -> dict:
    """The configuration file speaks the source's language (Hugging
    Face keys, and the benchmark's own for what the source has none);
    the program, the reference and the counts want these."""
    linear, assumed = config["linear_attn_config"], \
        config["assumed"]["sizes"]
    n_layers = config["num_hidden_layers"]
    return {
        "vocab": config["vocab_size"], "d_model": config["hidden_size"],
        "n_layers": n_layers,
        # the source's list is kept whole: the cell holds the layers
        # below `num_hidden_layers`
        "gqa_layers": tuple(i for i in config["gqa_layers"] if i < n_layers),
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "attn_gate": config["use_gqa_gate"],
        "kda_heads": linear["num_heads"], "kda_head_dim": linear["head_dim"],
        "kda_conv": linear["short_conv_kernel_size"],
        "kda_chunk": assumed["kda_chunk"],
        "kda_rank": assumed["kda_gate_rank"],
        "l2_eps": assumed["l2norm_eps"],
        "dt_min": assumed["time_step_min"],
        "dt_max": assumed["time_step_max"],
        "moe_d_ff": config["moe_intermediate_size"],
        "max_seq": config["max_position_embeddings"],
        # the router scores the published number of experts; this chip
        # holds `n_routed_experts` of them, from `held_first`
        "n_experts": config["router_experts"],
        "held_first": config["held_first"],
        "held_count": config["n_routed_experts"],
        "top_k": config["num_experts_per_tok"],
        "norm_topk_prob": config["norm_topk_prob"],
        "routed_scale": config["routed_scaling_factor"],
        "n_shared_experts": config["n_shared_experts"],
        "rms_eps": config["rms_norm_eps"],
        "tie_head": config["tie_word_embeddings"],
        "param_dtype": config["param_dtype"],
    }


def program_config(sizes: dict):
    """The program's description of this model (one of the imports of
    the system under test in this file). A program that lacks any of
    these fields cannot run the configuration and says so here, before
    anything is placed on the device."""
    import jax.numpy as jnp

    from ompi_tpu.models import transformer as tfm

    return tfm.Config(
        vocab=sizes["vocab"], d_model=sizes["d_model"],
        n_layers=sizes["n_layers"], n_heads=sizes["n_heads"],
        n_kv_heads=sizes["n_kv_heads"], head_width=sizes["head_dim"],
        max_seq=sizes["max_seq"], moe_every=1, moe_d_ff=sizes["moe_d_ff"],
        n_experts=sizes["n_experts"], top_k=sizes["top_k"],
        norm_topk_prob=sizes["norm_topk_prob"], router_score="sigmoid",
        router_bias=True, routed_scale=sizes["routed_scale"],
        n_shared_experts=sizes["n_shared_experts"],
        held_experts=(sizes["held_first"], sizes["held_count"]),
        mlp_act="silu", mlp_gated=True, norm="rmsnorm",
        norm_eps=sizes["rms_eps"], pos="none", tie_head=sizes["tie_head"],
        attn_layers="".join(
            tfm.FULL if i in sizes["gqa_layers"] else tfm.DELTA
            for i in range(sizes["n_layers"])),
        attn_gate=sizes["attn_gate"], kda_heads=sizes["kda_heads"],
        kda_head_dim=sizes["kda_head_dim"], kda_conv=sizes["kda_conv"],
        kda_chunk=sizes["kda_chunk"], kda_rank=sizes["kda_rank"],
        remat=True, param_dtype=jnp.dtype(sizes["param_dtype"]))


def build_step(sizes: dict, lr: float):
    """The program's jitted train step."""
    import jax

    from ompi_tpu.models import transformer as tfm

    cfg, ax = program_config(sizes), tfm.Axes()
    return jax.jit(
        tfm.make_train_step(cfg, ax, tfm.param_specs(cfg, ax), lr=lr),
        donate_argnums=(0,))


def reference_spec(sizes: dict):
    from benchmark.reference import solar2_decoder as ref

    return ref.Spec(
        gqa_layers=sizes["gqa_layers"], n_heads=sizes["n_heads"],
        n_kv_heads=sizes["n_kv_heads"], kda_heads=sizes["kda_heads"],
        top_k=sizes["top_k"], held_first=sizes["held_first"],
        routed_scale=sizes["routed_scale"], rms_eps=sizes["rms_eps"],
        l2_eps=sizes["l2_eps"])


def probed_layers(sizes: dict) -> dict:
    """The first layer of each kind of mixer."""
    return {"gqa": sizes["gqa_layers"][0],
            "kda": next(i for i in range(sizes["n_layers"])
                        if i not in sizes["gqa_layers"])}


#: a delta-rule layer's leaves whose movement bfloat16 cannot resolve
#: (a step of lr x their gradient is a fraction of one rounding of a
#: leaf near 0.5 or 1: what moves is rounding flips)
KDA_SMALL = ("A_log", "dt_bias", "conv_q", "conv_k", "conv_v", "o_norm")


def leaf_kinds(sizes: dict) -> list:
    """Per leaf of the parameter tree, in its flattened order:
    "router" (wg and its bias), "kda_small" (`KDA_SMALL`) or "rest"."""
    import jax

    def kind(path) -> str:
        name = jax.tree_util.keystr(path)
        return "router" if "'wg" in name else "kda_small" if any(
            f"'{n}'" in name for n in KDA_SMALL) else "rest"

    return [kind(path) for path, _ in jax.tree_util.tree_leaves_with_path(
        weights_solar2.plan(sizes), is_leaf=lambda t: isinstance(t, tuple))]


def checks_against(prog, reference, limits, sizes) -> list:
    """`prog`, `reference`: (losses, per-leaf movement after the first
    step, after the last)."""
    import numpy as np

    (p_loss, p_first, p_last), (r_loss, r_first, r_last) = prog, reference
    kinds = np.array(leaf_kinds(sizes))
    rest = kinds == "rest"

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
         gap(compare.worst_leaf_gap, p_first, r_first, kinds == "router"),
         limits["router_grad_norm_gap"]),
    ]


def first_batch_checks(probe, reference_first, limits) -> list:
    """The first batch's four: layer 0's gated attention and layer 1's
    delta-rule mixer (output and final state) on the embedded batch,
    layer 0's routing."""
    chosen, (out, last), attn = reference_first
    return [
        ("gqa_out_gap", rel_err(probe["gqa_out"], attn),
         limits["gqa_out_gap"]),
        ("kda_out_gap", rel_err(probe["kda_out"], out),
         limits["kda_out_gap"]),
        ("kda_state_gap", rel_err(probe["kda_state"], last),
         limits["kda_state_gap"]),
        ("route_disagreement", route_disagreement(probe["experts"], chosen),
         limits["route_disagreement"])]


def probes(sizes: dict, params, toks, steps: int) -> dict:
    """The program's set-up probes on the seed's state. `route_counts`
    on the first `steps` batches: the worst shortfall of a layer's
    assignments against tokens x top_k, the fullest expert over the
    mean (worst layer, worst batch), the assignments that fell to the
    held experts a batch (all layers), what the program's counters
    gained. On the first batch: layer 0's choices, `kda_probe`'s output
    and state for the first delta-rule layer, `attn_probe`'s output for
    the first attention layer."""
    import numpy as np

    from ompi_tpu.core import pvar
    from ompi_tpu.models import transformer as tfm

    cfg = program_config(sizes)
    names = ("moe_assignments", "moe_dropped_assignments",
             "moe_held_assignments", "moe_over_bound_layers",
             "kda_state_norm_micro")
    before = {n: pvar.read(n) for n in names}
    short, load = 0, 0.0
    for i in range(steps):
        counts = np.asarray(tfm.route_counts(params, toks[i], cfg))
        want = toks[i].size * sizes["top_k"]
        short = max(short, int(np.abs(counts.sum(1) - want).max()))
        load = max(load, float((counts.max(1) / counts.mean(1)).max()))
    at = probed_layers(sizes)
    kda_out, kda_state = tfm.kda_probe(params, toks[0], cfg, at["kda"])
    out = {"route_counts_short": short, "load_max_over_mean": load,
           **{n: pvar.read(n) - v for n, v in before.items()}}
    out["held_rows_per_batch"] = out["moe_held_assignments"] / steps
    out["experts"] = np.asarray(tfm.route_experts(params, toks[0], cfg)[0])
    out["kda_out"], out["kda_state"] = kda_out, kda_state
    out["gqa_out"] = tfm.attn_probe(params, toks[0], cfg, at["gqa"])
    return out


#: a probe's arrays, which no line prints
ARRAYS = ("experts", "kda_out", "kda_state", "gqa_out")


def reference_first_batch(sizes, toks, seed, quantize=None):
    """(layer 0's chosen experts [T, E], (the first delta-rule layer's
    mixer output, its final state), the first attention layer's mixer
    output) of the plain reference on the first batch, from the seed's
    state; the two mixers on the embedded batch."""
    from benchmark.reference import solar2_decoder as ref

    spec = reference_spec(sizes)
    params = weights_solar2.device_init(sizes, seed)
    at = probed_layers(sizes)
    return (ref.chosen_experts(params, toks[0], spec, quantize),
            ref.mixer_out(params, toks[0], at["kda"], spec, quantize),
            ref.mixer_out(params, toks[0], at["gqa"], spec, quantize))


def first_steps(step, params, toks, labs, sizes, seed, steps):
    """Drive the compiled step through its first steps from the seed's
    state: (state, (losses, per-leaf movement after the first step,
    after the last))."""
    losses, moved = [], {}
    for i in range(steps):
        params, loss = step(params, toks[i], labs[i])
        losses.append(float(loss))
        if i in (0, steps - 1):
            moved[i] = weights_solar2.delta_norms(sizes, seed, params)
    return params, (losses, moved[0], moved[steps - 1])


def reference_steps(sizes, toks, labs, seed, lr, steps, quantize=None):
    """The plain reference through the same first steps: losses,
    per-leaf movement after the first step and after the last."""
    from benchmark.reference import solar2_decoder as ref

    spec = reference_spec(sizes)
    params = weights_solar2.device_init(sizes, seed)
    losses, moved = [], {}
    for i in range(steps):
        params, val = ref.sgd_step(params, toks[i], labs[i], lr, spec,
                                   quantize)
        losses.append(float(val))
        if i in (0, steps - 1):
            moved[i] = weights_solar2.delta_norms(sizes, seed, params)
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
    params = weights_solar2.device_init(sizes, ctx.seed)
    toks, labs = weights.batches(sizes["vocab"], traffic["n_batches"],
                                 batch, seq, ctx.seed)
    jax.block_until_ready((params, toks, labs))
    # what the comparison rests on: the seed's tree made again leaf by
    # leaf is THIS tree, bit for bit
    remade = float(weights_solar2.delta_norms(sizes, ctx.seed,
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
    probe = probes(sizes, params, toks, n_check)
    spans["probes_s"] = time.perf_counter() - t
    held_rows = probe["held_rows_per_batch"]
    counters["compile_requests_setup"] = requests[0]
    counters["moe_load_max_over_mean"] = probe["load_max_over_mean"]
    for name in ("moe_assignments", "moe_held_assignments",
                 "moe_over_bound_layers", "kda_state_norm_micro"):
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
    fl = flops_solar2.train_flops_per_step(sizes, batch, seq, held_rows)
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
    checks += [("seed_tree_remade_gap", remade, 0),
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
                      flops_solar2.expert_flops_per_step(sizes, held_rows),
                  "kda_core_flops_per_step":
                      flops_solar2.kda_core_flops_per_step(sizes, batch,
                                                           seq),
                  "kda_core_bytes_per_step":
                      flops_solar2.kda_core_bytes_per_step(sizes, batch,
                                                           seq),
                  "gqa_attn_flops_per_step":
                      flops_solar2.gqa_attn_flops_per_step(sizes, batch,
                                                           seq),
                  "held_rows_per_step": held_rows,
                  "steps": done, "tokens_per_step": tokens_per_step,
                  "window_s": window_s},
    }
