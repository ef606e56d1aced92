"""Runner `nemotron_train`: a closed loop of single-chip train steps of
the `nemotron-3-nano-30b-a3b` configuration.

The system under test is the program's own step,
`jax.jit(ompi_tpu.models.transformer.make_train_step(...), donate)` —
the entry every train cell uses — with the `Config` the published
config describes: layers that are ONE pre-norm and ONE mixer by the
pattern string — Mamba-2 state-space layers (a chunked scan with no
loop in the step), grouped-query attention with no positions, relu2
experts without a gate matrix under a sigmoid `noaux_tc` router that
scores all 128 experts while this chip holds 16 of them and the shared
one — every layer recomputed in the backward pass. Everything else is
the benchmark's: weights and batches from --seed, the window, the plain
reference (reference/nemotron_decoder.py) and the comparison. The
window, the trace window named `train` and the first steps are
glm5_train.py's, written again here because that file builds GLM-5's
`Config`, tree, reference and operation count by name.

What decides `correct`: glm5_train.py's comparison (losses; per-leaf
movement after the first step and after the last, over the leaves that
are neither a router's nor a state-space layer's small ones; the
routers' own gap against gross faults; the
first expert layer's routing compared as sets) and what the mechanism
adds, read by two probes of the program on the first batch from the
seed's state: `ssm_out_gap` and `ssm_state_gap` — the relative error
of the first state-space layer's mixer output and of its scan's state
after the last token (`transformer.ssm_probe`; a chunk boundary handled
wrongly, a decay taken at the wrong end of a chunk or a carried state
left out shows in the state and nowhere as clearly) —, `attn_out_gap`
— the relative error of the first attention layer's mixer output
(`transformer.gqa_probe`: query heads paired with the wrong key heads
move no norm of a gradient and hardly the loss, and read ~1 here) — and
`ssm_grad_norm_gap` — the worst gap, over every state-space layer's
`A_log`, `dt_bias`, `D`, convolution and gated-norm gain, between the
float32 norm of the program's gradient (`transformer.ssm_leaf_grads`)
and the reference's: gradients that exist only through the scan (a
stopped one reads 1.0; through the bfloat16 state such a leaf's
movement is below what its type resolves). The reference starts only
when the program's state is freed.
"""

from __future__ import annotations

import math
import os
import time

from benchmark import compare, flops_nemotron, weights, weights_nemotron
from benchmark.common import compile_requests, memory_stats, say
from benchmark.runners.glm5_train import (chosen_numbers,  # noqa: F401
                                          route_disagreement)
from benchmark.runners.kimivl_train import rows_gap as rel_err
from benchmark.runners.train_step import _stolen_s

#: what the step's one trace counts of itself (the program's pvars)
TRACED = ("attn_blockwise_layers", "attn_reference_layers",
          "attn_gqa_layers", "ssm_layers", "ssm_chunks",
          "remat_kept_applications", "remat_whole_applications",
          "remat_kept_bytes", "moe_grouped_kernel_layers",
          "moe_ragged_dot_layers", "moe_bounded_layers")


def model_sizes(config: dict) -> dict:
    """The configuration file speaks the source's language (Hugging
    Face keys, and the benchmark's own for what the source has none);
    the program, the reference and the counts want these."""
    return {
        "vocab": config["vocab_size"], "d_model": config["hidden_size"],
        "n_layers": config["num_hidden_layers"],
        "pattern": config["hybrid_override_pattern"],
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "moe_d_ff": config["moe_intermediate_size"],
        "shared_d_ff": config["moe_shared_expert_intermediate_size"],
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
        "ssm_heads": config["mamba_num_heads"],
        "ssm_head_dim": config["mamba_head_dim"],
        "ssm_groups": config["n_groups"],
        "ssm_state": config["ssm_state_size"],
        "ssm_conv": config["conv_kernel"],
        "ssm_chunk": config["chunk_size"],
        "dt_min": config["time_step_min"], "dt_max": config["time_step_max"],
        "dt_floor": config["time_step_floor"],
        "act": config["mlp_hidden_act"], "rms_eps": config["norm_eps"],
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
        n_layers=sizes["n_layers"], layer_pattern=sizes["pattern"],
        n_heads=sizes["n_heads"], n_kv_heads=sizes["n_kv_heads"],
        head_width=sizes["head_dim"], max_seq=sizes["max_seq"],
        moe_d_ff=sizes["moe_d_ff"], shared_d_ff=sizes["shared_d_ff"],
        n_experts=sizes["n_experts"], top_k=sizes["top_k"],
        norm_topk_prob=sizes["norm_topk_prob"], router_score="sigmoid",
        router_bias=True, routed_scale=sizes["routed_scale"],
        n_shared_experts=sizes["n_shared_experts"],
        held_experts=(sizes["held_first"], sizes["held_count"]),
        mlp_act=sizes["act"], mlp_gated=False, norm="rmsnorm",
        norm_eps=sizes["rms_eps"], pos="none", tie_head=sizes["tie_head"],
        ssm_heads=sizes["ssm_heads"], ssm_head_dim=sizes["ssm_head_dim"],
        ssm_groups=sizes["ssm_groups"], ssm_state=sizes["ssm_state"],
        ssm_conv=sizes["ssm_conv"], ssm_chunk=sizes["ssm_chunk"],
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
    from benchmark.reference import nemotron_decoder as ref

    return ref.Spec(
        pattern=sizes["pattern"], n_heads=sizes["n_heads"],
        n_kv_heads=sizes["n_kv_heads"], ssm_heads=sizes["ssm_heads"],
        ssm_groups=sizes["ssm_groups"], ssm_state=sizes["ssm_state"],
        top_k=sizes["top_k"], held_first=sizes["held_first"],
        routed_scale=sizes["routed_scale"], rms_eps=sizes["rms_eps"])


#: a state-space layer's leaves whose movement bfloat16 cannot resolve
#: (a step of lr x their gradient is a fraction of one rounding of a
#: leaf near 0.5 or 1: what moves is rounding flips): they are held by
#: their float32 gradient norms instead (`ssm_grad_norm_gap`)
SSM_SMALL = ("A_log", "dt_bias", "D", "conv_w", "conv_b", "ssm_norm")


def leaf_kinds(sizes: dict) -> list:
    """Per leaf of the parameter tree, in its flattened order:
    "router" (wg and its bias), "ssm_small" (`SSM_SMALL`) or "rest"."""
    import jax

    def kind(path) -> str:
        name = jax.tree_util.keystr(path)
        return "router" if "'wg" in name else "ssm_small" if any(
            f"'{n}'" in name for n in SSM_SMALL) else "rest"

    return [kind(path) for path, _ in jax.tree_util.tree_leaves_with_path(
        weights_nemotron.plan(sizes), is_leaf=lambda t: isinstance(t, tuple))]


def checks_against(prog, reference, limits, sizes) -> list:
    """`prog`, `reference`: (losses, per-leaf movement after the first
    step, after the last, the state-space layers' small leaves'
    float32 gradient norms on the first batch)."""
    import numpy as np

    p_loss, p_first, p_last, p_small = prog
    r_loss, r_first, r_last, r_small = reference
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
        ("ssm_grad_norm_gap",
         compare.worst_leaf_gap(np.asarray(p_small).ravel(),
                                np.asarray(r_small).ravel()),
         limits["ssm_grad_norm_gap"]),
    ]


def first_batch_checks(probe, reference_first, limits) -> list:
    """The first batch's four: the first expert layer's routing, the
    first state-space layer's output and final state, the first
    attention layer's output."""
    chosen, (out, last), attn = reference_first
    return [
        ("attn_out_gap", rel_err(probe["attn_out"], attn),
         limits["attn_out_gap"]),
        ("route_disagreement", route_disagreement(probe["experts"], chosen),
         limits["route_disagreement"]),
        ("ssm_out_gap", rel_err(probe["ssm_out"], out),
         limits["ssm_out_gap"]),
        ("ssm_state_gap", rel_err(probe["ssm_state"], last),
         limits["ssm_state_gap"])]


def probes(sizes: dict, params, toks, labs, steps: int) -> dict:
    """The program's set-up probes on the seed's state. `route_counts`
    on the first `steps` batches: the worst shortfall of a layer's
    assignments against tokens x top_k, the fullest expert over the
    mean (worst layer, worst batch), the assignments that fell to the
    held experts a batch (all expert layers), what the program's
    counters gained. On the first batch: the first expert layer's
    choices, `ssm_probe`'s output and state, `gqa_probe`'s output,
    `ssm_leaf_grads`."""
    import numpy as np

    from ompi_tpu.core import pvar
    from ompi_tpu.models import transformer as tfm

    cfg = program_config(sizes)
    names = ("moe_assignments", "moe_dropped_assignments",
             "moe_held_assignments", "moe_over_bound_layers",
             "ssm_state_norm_micro")
    before = {n: pvar.read(n) for n in names}
    short, load = 0, 0.0
    for i in range(steps):
        counts = np.asarray(tfm.route_counts(params, toks[i], cfg))
        want = toks[i].size * sizes["top_k"]
        short = max(short, int(np.abs(counts.sum(1) - want).max()))
        load = max(load, float((counts.max(1) / counts.mean(1)).max()))
    ssm_out, ssm_state = tfm.ssm_probe(params, toks[0], cfg)
    out = {"route_counts_short": short, "load_max_over_mean": load,
           **{n: pvar.read(n) - v for n, v in before.items()}}
    out["held_rows_per_batch"] = out["moe_held_assignments"] / steps
    out["experts"] = np.asarray(tfm.route_experts(params, toks[0], cfg)[0])
    out["ssm_out"], out["ssm_state"] = ssm_out, ssm_state
    out["attn_out"] = tfm.gqa_probe(params, toks[0], cfg)
    out["ssm_grads"] = np.asarray(
        tfm.ssm_leaf_grads(params, toks[0], labs[0], cfg))
    return out


#: a probe's arrays, which no line prints
ARRAYS = ("experts", "ssm_out", "ssm_state", "attn_out", "ssm_grads")


def reference_first_batch(sizes, toks, seed, quantize=None):
    """(the first expert layer's chosen experts [T, E], (the first
    state-space layer's mixer output, its final state), the first
    attention layer's mixer output) of the plain reference on the
    first batch, from the seed's state."""
    from benchmark.reference import nemotron_decoder as ref

    spec = reference_spec(sizes)
    params = weights_nemotron.device_init(sizes, seed)
    return (ref.chosen_experts(params, toks[0], spec, quantize),
            ref.first_ssm(params, toks[0], spec, quantize),
            ref.first_attention(params, toks[0], spec, quantize))


def first_steps(step, params, toks, labs, sizes, seed, steps):
    """Drive the compiled step through its first steps from the seed's
    state: (state, (losses, per-leaf movement after the first step,
    after the last))."""
    losses, moved = [], {}
    for i in range(steps):
        params, loss = step(params, toks[i], labs[i])
        losses.append(float(loss))
        if i in (0, steps - 1):
            moved[i] = weights_nemotron.delta_norms(sizes, seed, params)
    return params, (losses, moved[0], moved[steps - 1])


def reference_steps(sizes, toks, labs, seed, lr, steps, quantize=None):
    """The plain reference through the same first steps: losses,
    per-leaf movement after the first step and after the last, and the
    first step's float32 gradient norms of the state-space layers'
    small leaves."""
    import numpy as np

    from benchmark.reference import nemotron_decoder as ref

    spec = reference_spec(sizes)
    params = weights_nemotron.device_init(sizes, seed)
    losses, moved, small = [], {}, None
    for i in range(steps):
        params, val, grads = ref.sgd_step(params, toks[i], labs[i], lr, spec,
                                          quantize)
        losses.append(float(val))
        if i == 0:
            small = np.asarray(grads)
        if i in (0, steps - 1):
            moved[i] = weights_nemotron.delta_norms(sizes, seed, params)
    return losses, moved[0], moved[steps - 1], small


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
    params = weights_nemotron.device_init(sizes, ctx.seed)
    toks, labs = weights.batches(sizes["vocab"], traffic["n_batches"],
                                 batch, seq, ctx.seed)
    jax.block_until_ready((params, toks, labs))
    # what the comparison rests on: the seed's tree made again leaf by
    # leaf is THIS tree, bit for bit
    remade = float(weights_nemotron.delta_norms(sizes, ctx.seed,
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
    probe = probes(sizes, params, toks, labs, n_check)
    spans["probes_s"] = time.perf_counter() - t
    held_rows = probe["held_rows_per_batch"]
    counters["compile_requests_setup"] = requests[0]
    counters["moe_load_max_over_mean"] = probe["load_max_over_mean"]
    for name in ("moe_assignments", "moe_held_assignments",
                 "moe_over_bound_layers", "ssm_state_norm_micro"):
        counters[name] = probe[name]
    say(f"probes on {n_check} batches: "
        f"{ {k: v for k, v in probe.items() if k not in ARRAYS} } "
        f"({spans['probes_s']:.2f}s)")

    # -- the first steps, through the window's own call and feed -----
    t = time.perf_counter()
    params, program = first_steps(step, params, toks, labs, sizes,
                                  ctx.seed, n_check)
    program += (probe["ssm_grads"],)
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
    fl = flops_nemotron.train_flops_per_step(sizes, batch, seq, held_rows)
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
                      flops_nemotron.expert_flops_per_step(sizes, held_rows),
                  "ssm_scan_flops_per_step":
                      flops_nemotron.ssm_scan_flops_per_step(sizes, batch,
                                                             seq),
                  "ssm_scan_bytes_per_step":
                      flops_nemotron.ssm_scan_bytes_per_step(sizes, batch,
                                                             seq),
                  "gqa_attn_flops_per_step":
                      flops_nemotron.gqa_attn_flops_per_step(sizes, batch,
                                                             seq),
                  "held_rows_per_step": held_rows,
                  "steps": done, "tokens_per_step": tokens_per_step,
                  "window_s": window_s},
    }
