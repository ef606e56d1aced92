"""Runner `olmoe_train`: a closed loop of single-chip train steps of the
`olmoe-1b-7b` configuration.

The system under test is the program's own step,
`jax.jit(ompi_tpu.models.transformer.make_train_step(...), donate)` —
the entry the `opt-30b` cells use — with the `Config` the published
config describes (RMSNorm, QK-norm, RoPE, top-8 of 64 gated experts,
untied head, the two router losses). Everything else is the
benchmark's: weights and batches from --seed, the window, the plain
reference (reference/olmoe_decoder.py) and the comparison. The window,
the trace window named `train` and `first_steps` are train_step.py's,
written again here because that file builds OPT's `Config`, tree,
reference and operation count by name.

What decides `correct` differs from the OPT cells in one respect.
Top-8 of 64 is discrete: a bfloat16 step re-routes about 0.3% of the
token-expert assignments, and the ROUTERS' gradients (leaves `wg`)
swing with that by up to 13% in a sound run — as far as the fp8
control's do. So the per-leaf gaps are read over the leaves other
than the routers', where sound and control separate by 2.3x and more;
the routers' own gap is a number of its own with a limit that holds
only a missing or partial router gradient; and the routing itself is
compared: the share of layer 0's assignments that are not the
reference's (`route_disagreement`: 0.3-0.4% sound, 5% in the control).
In set-up and outside any timed window the program's probe
`route_counts` also runs on the check batches: every layer's
assignments must sum to tokens x top_k (none dropped) and the
program's counter `moe_dropped_assignments` must read 0; the fullest
expert over the mean is the cell's load metric.
"""

from __future__ import annotations

import math
import os
import time

from benchmark import compare, flops_olmoe, weights, weights_olmoe
from benchmark.common import compile_requests, memory_stats, say
from benchmark.runners.train_step import _stolen_s


def model_sizes(config: dict) -> dict:
    """The configuration file speaks the source's language (Hugging
    Face keys); the program, the reference and the counts want these."""
    return {
        "vocab": config["vocab_size"], "d_model": config["hidden_size"],
        "n_layers": config["num_hidden_layers"],
        "n_heads": config["num_attention_heads"],
        "d_ff": config["intermediate_size"],
        "max_seq": config["max_position_embeddings"],
        "n_experts": config["num_experts"],
        "top_k": config["num_experts_per_tok"],
        "norm_topk_prob": config["norm_topk_prob"],
        "act": config["hidden_act"],
        "rope_theta": float(config["rope_theta"]),
        "rms_eps": config["rms_norm_eps"],
        "tie_head": config["tie_word_embeddings"],
        "balance_weight": config["router_aux_loss_coef"],
        "z_weight": config["router_z_loss_coef"],
        "param_dtype": config["param_dtype"],
    }


def program_config(sizes: dict):
    """The program's description of this model (one of the two imports
    of the system under test in this file). A program that lacks any of
    these fields cannot run the configuration and says so here, before
    anything is placed on the device."""
    import jax.numpy as jnp

    from ompi_tpu.models import transformer as tfm

    return tfm.Config(
        vocab=sizes["vocab"], d_model=sizes["d_model"],
        n_layers=sizes["n_layers"], n_heads=sizes["n_heads"],
        d_ff=sizes["d_ff"], max_seq=sizes["max_seq"], moe_every=1,
        n_experts=sizes["n_experts"], top_k=sizes["top_k"],
        norm_topk_prob=sizes["norm_topk_prob"], mlp_act=sizes["act"],
        mlp_gated=True, norm="rmsnorm", norm_eps=sizes["rms_eps"],
        pos="rope", rope_theta=sizes["rope_theta"], qk_norm=True,
        tie_head=sizes["tie_head"],
        router_aux_weight=sizes["balance_weight"],
        router_z_weight=sizes["z_weight"],
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
    from benchmark.reference import olmoe_decoder as ref

    return ref.Spec(
        n_heads=sizes["n_heads"], top_k=sizes["top_k"],
        rope_theta=sizes["rope_theta"], rms_eps=sizes["rms_eps"],
        norm_topk_prob=sizes["norm_topk_prob"],
        balance_weight=sizes["balance_weight"], z_weight=sizes["z_weight"])


def router_leaves(sizes: dict) -> list:
    """Per leaf of the parameter tree, in its flattened order: is it a
    router's matrix?"""
    import jax

    plan = weights_olmoe.plan(sizes)
    return ["wg" in jax.tree_util.keystr(path) for path, _ in
            jax.tree_util.tree_leaves_with_path(
                plan, is_leaf=lambda t: isinstance(t, tuple))]


def checks_against(prog, reference, limits, sizes) -> list:
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


def routing_probe(sizes: dict, params, toks, steps: int) -> dict:
    """The program's `route_counts` on the first `steps` batches:
    the worst shortfall of a layer's assignments against tokens x
    top_k, the fullest expert over the mean (worst layer, worst
    batch), and what the program's two counters gained."""
    import numpy as np

    from ompi_tpu.core import pvar
    from ompi_tpu.models import transformer as tfm

    cfg = program_config(sizes)
    before = {n: pvar.read(n) for n in ("moe_assignments",
                                        "moe_dropped_assignments")}
    short, load = 0, 0.0
    for i in range(steps):
        counts = np.asarray(tfm.route_counts(params, toks[i], cfg))
        want = toks[i].size * sizes["top_k"]
        short = max(short, int(np.abs(counts.sum(1) - want).max()))
        load = max(load, float((counts.max(1) / counts.mean(1)).max()))
    return {"route_counts_short": short, "load_max_over_mean": load,
            **{n: pvar.read(n) - v for n, v in before.items()},
            # layer 0's choices on the first batch, for the reference
            "experts": np.sort(np.asarray(
                tfm.route_experts(params, toks[0], cfg)[0]), -1)}


def route_disagreement(program_experts, sizes, toks, seed,
                       quantize=None) -> float:
    """The share of layer 0's token-expert assignments on the first
    batch, from the seed's state, that are not the reference's."""
    import numpy as np

    from benchmark.reference import olmoe_decoder as ref

    want = np.asarray(ref.chosen_experts(
        weights_olmoe.device_init(sizes, seed), toks[0],
        reference_spec(sizes), quantize))
    same = sum(len(set(a) & set(b)) for a, b in zip(program_experts, want))
    return 1.0 - same / want.size


def first_steps(step, params, toks, labs, sizes, seed, steps):
    """Drive the compiled step through its first steps from the seed's
    state: (state, (losses, per-leaf movement after the first step,
    after the last)). The state it started from was donated, so the
    seed's state is drawn again to measure the movement against."""
    import jax

    losses, moved = [], {}
    for i in range(steps):
        params, loss = step(params, toks[i], labs[i])
        losses.append(float(loss))
        if i in (0, steps - 1):
            start = weights_olmoe.device_init(sizes, seed)
            moved[i] = jax.device_get(
                compare.leaf_delta_norms(params, start))
            del start
    return params, (losses, moved[0], moved[steps - 1])


def reference_steps(sizes, toks, labs, seed, lr, steps, quantize=None):
    """The plain reference through the same first steps: losses, and
    per-leaf movement after the first step and after the last."""
    import jax

    from benchmark.reference import olmoe_decoder as ref

    spec = reference_spec(sizes)
    params = weights_olmoe.device_init(sizes, seed)
    losses, moved = [], {}
    for i in range(steps):
        params, val = ref.sgd_step(params, toks[i], labs[i], lr, spec,
                                   quantize)
        losses.append(float(val))
        if i in (0, steps - 1):  # the seed's state, only while measured
            start = weights_olmoe.device_init(sizes, seed)
            moved[i] = jax.device_get(
                compare.leaf_delta_norms(params, start))
            del start
    return losses, moved[0], moved[steps - 1]


def run(ctx) -> dict:
    import jax

    traffic = ctx.traffic
    sizes = model_sizes(ctx.config)
    program_config(sizes)  # a program without these fields stops here
    batch, seq, lr = traffic["batch"], traffic["seq"], traffic["lr"]
    n_check = traffic["check_steps"]
    tokens_per_step = batch * seq
    spans, counters = {}, {}

    # -- set-up: state, batches, the compiled step -------------------
    t = time.perf_counter()
    params = weights_olmoe.device_init(sizes, ctx.seed)
    toks, labs = weights.batches(sizes["vocab"], traffic["n_batches"],
                                 batch, seq, ctx.seed)
    jax.block_until_ready((params, toks, labs))
    spans["weights_s"] = time.perf_counter() - t
    n_params = sum(x.size for x in jax.tree.leaves(params))
    live = memory_stats().get("bytes_in_use", 0)
    say(f"config {sizes}; B={batch} T={seq} "
        f"tokens/step={tokens_per_step} params={n_params:,}")

    requests = compile_requests()
    t = time.perf_counter()
    step = build_step(sizes, lr).lower(params, toks[0], labs[0]).compile()
    spans["compile_s"] = time.perf_counter() - t
    mem = step.memory_analysis()
    temp = int(getattr(mem, "temp_size_in_bytes", 0) or 0)
    say(f"step executable: temporaries {temp:,} B beside "
        f"{live:,} B live; compile {spans['compile_s']:.2f}s")

    # -- the routing probe, on the seed's state -----------------------
    t = time.perf_counter()
    probe = routing_probe(sizes, params, toks, n_check)
    spans["routing_probe_s"] = time.perf_counter() - t
    counters["compile_requests_setup"] = requests[0]
    counters["moe_load_max_over_mean"] = probe["load_max_over_mean"]
    counters["moe_assignments"] = probe["moe_assignments"]
    say(f"routing probe on {n_check} batches: "
        f"{ {k: v for k, v in probe.items() if k != 'experts'} } "
        f"({spans['routing_probe_s']:.2f}s)")

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
    fl = flops_olmoe.train_flops_per_token(sizes, seq)
    rate = tokens_per_step * done / window_s
    if ctx.peaks:
        say(f"{fl:.6g} FLOP/token required; model FLOP/s utilization "
            f"{100 * rate * fl / ctx.peaks['bf16_flops_per_s']:.2f}% of "
            f"{ctx.peaks['bf16_flops_per_s']:.3g} (information; "
            "end to end, not a kernel's roofline share)")

    # -- the reference, once the program's state is freed ------------
    state.clear()
    del step
    t = time.perf_counter()
    reference = reference_steps(sizes, toks, labs, ctx.seed, lr, n_check)
    spans["reference_s"] = time.perf_counter() - t
    say(f"reference losses: {reference[0]} "
        f"({spans['reference_s']:.1f}s, not in setup_s)")
    checks = checks_against(program, reference, ctx.limits, sizes)
    checks += [("route_disagreement",
                route_disagreement(probe["experts"], sizes, toks, ctx.seed),
                ctx.limits["route_disagreement"]),
               ("nonfinite_window_losses", failed, 0),
               ("route_counts_short", probe["route_counts_short"], 0),
               ("moe_dropped_assignments",
                probe["moe_dropped_assignments"], 0)]

    return {
        "end_to_end": {"tokens_per_s": rate},
        "attempted": done, "failed": failed, "checks": checks,
        "spans": spans, "counters": counters,
        "memory_peak_bytes": peak,
        "facts": {"flops_per_step": fl * tokens_per_step,
                  "flops_per_token": fl,
                  "moe_experts_flops_per_step":
                      flops_olmoe.expert_flops_per_step(sizes, batch, seq),
                  "steps": done, "tokens_per_step": tokens_per_step,
                  "window_s": window_s},
    }
