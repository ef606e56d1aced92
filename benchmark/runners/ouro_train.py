"""Runner `ouro_train`: a closed loop of single-chip train steps of the
`ouro-2.6b` configuration.

The system under test is the program's own step,
`jax.jit(ompi_tpu.models.transformer.make_train_step(...), donate)` —
the entry every train cell uses — with the `Config` the published
config describes: ONE list of layers run `total_ut_steps` times with
the same weights, a norm before and after each sub-layer, the final
norm between passes, an exit (the shared untied head and a learned
gate) after every pass, the expected loss over the exits less the
entropy term, every layer application recomputed in the backward pass.
Everything else is the benchmark's: weights and batches from --seed,
the window, the plain reference (reference/ouro_decoder.py) and the
comparison. The window is `closed_loop` below — the loop
train_step.py, olmoe_train.py and glm5_train.py each carry inside their
`run`, here as a function a later runner can import.

What decides `correct`: the train cells' comparison (losses; per-leaf
movement after the first step and after the last, over the leaves
other than the gate's two; the gate's own gap) and what the mechanism
adds, read by the program's set-up probe `exit_stats` on the first
batch from the seed's state against the reference's forward pass
there: `pass_loss_gap` (the worst of the exits' mean cross-entropies
against the reference's: a pass skipped, a norm left out between
passes or an exit read from the wrong pass shows here and not in the
blended loss) and `exit_prob_gap` (the largest absolute difference of
the mean probability of leaving at an exit). The state is 1.64 GB, so
the seed's tree is simply made again to read movements against — by
the same kept per-leaf programs, and it must be the tree the step
started from bit for bit (`seed_tree_remade_gap` 0).
"""

from __future__ import annotations

import math
import os
import time

from benchmark import compare, flops_ouro, weights, weights_ouro
from benchmark.common import compile_requests, memory_stats, say
from benchmark.runners.train_step import _stolen_s


def model_sizes(config: dict) -> dict:
    """The configuration file speaks the source's language (Hugging
    Face keys, and the benchmark's own for what the source has none);
    the program, the reference and the counts want these."""
    if config["num_key_value_heads"] != config["num_attention_heads"] \
            or config["use_sliding_window"] or config["rope_scaling"]:
        raise ValueError("ouro_train runs full multi-head attention "
                         "without a window or RoPE scaling")
    return {
        "vocab": config["vocab_size"], "d_model": config["hidden_size"],
        "n_layers": config["num_hidden_layers"],
        "n_heads": config["num_attention_heads"],
        "d_ff": config["intermediate_size"],
        "max_seq": config["max_position_embeddings"],
        "loops": config["total_ut_steps"],
        "beta": config["exit_entropy_weight"],
        "act": config["hidden_act"],
        "rope_theta": float(config["rope_theta"]),
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
        d_ff=sizes["d_ff"], max_seq=sizes["max_seq"],
        mlp_act=sizes["act"], mlp_gated=True, norm="rmsnorm",
        norm_eps=sizes["rms_eps"], pos="rope",
        rope_theta=sizes["rope_theta"], tie_head=sizes["tie_head"],
        loops=sizes["loops"], post_norm=True, exit_gate=True,
        exit_entropy_weight=sizes["beta"], remat=True,
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
    from benchmark.reference import ouro_decoder as ref

    return ref.Spec(n_heads=sizes["n_heads"], loops=sizes["loops"],
                    rope_theta=sizes["rope_theta"], rms_eps=sizes["rms_eps"],
                    beta=sizes["beta"])


def gate_leaves(sizes: dict) -> list:
    """Per leaf of the parameter tree, in its flattened order: is it
    the exit gate's?"""
    import jax

    return ["exit_gate" in jax.tree_util.keystr(path) for path, _ in
            jax.tree_util.tree_leaves_with_path(
                weights_ouro.plan(sizes),
                is_leaf=lambda t: isinstance(t, tuple))]


def checks_against(prog, reference, limits, sizes) -> list:
    import numpy as np

    (p_loss, p_first, p_last), (r_loss, r_first, r_last) = prog, reference
    gate = np.array(gate_leaves(sizes))

    def gap(how, a, b, which):
        return how(np.asarray(a)[which], np.asarray(b)[which])

    return [
        ("loss_gap", max(compare.rel_gap(a, b)
                         for a, b in zip(p_loss, r_loss)),
         limits["loss_gap"]),
        ("first_grad_norm_gap",
         gap(compare.worst_leaf_gap, p_first, r_first, ~gate),
         limits["first_grad_norm_gap"]),
        ("first_grad_norm_rms_gap",
         gap(compare.rms_leaf_gap, p_first, r_first, ~gate),
         limits["first_grad_norm_rms_gap"]),
        ("param_change_norm_gap",
         gap(compare.worst_leaf_gap, p_last, r_last, ~gate),
         limits["param_change_norm_gap"]),
        ("gate_grad_norm_gap",
         gap(compare.worst_leaf_gap, p_first, r_first, gate),
         limits["gate_grad_norm_gap"]),
    ]


def exit_checks(mine, reference, limits) -> list:
    """(mean cross-entropy per exit, mean probability per exit) of a
    run against the reference's."""
    (nll, mass), (r_nll, r_mass) = mine, reference
    return [
        ("pass_loss_gap", max(compare.rel_gap(float(a), float(b))
                              for a, b in zip(nll, r_nll)),
         limits["pass_loss_gap"]),
        ("exit_prob_gap", max(abs(float(a) - float(b))
                              for a, b in zip(mass, r_mass)),
         limits["exit_prob_gap"]),
    ]


def exit_probe(sizes: dict, params, tok, lab) -> dict:
    """The program's set-up probe `exit_stats` on one batch from the
    seed's state: every exit's mean cross-entropy, the exit
    distribution's mean mass per pass, and what the program's counters
    gained."""
    from ompi_tpu.core import pvar
    from ompi_tpu.models import transformer as tfm

    last = f"exit_mass_micro_p{sizes['loops'] - 1}"
    before = {n: pvar.read(n) for n in ("exit_probe_tokens", last)}
    nll, mass = tfm.exit_stats(params, tok, lab, program_config(sizes))
    return {"nll": [float(x) for x in nll], "mass": [float(x) for x in mass],
            "exit_probe_tokens": pvar.read("exit_probe_tokens")
            - before["exit_probe_tokens"],
            "exit_last_pass_mass_micro": pvar.read(last) - before[last]}


def _moved(sizes: dict, seed: int, params):
    """Per leaf, how far `params` is from the seed's tree."""
    import jax

    return jax.device_get(compare.leaf_delta_norms(
        params, weights_ouro.device_init(sizes, seed)))


def first_steps(step, params, toks, labs, sizes, seed, steps):
    """Drive the compiled step through its first steps from the seed's
    state: (state, (losses, per-leaf movement after the first step,
    after the last))."""
    losses, moved = [], {}
    for i in range(steps):
        params, loss = step(params, toks[i], labs[i])
        losses.append(float(loss))
        if i in (0, steps - 1):
            moved[i] = _moved(sizes, seed, params)
    return params, (losses, moved[0], moved[steps - 1])


def reference_steps(sizes, toks, labs, seed, lr, steps, quantize=None):
    """The plain reference from the seed's state: ((losses, per-leaf
    movement after the first step, after the last) through the same
    first steps, (mean cross-entropy, mean probability) per exit on the
    first batch before any step)."""
    import jax

    from benchmark.reference import ouro_decoder as ref

    spec = reference_spec(sizes)
    params = weights_ouro.device_init(sizes, seed)
    exits = jax.device_get(ref.exit_means(params, toks[0], labs[0], spec,
                                          quantize))
    losses, moved = [], {}
    for i in range(steps):
        params, val = ref.sgd_step(params, toks[i], labs[i], lr, spec,
                                   quantize)
        losses.append(float(val))
        if i in (0, steps - 1):
            moved[i] = _moved(sizes, seed, params)
    return (losses, moved[0], moved[steps - 1]), exits


def closed_loop(ctx, step, params, toks, labs, first: int,
                trace_steps: int) -> dict:
    """The timed window of a train cell: a closed loop of `step` on the
    batches in turn from batch `first`, one step dispatched ahead, a
    step complete when its loss is ready, until ctx.seconds have
    passed; with `trace_steps`, that many steps traced from an idle
    device in a window named `train` after the first two. Returns the
    losses, the window's seconds and what the host did meanwhile."""
    import jax

    nb = len(toks)
    losses, ready, parts = [], [], []
    state = {"params": params, "i": first, "pending": None,
             "dispatch_s": 0.0}

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
    gaps = [b - a for a, b in zip(ready, ready[1:])]
    if gaps and not ctx.trace:  # a stalled host or chip shows here
        at = max(range(len(gaps)), key=gaps.__getitem__)
        say(f"seconds between losses: median "
            f"{sorted(gaps)[len(gaps) // 2]:.4f} longest {gaps[at]:.4f} "
            f"(before loss {at + 2} of {len(losses)}: "
            f"{parts[at + 1][0]:.4f} in the dispatch of the next step, "
            f"{parts[at + 1][1]:.4f} waiting for the loss); this process used "
            f"{cpu_s:.2f}s of CPU in the window, {stolen_s:.2f}s of CPU "
            f"were stolen from the machine; host load average "
            f"{load0[0]:.2f} at its start, {os.getloadavg()[0]:.2f} at "
            "its end (information)")
    return {"losses": [float(x) for x in losses], "window_s": window_s}


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
    params = weights_ouro.device_init(sizes, ctx.seed)
    toks, labs = weights.batches(sizes["vocab"], traffic["n_batches"],
                                 batch, seq, ctx.seed)
    jax.block_until_ready((params, toks, labs))
    # what the comparison rests on: the seed's tree made again is THIS
    # tree, bit for bit
    remade = float(_moved(sizes, ctx.seed, params).max())
    spans["weights_s"] = time.perf_counter() - t
    n_params = sum(x.size for x in jax.tree.leaves(params))
    live = memory_stats().get("bytes_in_use", 0)
    say(f"config {sizes}; B={batch} T={seq} "
        f"tokens/step={tokens_per_step} params={n_params:,}")

    requests = compile_requests()
    traced = {n: pvar.read(n) for n in ("loop_passes",
                                        "loop_layer_applications")}
    t = time.perf_counter()
    step = build_step(sizes, lr).lower(params, toks[0], labs[0]).compile()
    spans["compile_s"] = time.perf_counter() - t
    for n, v in traced.items():  # what tracing the step counted
        counters[n] = pvar.read(n) - v
    mem = step.memory_analysis()
    temp = int(getattr(mem, "temp_size_in_bytes", 0) or 0)
    say(f"step executable: temporaries {temp:,} B beside "
        f"{live:,} B live; compile {spans['compile_s']:.2f}s; traced "
        f"{counters['loop_passes']} passes, "
        f"{counters['loop_layer_applications']} layer applications")

    # -- the exits' probe, on the seed's state -------------------------
    t = time.perf_counter()
    probe = exit_probe(sizes, params, toks[0], labs[0])
    spans["probes_s"] = time.perf_counter() - t
    counters["compile_requests_setup"] = requests[0]
    for name in ("exit_probe_tokens", "exit_last_pass_mass_micro"):
        counters[name] = probe[name]
    say(f"exit probe on the first batch: {probe} "
        f"({spans['probes_s']:.2f}s)")

    # -- the first steps, through the window's own call and feed -----
    t = time.perf_counter()
    params, program = first_steps(step, params, toks, labs, sizes,
                                  ctx.seed, n_check)
    spans["first_steps_s"] = time.perf_counter() - t
    say(f"first {n_check} losses: {program[0]}")

    # -- the window ---------------------------------------------------
    window_requests = requests[0]
    win = closed_loop(ctx, step, params, toks, labs, n_check,
                      traffic["trace_steps"] if ctx.trace else 0)
    del params
    losses, window_s = win["losses"], win["window_s"]
    done = len(losses)
    counters["compiles_in_window"] = requests[0] - window_requests
    failed = sum(1 for x in losses if not math.isfinite(x))
    stats = memory_stats()
    peak = max(stats.get("peak_bytes_in_use", 0), live + temp)
    say(f"window: {done} steps in {window_s:.4f}s, "
        f"{tokens_per_step * done} tokens; loss {losses[0]:.4f} -> "
        f"{losses[-1]:.4f}; compile requests in window "
        f"{counters['compiles_in_window']}; memory_stats {stats}")
    fl = flops_ouro.train_flops_per_step(sizes, batch, seq)
    rate = tokens_per_step * done / window_s
    if ctx.peaks:
        say(f"{fl:.6g} FLOP/step required; model FLOP/s utilization "
            f"{100 * rate / tokens_per_step * fl / ctx.peaks['bf16_flops_per_s']:.2f}% of "
            f"{ctx.peaks['bf16_flops_per_s']:.3g} (information; "
            "end to end, not a kernel's roofline share)")

    # -- the reference, once the program's state is freed ------------
    del step, win
    t = time.perf_counter()
    reference, ref_exits = reference_steps(sizes, toks, labs, ctx.seed, lr,
                                           n_check)
    spans["reference_s"] = time.perf_counter() - t
    say(f"reference losses: {reference[0]}; exits "
        f"{[[float(x) for x in a] for a in ref_exits]} "
        f"({spans['reference_s']:.1f}s, not in setup_s)")
    checks = checks_against(program, reference, ctx.limits, sizes)
    checks += exit_checks((probe["nll"], probe["mass"]), ref_exits,
                          ctx.limits)
    checks += [("seed_tree_remade_gap", remade, 0),
               ("nonfinite_window_losses", failed, 0)]

    return {
        "end_to_end": {"tokens_per_s": rate},
        "attempted": done, "failed": failed, "checks": checks,
        "spans": spans, "counters": counters,
        "memory_peak_bytes": peak,
        "facts": {"flops_per_step": fl,
                  "flops_per_token": fl / tokens_per_step,
                  "steps": done, "tokens_per_step": tokens_per_step,
                  "window_s": window_s},
    }
