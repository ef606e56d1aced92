"""Runner `train_step`: a closed loop of single-chip train steps.

The system under test is the program's own step,
`jax.jit(ompi_tpu.models.transformer.make_train_step(...), donate)`,
reached the way a job reaches it (launcher -> mpi.Init()). Everything
else is the benchmark's: weights and batches from --seed, the window,
the plain reference and the comparison.

Set-up builds ONE object — the compiled step with its state — drives
it through `check_steps` steps on batches that all differ, reading how
far each leaf moved after the first and after the last, and hands the
same object to the window. When the window has closed and the state is
freed, the plain reference follows those first steps from the same
seed and the two are compared (see compare.py and PERF.md, section 2).
"""

from __future__ import annotations

import math
import os
import time

from benchmark import compare, flops, weights
from benchmark.common import compile_requests, memory_stats, say


def model_sizes(config: dict) -> dict:
    """The configuration file speaks the source's language (Hugging
    Face keys); the program and the reference want these."""
    return {
        "vocab": config["vocab_size"], "d_model": config["hidden_size"],
        "n_layers": config["num_hidden_layers"],
        "n_heads": config["num_attention_heads"],
        "d_ff": config["ffn_dim"],
        "max_seq": config["max_position_embeddings"],
        "param_dtype": config["param_dtype"],
    }


def build_step(sizes: dict, lr: float):
    """The program's jitted train step (the one import of the system
    under test in this file)."""
    import jax
    import jax.numpy as jnp

    from ompi_tpu.models import transformer as tfm

    cfg = tfm.Config(
        vocab=sizes["vocab"], d_model=sizes["d_model"],
        n_layers=sizes["n_layers"], n_heads=sizes["n_heads"],
        d_ff=sizes["d_ff"], max_seq=sizes["max_seq"],
        param_dtype=jnp.dtype(sizes["param_dtype"]))
    ax = tfm.Axes()
    return jax.jit(
        tfm.make_train_step(cfg, ax, tfm.param_specs(cfg, ax), lr=lr),
        donate_argnums=(0,))


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
            start = weights.device_init(sizes, seed)
            moved[i] = jax.device_get(
                compare.leaf_delta_norms(params, start))
            del start
    return params, (losses, moved[0], moved[steps - 1])


def reference_steps(sizes, toks, labs, seed, lr, steps, quantize=None):
    """The plain reference through the same first steps: losses, and
    per-leaf movement after the first step and after the last."""
    import jax

    from benchmark.reference import opt_decoder as ref

    params = weights.device_init(sizes, seed)
    start = weights.device_init(sizes, seed)
    losses, moved = [], {}
    for i in range(steps):
        params, val = ref.sgd_step(params, toks[i], labs[i], lr,
                                   sizes["n_heads"], quantize)
        losses.append(float(val))
        if i in (0, steps - 1):
            moved[i] = jax.device_get(
                compare.leaf_delta_norms(params, start))
    return losses, moved[0], moved[steps - 1]


def checks_against(prog, reference, limits) -> list:
    (p_loss, p_first, p_last), (r_loss, r_first, r_last) = prog, reference
    return [
        ("loss_gap", max(compare.rel_gap(a, b)
                         for a, b in zip(p_loss, r_loss)),
         limits["loss_gap"]),
        ("first_grad_norm_gap", compare.worst_leaf_gap(p_first, r_first),
         limits["first_grad_norm_gap"]),
        ("first_grad_norm_rms_gap", compare.rms_leaf_gap(p_first, r_first),
         limits["first_grad_norm_rms_gap"]),
        ("param_change_norm_gap", compare.worst_leaf_gap(p_last, r_last),
         limits["param_change_norm_gap"]),
    ]


def _stolen_s() -> float:
    """Seconds the hypervisor kept this machine's CPUs from it so far
    (`steal` on the first line of /proc/stat, all CPUs), or nan."""
    try:
        with open("/proc/stat") as f:
            return int(f.readline().split()[8]) / os.sysconf("SC_CLK_TCK")
    except (OSError, IndexError, ValueError):
        return float("nan")


def run(ctx) -> dict:
    import jax

    traffic = ctx.traffic
    sizes = model_sizes(ctx.config)
    batch, seq, lr = traffic["batch"], traffic["seq"], traffic["lr"]
    n_check = traffic["check_steps"]
    tokens_per_step = batch * seq
    spans, counters = {}, {}

    # -- set-up: state, batches, the compiled step -------------------
    t = time.perf_counter()
    params = weights.device_init(sizes, ctx.seed)
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
    counters["compile_requests_setup"] = requests[0]
    mem = step.memory_analysis()
    temp = int(getattr(mem, "temp_size_in_bytes", 0) or 0)
    say(f"step executable: temporaries {temp:,} B beside "
        f"{live:,} B live; compile {spans['compile_s']:.2f}s")

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
    fl = flops.train_flops_per_token(sizes, seq)
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
    checks = checks_against(program, reference, ctx.limits)
    checks.append(("nonfinite_window_losses", failed, 0))

    return {
        "end_to_end": {"tokens_per_s": rate},
        "attempted": done, "failed": failed, "checks": checks,
        "spans": spans, "counters": counters,
        "memory_peak_bytes": peak,
        "facts": {"flops_per_step": fl * tokens_per_step,
                  "flops_per_token": fl, "steps": done,
                  "tokens_per_step": tokens_per_step,
                  "window_s": window_s},
    }
