"""Runner `glm5_train`: a closed loop of single-chip train steps of the
`glm-5` configuration.

The system under test is the program's own step,
`jax.jit(ompi_tpu.models.transformer.make_train_step(...), donate)` —
the entry the `opt-30b` and `olmoe-1b-7b` cells use — with the `Config`
the published config describes: latent attention with the learned
sparse-attention indexer, a leading dense layer, expert layers whose
sigmoid router scores all 256 experts while this chip holds 8 of them
and the shared one, a multi-token-prediction module, every layer
recomputed in the backward pass. Everything else is the benchmark's:
weights and batches from --seed, the window, the plain reference
(reference/glm5_decoder.py) and the comparison. The window, the trace
window named `train` and the first steps are olmoe_train.py's, written
again here because that file builds OLMoE's `Config`, tree, reference
and operation count by name.

What decides `correct`: olmoe_train.py's comparison (losses; per-leaf
movement after the first step and after the last, over the leaves that
are neither a router's nor an indexer's; the routers' and the indexers'
own gaps against gross faults; the routing compared as sets) and one
number more, because a second choice here is discrete: the share of
layer 0's selected (query, key) pairs on the first batch that are not
the reference's, among the queries that have more than `index_topk`
keys to choose from (`select_disagreement`). The state is 6.6 GB, so
nothing is drawn twice: how far each leaf moved is read against the
seed's tree made again one leaf at a time (weights_glm5.delta_norms),
and the reference starts only when the program's state is freed.
"""

from __future__ import annotations

import math
import os
import time

from benchmark import compare, flops_glm5, weights, weights_glm5
from benchmark.common import compile_requests, memory_stats, say
from benchmark.runners.train_step import _stolen_s


def model_sizes(config: dict) -> dict:
    """The configuration file speaks the source's language (Hugging
    Face keys, and the benchmark's own for what the source has none);
    the program, the reference and the counts want these."""
    return {
        "vocab": config["vocab_size"], "d_model": config["hidden_size"],
        "n_layers": config["num_hidden_layers"],
        "first_dense": config["first_k_dense_replace"],
        "n_heads": config["num_attention_heads"],
        "d_ff": config["intermediate_size"],
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
        "q_lora_rank": config["q_lora_rank"],
        "kv_lora_rank": config["kv_lora_rank"],
        "qk_nope_dim": config["qk_nope_head_dim"],
        "qk_rope_dim": config["qk_rope_head_dim"],
        "v_head_dim": config["v_head_dim"],
        "rope_interleave": config["rope_interleave"],
        "rope_theta": float(config["rope_parameters"]["rope_theta"]),
        "index_heads": config["index_n_heads"],
        "index_dim": config["index_head_dim"],
        "index_topk": config["index_topk"],
        "act": config["hidden_act"], "rms_eps": config["rms_norm_eps"],
        "tie_head": config["tie_word_embeddings"],
        "mtp_layers": config["num_nextn_predict_layers"],
        "mtp_weight": config["mtp_loss_weight"],
        "index_weight": config["index_loss_weight"],
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
        first_dense=sizes["first_dense"], moe_d_ff=sizes["moe_d_ff"],
        n_experts=sizes["n_experts"], top_k=sizes["top_k"],
        norm_topk_prob=sizes["norm_topk_prob"], router_score="sigmoid",
        router_bias=True, routed_scale=sizes["routed_scale"],
        n_shared_experts=sizes["n_shared_experts"],
        held_experts=(sizes["held_first"], sizes["held_count"]),
        mlp_act=sizes["act"], mlp_gated=True, norm="rmsnorm",
        norm_eps=sizes["rms_eps"], pos="rope", rope_theta=sizes["rope_theta"],
        tie_head=sizes["tie_head"], attn="mla",
        q_lora_rank=sizes["q_lora_rank"], kv_lora_rank=sizes["kv_lora_rank"],
        qk_nope_dim=sizes["qk_nope_dim"], qk_rope_dim=sizes["qk_rope_dim"],
        v_head_dim=sizes["v_head_dim"],
        rope_interleave=sizes["rope_interleave"],
        index_heads=sizes["index_heads"], index_dim=sizes["index_dim"],
        index_topk=sizes["index_topk"],
        index_loss_weight=sizes["index_weight"],
        mtp_layers=sizes["mtp_layers"], mtp_weight=sizes["mtp_weight"],
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
    from benchmark.reference import glm5_decoder as ref

    return ref.Spec(
        n_heads=sizes["n_heads"], qk_nope_dim=sizes["qk_nope_dim"],
        qk_rope_dim=sizes["qk_rope_dim"], v_head_dim=sizes["v_head_dim"],
        index_heads=sizes["index_heads"], index_topk=sizes["index_topk"],
        top_k=sizes["top_k"], held_first=sizes["held_first"],
        routed_scale=sizes["routed_scale"], rope_theta=sizes["rope_theta"],
        rms_eps=sizes["rms_eps"], index_weight=sizes["index_weight"],
        mtp_weight=sizes["mtp_weight"])


def leaf_kinds(sizes: dict) -> list:
    """Per leaf of the parameter tree, in its flattened order:
    "router" (wg and its bias), "indexer" (wi_*) or "rest"."""
    import jax

    def kind(path) -> str:
        name = jax.tree_util.keystr(path)
        return "router" if "'wg" in name else \
            "indexer" if "'wi_" in name else "rest"

    return [kind(path) for path, _ in jax.tree_util.tree_leaves_with_path(
        weights_glm5.plan(sizes), is_leaf=lambda t: isinstance(t, tuple))]


def checks_against(prog, reference, limits, sizes) -> list:
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
        ("indexer_grad_norm_gap",
         gap(compare.worst_leaf_gap, p_first, r_first, kinds == "indexer"),
         limits["indexer_grad_norm_gap"]),
    ]


def probes(sizes: dict, params, toks, steps: int) -> dict:
    """The program's two set-up probes on the seed's state.
    `route_counts` on the first `steps` batches: the worst shortfall of
    a layer's assignments against tokens x top_k, the fullest expert
    over the mean (worst layer, worst batch), the assignments that fell
    to the held experts a batch (all expert layers of the trunk), what
    the program's counters gained. `dsa_selection` on the first batch:
    layer 0's selection (kept on the device for the reference) and the
    two pair counters."""
    import numpy as np

    from ompi_tpu.core import pvar
    from ompi_tpu.models import transformer as tfm

    cfg = program_config(sizes)
    names = ("moe_assignments", "moe_dropped_assignments",
             "moe_held_assignments", "dsa_selected_pairs",
             "dsa_causal_pairs")
    before = {n: pvar.read(n) for n in names}
    short, load = 0, 0.0
    for i in range(steps):
        counts = np.asarray(tfm.route_counts(params, toks[i], cfg))
        want = toks[i].size * sizes["top_k"]
        short = max(short, int(np.abs(counts.sum(1) - want).max()))
        load = max(load, float((counts.max(1) / counts.mean(1)).max()))
    keep = tfm.dsa_selection(params, toks[0], cfg)
    out = {"route_counts_short": short, "load_max_over_mean": load,
           **{n: pvar.read(n) - v for n, v in before.items()}}
    out["held_rows_per_batch"] = out["moe_held_assignments"] / steps
    # the first expert layer's choices on the first batch and layer 0's
    # selection, for the reference
    out["experts"] = np.asarray(tfm.route_experts(params, toks[0], cfg)[0])
    out["keep"] = keep[0] if keep.shape[0] else None
    return out


def route_disagreement(program_experts, chosen) -> float:
    """The share of the first expert layer's token-expert assignments
    ([T, k] expert numbers) that are not among the reference's chosen
    ([T, E] bool)."""
    import numpy as np

    chosen = np.asarray(chosen)
    same = np.take_along_axis(chosen, np.asarray(program_experts), 1).sum()
    return 1.0 - float(same) / program_experts.size


def chosen_numbers(chosen, top_k: int):
    """A [T, E] mask of chosen experts as route_experts gives a
    choice: expert numbers [T, k], ascending."""
    import jax.numpy as jnp
    import numpy as np

    return np.asarray(jnp.argsort(~chosen, axis=-1, stable=True)[:, :top_k])


def select_disagreement(program_keep, reference_keep, topk: int) -> float:
    """The share of the program's selected (query, key) pairs that are
    not the reference's, among the queries with more than `topk`
    causal keys (the others select every key). [B, T, T] bool each."""
    import jax.numpy as jnp

    choosing = (jnp.arange(program_keep.shape[-1]) >= topk)[None, :, None]
    mine = program_keep & choosing
    return float((mine & ~reference_keep).sum() / mine.sum())


def reference_choices(sizes, toks, seed, quantize=None):
    """(the first expert layer's chosen experts [T, E], layer 0's
    selection [B, T, T] or None) of the plain reference on the first
    batch, from the seed's state."""
    from benchmark.reference import glm5_decoder as ref

    spec = reference_spec(sizes)
    params = weights_glm5.device_init(sizes, seed)
    chosen = ref.chosen_experts(params, toks[0], spec, quantize)
    keep = ref.selection(params, toks[0], spec, quantize) \
        if flops_glm5.selects(sizes, toks[0].shape[1]) else None
    return chosen, keep


def first_steps(step, params, toks, labs, sizes, seed, steps):
    """Drive the compiled step through its first steps from the seed's
    state: (state, (losses, per-leaf movement after the first step,
    after the last))."""
    losses, moved = [], {}
    for i in range(steps):
        params, loss = step(params, toks[i], labs[i])
        losses.append(float(loss))
        if i in (0, steps - 1):
            moved[i] = weights_glm5.delta_norms(sizes, seed, params)
    return params, (losses, moved[0], moved[steps - 1])


def reference_steps(sizes, toks, labs, seed, lr, steps, quantize=None):
    """The plain reference through the same first steps: losses, and
    per-leaf movement after the first step and after the last."""
    from benchmark.reference import glm5_decoder as ref

    spec = reference_spec(sizes)
    params = weights_glm5.device_init(sizes, seed)
    losses, moved = [], {}
    for i in range(steps):
        params, val = ref.sgd_step(params, toks[i], labs[i], lr, spec,
                                   quantize)
        losses.append(float(val))
        if i in (0, steps - 1):
            moved[i] = weights_glm5.delta_norms(sizes, seed, params)
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
    params = weights_glm5.device_init(sizes, ctx.seed)
    toks, labs = weights.batches(sizes["vocab"], traffic["n_batches"],
                                 batch, seq, ctx.seed)
    jax.block_until_ready((params, toks, labs))
    # what the comparison rests on: the seed's tree made again leaf by
    # leaf is THIS tree, bit for bit
    remade = float(weights_glm5.delta_norms(sizes, ctx.seed, params).max())
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

    # -- the probes, on the seed's state -------------------------------
    t = time.perf_counter()
    probe = probes(sizes, params, toks, n_check)
    spans["probes_s"] = time.perf_counter() - t
    dense, moe = flops_glm5.layer_counts(sizes)
    trunk_moe = moe - sizes["mtp_layers"]
    # the probe runs the trunk: the MTP module's expert layer is taken
    # to get the trunk's mean
    held_rows = probe["held_rows_per_batch"] * moe / max(trunk_moe, 1)
    counters["compile_requests_setup"] = requests[0]
    counters["moe_load_max_over_mean"] = probe["load_max_over_mean"]
    for name in ("moe_assignments", "moe_held_assignments",
                 "dsa_selected_pairs", "dsa_causal_pairs"):
        counters[name] = probe[name]
    say(f"probes on {n_check} batches: "
        f"{ {k: v for k, v in probe.items() if k not in ('experts', 'keep')} } "
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
    fl = flops_glm5.train_flops_per_step(sizes, batch, seq, held_rows)
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
    chosen, ref_keep = reference_choices(sizes, toks, ctx.seed)
    spans["reference_s"] = time.perf_counter() - t
    say(f"reference losses: {reference[0]} "
        f"({spans['reference_s']:.1f}s, not in setup_s)")
    checks = checks_against(program, reference, ctx.limits, sizes)
    checks.append(("route_disagreement",
                   route_disagreement(probe["experts"], chosen),
                   ctx.limits["route_disagreement"]))
    if ref_keep is not None:
        checks.append(("select_disagreement", select_disagreement(
            probe["keep"], ref_keep, sizes["index_topk"]),
            ctx.limits["select_disagreement"]))
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
                      flops_glm5.expert_flops_per_step(sizes, held_rows),
                  "dsa_index_flops_per_step":
                      flops_glm5.dsa_index_flops_per_step(sizes, batch, seq),
                  "dsa_attend_flops_per_step":
                      flops_glm5.dsa_attend_flops_per_step(sizes, batch, seq),
                  "held_rows_per_step": held_rows,
                  "steps": done, "tokens_per_step": tokens_per_step,
                  "window_s": window_s},
    }
