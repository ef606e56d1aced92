"""Runner `kimivl_train`: a closed loop of single-chip train steps of
the `kimi-vl-a3b` configuration.

The system under test is the program's own step,
`jax.jit(ompi_tpu.models.transformer.make_train_step(...), donate)` —
the entry every train cell uses — with the `Config` the published
config describes: a native-resolution vision tower (`Config.vision`)
whose merged rows replace the embedding's at the image positions, and
a latent-attention decoder WITHOUT a query latent, a leading dense
layer, expert layers under a sigmoid `noaux_tc` router with a shared
expert, every layer application recomputed in the backward pass. The
batch is the dict that step takes: ids, pixels and the packing of the
cell's images (weights_kimivl.batches). Everything else is the
benchmark's: weights and batches from --seed, the window, the plain
reference (reference/kimivl_decoder.py) and the comparison. The window,
the trace window named `train` and the first steps are glm5_train.py's,
written again here because that file builds GLM-5's `Config`, tree,
reference and operation count by name.

What decides `correct`: glm5_train.py's comparison (losses; per-leaf
movement after the first step and after the last, over the leaves that
are not a router's; the routers' own gap against gross faults; the
first expert layer's routing compared as sets) and what the mechanism
adds, on the first batch: `vision_embed_gap` — the relative error of
the projector's rows (the program's probe `transformer.vision_rows`)
against the reference's — and
`tower_grad_norm_gap` — the worst per-leaf gap over the tower's and the
projector's leaves alone (a gradient that does not cross the projector
reads 1.0). The reference starts only when the program's state is
freed.
"""

from __future__ import annotations

import math
import os
import time

from benchmark import compare, flops_kimivl, weights_kimivl
from benchmark.common import compile_requests, memory_stats, say
from benchmark.runners.glm5_train import route_disagreement
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
        # the router scores `router_experts`; this chip holds
        # `n_routed_experts` of them, from `held_first`
        "n_experts": config["router_experts"],
        "held_first": config["held_first"],
        "held_count": config["n_routed_experts"],
        "top_k": config["num_experts_per_tok"],
        "norm_topk_prob": config["norm_topk_prob"],
        "routed_scale": config["routed_scaling_factor"],
        "n_shared_experts": config["n_shared_experts"],
        "q_lora_rank": config["q_lora_rank"] or 0,
        "kv_lora_rank": config["kv_lora_rank"],
        "qk_nope_dim": config["qk_nope_head_dim"],
        "qk_rope_dim": config["qk_rope_head_dim"],
        "v_head_dim": config["v_head_dim"],
        "rope_theta": float(config["rope_theta"]),
        "act": config["hidden_act"], "rms_eps": config["rms_norm_eps"],
        "tie_head": config["tie_word_embeddings"],
        "param_dtype": config["param_dtype"],
        "vision": {
            "d_model": config["vision"]["hidden_size"],
            "n_layers": config["vision"]["num_hidden_layers"],
            "n_heads": config["vision"]["num_attention_heads"],
            "d_ff": config["vision"]["intermediate_size"],
            "patch_dim": config["vision"]["patch_size"] ** 2 * 3,
            "pos_grid": tuple(config["vision"]["pos_grid"]),
            "merge": config["vision"]["merge_kernel_size"],
            "rope_theta": float(config["vision"]["rope_theta"]),
            "norm_eps": config["vision"]["layer_norm_eps"],
        },
    }


def program_config(sizes: dict):
    """The program's description of this model (one of the imports of
    the system under test in this file). A program that lacks any of
    these fields cannot run the configuration and says so here, before
    anything is placed on the device."""
    import jax.numpy as jnp

    from ompi_tpu.models import transformer as tfm
    from ompi_tpu.models import vision

    whole = sizes["held_count"] == sizes["n_experts"]
    return tfm.Config(
        vocab=sizes["vocab"], d_model=sizes["d_model"],
        n_layers=sizes["n_layers"], n_heads=sizes["n_heads"],
        d_ff=sizes["d_ff"], max_seq=sizes["max_seq"],
        first_dense=sizes["first_dense"], moe_d_ff=sizes["moe_d_ff"],
        n_experts=sizes["n_experts"], top_k=sizes["top_k"],
        norm_topk_prob=sizes["norm_topk_prob"], router_score="sigmoid",
        router_bias=True, routed_scale=sizes["routed_scale"],
        n_shared_experts=sizes["n_shared_experts"],
        held_experts=None if whole else (sizes["held_first"],
                                         sizes["held_count"]),
        mlp_act=sizes["act"], mlp_gated=True, norm="rmsnorm",
        norm_eps=sizes["rms_eps"], pos="rope", rope_theta=sizes["rope_theta"],
        tie_head=sizes["tie_head"], attn="mla",
        q_lora_rank=sizes["q_lora_rank"], kv_lora_rank=sizes["kv_lora_rank"],
        qk_nope_dim=sizes["qk_nope_dim"], qk_rope_dim=sizes["qk_rope_dim"],
        v_head_dim=sizes["v_head_dim"], rope_interleave=True,
        remat=True, param_dtype=jnp.dtype(sizes["param_dtype"]),
        vision=vision.VisionConfig(**sizes["vision"]))


def build_step(sizes: dict, lr: float):
    """The program's jitted train step."""
    import jax

    from ompi_tpu.models import transformer as tfm

    cfg, ax = program_config(sizes), tfm.Axes()
    return jax.jit(
        tfm.make_train_step(cfg, ax, tfm.param_specs(cfg, ax), lr=lr),
        donate_argnums=(0,))


def reference_spec(sizes: dict, traffic: dict):
    from benchmark.reference import kimivl_decoder as ref

    return ref.Spec(
        n_heads=sizes["n_heads"], qk_nope_dim=sizes["qk_nope_dim"],
        qk_rope_dim=sizes["qk_rope_dim"], v_head_dim=sizes["v_head_dim"],
        top_k=sizes["top_k"],
        images=tuple(tuple(g) for g in traffic["images"]),
        text_run=traffic["text_run"], vit_heads=sizes["vision"]["n_heads"],
        held_first=sizes["held_first"], routed_scale=sizes["routed_scale"],
        rope_theta=sizes["rope_theta"], rms_eps=sizes["rms_eps"],
        vit_theta=sizes["vision"]["rope_theta"],
        vit_eps=sizes["vision"]["norm_eps"])


def leaf_kinds(sizes: dict) -> list:
    """Per leaf of the parameter tree, in its flattened order:
    "router" (wg and its bias), "tower" (everything under `vision`) or
    "rest"."""
    import jax

    def kind(path) -> str:
        name = jax.tree_util.keystr(path)
        return "router" if "'wg" in name else \
            "tower" if "'vision'" in name else "rest"

    return [kind(path) for path, _ in jax.tree_util.tree_leaves_with_path(
        weights_kimivl.plan(sizes), is_leaf=lambda t: isinstance(t, tuple))]


def checks_against(prog, reference, limits, sizes) -> list:
    import numpy as np

    (p_loss, p_first, p_last), (r_loss, r_first, r_last) = prog, reference
    kinds = np.array(leaf_kinds(sizes))
    rest = kinds != "router"

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
        ("tower_grad_norm_gap",
         gap(compare.worst_leaf_gap, p_first, r_first, kinds == "tower"),
         limits["tower_grad_norm_gap"]),
    ]


def program_rows(sizes: dict, params, batch):
    """The projector's rows of a batch as the PROGRAM computes them
    (its probe `vision_rows`)."""
    from ompi_tpu.models import transformer as tfm

    return tfm.vision_rows(params, batch, program_config(sizes))


def rows_gap(program, reference) -> float:
    """|program - reference| / |reference| over the whole [rows, d]."""
    import jax.numpy as jnp

    p, r = program.astype(jnp.float32), reference.astype(jnp.float32)
    return float(jnp.linalg.norm(p - r) / jnp.linalg.norm(r))


def probes(sizes: dict, params, toks, steps: int) -> dict:
    """The program's set-up probes on the seed's state. `route_counts`
    on the first `steps` batches: the worst shortfall of a layer's
    assignments against tokens x top_k, the fullest expert over the
    mean, the assignments that fell to the held experts a batch.
    `vision_stats` and the projector's rows on the first batch."""
    import numpy as np

    from ompi_tpu.core import pvar
    from ompi_tpu.models import transformer as tfm
    from ompi_tpu.models import vision

    cfg = program_config(sizes)
    names = ("moe_assignments", "moe_dropped_assignments",
             "moe_held_assignments", "vision_images", "vision_diag_pairs",
             "vision_row_pairs")
    before = {n: pvar.read(n) for n in names}
    short, load, held = 0, 0.0, 0
    first, n = sizes["held_first"], sizes["held_count"]
    for i in range(steps):
        counts = np.asarray(tfm.route_counts(params, toks[i], cfg))
        want = toks[i]["tokens"].size * sizes["top_k"]
        short = max(short, int(np.abs(counts.sum(1) - want).max()))
        load = max(load, float((counts.max(1) / counts.mean(1)).max()))
        held += int(counts[:, first:first + n].sum())
    stats = vision.vision_stats(toks[0])
    out = {"route_counts_short": short, "load_max_over_mean": load,
           "held_rows_per_batch": held / steps, "vision_stats": stats,
           **{n: pvar.read(n) - v for n, v in before.items()}}
    out["experts"] = np.asarray(tfm.route_experts(params, toks[0], cfg)[0])
    out["rows"] = program_rows(sizes, params, toks[0])
    return out


def reference_first_batch(sizes, traffic, toks, seed, quantize=None):
    """(the first expert layer's chosen experts [T, E], the projector's
    rows) of the plain reference on the first batch, from the seed's
    state."""
    import jax

    from benchmark.reference import kimivl_decoder as ref

    spec = reference_spec(sizes, traffic)
    params = weights_kimivl.device_init(sizes, seed)
    chosen = ref.chosen_experts(params, toks[0], spec, quantize)
    rows = jax.jit(ref.vision_rows, static_argnames=("spec", "quantize"))(
        params, toks[0], spec=spec, quantize=quantize)
    return chosen, rows


def first_steps(step, params, toks, labs, sizes, seed, steps):
    """Drive the compiled step through its first steps from the seed's
    state: (state, (losses, per-leaf movement after the first step,
    after the last))."""
    losses, moved = [], {}
    for i in range(steps):
        params, loss = step(params, toks[i], labs[i])
        losses.append(float(loss))
        if i in (0, steps - 1):
            moved[i] = weights_kimivl.delta_norms(sizes, seed, params)
    return params, (losses, moved[0], moved[steps - 1])


def reference_steps(sizes, traffic, toks, labs, seed, lr, steps,
                    quantize=None):
    """The plain reference through the same first steps: losses, and
    per-leaf movement after the first step and after the last."""
    from benchmark.reference import kimivl_decoder as ref

    spec = reference_spec(sizes, traffic)
    params = weights_kimivl.device_init(sizes, seed)
    losses, moved = [], {}
    for i in range(steps):
        params, val = ref.sgd_step(params, toks[i], labs[i], lr, spec,
                                   quantize)
        losses.append(float(val))
        if i in (0, steps - 1):
            moved[i] = weights_kimivl.delta_norms(sizes, seed, params)
    return losses, moved[0], moved[steps - 1]


def run(ctx) -> dict:
    import jax

    from ompi_tpu.core import pvar

    traffic = ctx.traffic
    sizes = model_sizes(ctx.config)
    program_config(sizes)  # a program without these fields stops here
    batch, seq, lr = traffic["batch"], traffic["seq"], traffic["lr"]
    images = [tuple(g) for g in traffic["images"]]
    n_check = traffic["check_steps"]
    tokens_per_step = batch * seq
    spans, counters = {}, {}

    # -- set-up: state, batches, the compiled step -------------------
    t = time.perf_counter()
    params = weights_kimivl.device_init(sizes, ctx.seed)
    toks, labs = weights_kimivl.batches(sizes, traffic, ctx.seed)
    jax.block_until_ready((params, toks, labs))
    # what the comparison rests on: the seed's tree made again leaf by
    # leaf is THIS tree, bit for bit
    remade = float(weights_kimivl.delta_norms(sizes, ctx.seed,
                                              params).max())
    spans["weights_s"] = time.perf_counter() - t
    n_params = sum(x.size for x in jax.tree.leaves(params))
    live = memory_stats().get("bytes_in_use", 0)
    say(f"config {sizes}; B={batch} T={seq} images={images} "
        f"tokens/step={tokens_per_step} params={n_params:,}")

    requests = compile_requests()
    traced = {n: pvar.read(n) for n in (
        "attn_blockwise_layers", "attn_reference_layers",
        "attn_segment_layers", "attn_mla_plain_q_layers",
        "vision_patches", "vision_image_positions",
        "remat_kept_applications", "remat_whole_applications",
        "remat_kept_bytes", "moe_grouped_kernel_layers")}
    t = time.perf_counter()
    step = build_step(sizes, lr).lower(params, toks[0], labs[0]).compile()
    spans["compile_s"] = time.perf_counter() - t
    for name, was in traced.items():  # what the step's ONE trace counted
        counters[name] = pvar.read(name) - was
    mem = step.memory_analysis()
    temp = int(getattr(mem, "temp_size_in_bytes", 0) or 0)
    say(f"step executable: temporaries {temp:,} B beside "
        f"{live:,} B live; compile {spans['compile_s']:.2f}s; its trace "
        f"counted { {k: v for k, v in counters.items()} }")

    # -- the probes, on the seed's state -------------------------------
    t = time.perf_counter()
    probe = probes(sizes, params, toks, n_check)
    spans["probes_s"] = time.perf_counter() - t
    _, moe = flops_kimivl.layer_counts(sizes)
    held_rows = probe["held_rows_per_batch"]
    counters["compile_requests_setup"] = requests[0]
    counters["moe_load_max_over_mean"] = probe["load_max_over_mean"]
    for name in ("moe_assignments", "moe_held_assignments", "vision_images",
                 "vision_diag_pairs", "vision_row_pairs"):
        counters[name] = probe[name]
    say(f"probes on {n_check} batches: "
        f"{ {k: v for k, v in probe.items() if k not in ('experts', 'rows')} } "
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
    fl = flops_kimivl.train_flops_per_step(sizes, batch, seq, images,
                                           held_rows)
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
    reference = reference_steps(sizes, traffic, toks, labs, ctx.seed, lr,
                                n_check)
    chosen, ref_rows = reference_first_batch(sizes, traffic, toks, ctx.seed)
    spans["reference_s"] = time.perf_counter() - t
    say(f"reference losses: {reference[0]} "
        f"({spans['reference_s']:.1f}s, not in setup_s)")
    checks = checks_against(program, reference, ctx.limits, sizes)
    checks += [("route_disagreement",
                route_disagreement(probe["experts"], chosen),
                ctx.limits["route_disagreement"]),
               ("vision_embed_gap", rows_gap(probe["rows"], ref_rows),
                ctx.limits["vision_embed_gap"]),
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
                      flops_kimivl.expert_flops_per_step(sizes, held_rows),
                  "vit_attn_flops_per_step":
                      flops_kimivl.vit_attn_flops_per_step(sizes, images),
                  "mla_attn_flops_per_step":
                      flops_kimivl.mla_attn_flops_per_step(sizes, batch,
                                                           seq),
                  "held_rows_per_step": held_rows,
                  "vision_stats": probe["vision_stats"],
                  "steps": done, "tokens_per_step": tokens_per_step,
                  "window_s": window_s},
    }
