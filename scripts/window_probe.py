#!/usr/bin/env python3
"""One windowed attention core alone on one chip: which tile, which backward.

At ``kexaone-train-t8192``'s shape (``[1, 8192, 64, 128]`` bfloat16
under a window of 128 keys; ``--t --heads --window`` for another), time
forward + backward of the library's splash-attention kernels under a
``LocalMask`` WITH the layout changes into and out of their head-major
operands (what the step's ``attn_core/attn_window`` scope holds), for
each ``ROWSxKEYS`` tile of ``--tiles`` and each form of the backward
pass (``two``: `splash_mha_dq` + `splash_mha_dkv`; ``fused``: one
kernel and a partial dq a key tile), and print a line a variant: ms a
call, the pairs the kernels walk over the pairs the window keeps
(``ops.attention.window_tiles``) and the largest gap of the output and
of dq, dk, dv to the first variant's. ``--rule 1`` times
``ops.attention.attention`` itself besides: the tile the rule picks.
PERF.md 6 (PR 50) quotes the table; the rule is ``_WINDOW_TILES``'.

    chiprun -- python scripts/window_probe.py [--tiles 512x512 128x128]

``--interpret 1`` is its CPU twin at a toy size (results, no times).
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from ompi_tpu.ops import attention as att  # noqa: E402


def kernel(t, heads, window, rows, keys, fused, interpret):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)

    compute = min(keys, att._KV_COMPUTE)
    sizes = dict(block_q=rows, block_kv=keys, block_kv_compute=compute,
                 block_q_dkv=rows, block_kv_dkv=keys,
                 block_kv_dkv_compute=compute, use_fused_bwd_kernel=fused)
    if not fused:
        sizes.update(block_q_dq=rows, block_kv_dq=keys)
    mask = sm.MultiHeadMask(
        [sm.LocalMask((t, t), (window - 1, 0), 0)] * heads)
    with jax.ensure_compile_time_eval():
        return sk.make_splash_mha_single_device(
            mask, block_sizes=sk.BlockSizes(**sizes), interpret=interpret)


def core(attend):
    """[B, T, H, D] in and out around a [H, T, D] kernel: the value and
    the three cotangents under a given do."""
    def run(q, k, v, do):
        def out(q, k, v):
            qkv = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
            return jax.vmap(attend)(*qkv).transpose(0, 2, 1, 3)

        o, back = jax.vjp(out, q, k, v)
        return (o,) + back(do)
    return jax.jit(run)


def timed(fn, args, calls):
    out = jax.block_until_ready(fn(*args))
    for _ in range(2):
        jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return out, (time.perf_counter() - start) / calls * 1e3


def gap(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--t", type=int, default=8192)
    ap.add_argument("--heads", type=int, default=64)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--window", type=int, default=128)
    ap.add_argument("--tiles", nargs="*", default=[
        "512x512", "256x256", "128x128", "512x128", "256x128", "512x256"])
    ap.add_argument("--forms", nargs="*", default=["two", "fused"])
    ap.add_argument("--rule", type=int, default=1)
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--interpret", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/window_probe.txt")
    args = ap.parse_args()
    interpret = bool(args.interpret)
    device = jax.devices()[0]
    if not interpret and device.platform != "tpu":
        raise SystemExit("a time comes from the chip: no TPU here "
                         "(--interpret 1 for the CPU twin)")
    t, h, d, w = args.t, args.heads, args.head_dim, args.window
    keys = jax.random.split(jax.random.key(args.seed), 4)
    q, k, v, do = (jax.random.normal(kk, (1, t, h, d), jnp.float32).astype(
        jnp.bfloat16) for kk in keys)
    q = (q.astype(jnp.float32) * d ** -0.5).astype(jnp.bfloat16)
    kept = sum(min(i + 1, w) for i in range(t))
    lines = [f"device {device.platform} {device.device_kind}; [1, {t}, {h}, "
             f"{d}] bfloat16 under a window of {w}: {kept:,} pairs kept a "
             f"head; forward + backward with the layout changes, ms a call "
             f"over {args.calls} calls"]
    first = None

    def report(name, fn, walked):
        nonlocal first
        try:
            out, ms = timed(fn, (q, k, v, do), args.calls)
        except Exception as e:  # a variant the chip has no room for
            lines.append(f"{name:24s} failed: {type(e).__name__}: "
                         f"{str(e).splitlines()[0][:120]}")
            print(lines[-1], flush=True)
            return
        first = first or out
        gaps = " ".join(f"{n} {gap(a, b):.2e}" for n, a, b in zip(
            ("o", "dq", "dk", "dv"), out, first))
        lines.append(f"{name:24s} {ms:8.3f} ms  walked/kept {walked:5.2f}  "
                     f"gap to the first: {gaps}")
        print(lines[-1], flush=True)

    for tile in args.tiles:
        rows, cols = (int(n) for n in tile.split("x"))
        walked = att.window_tiles(t, (rows, cols), w) * min(rows, cols) ** 2 / kept
        for form in args.forms:
            report(f"{tile} {form}", core(kernel(
                t, h, w, rows, cols, form == "fused", interpret)), walked)
    if args.rule:
        tile = att.blockwise_tile(jax.default_backend(), t, t, d, window=w)
        @jax.jit
        def attend(q, k, v, do):
            o, back = jax.vjp(lambda q, k, v: att.attention(
                q, k, v, causal=True, scale=1.0, window=w), q, k, v)
            return (o,) + back(do)

        report(f"the rule: {tile}", attend, att.window_tiles(t, tile, w)
               * tile ** 2 / kept if tile else float("nan"))
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        f.write("\n".join(lines) + "\n")


if __name__ == "__main__":
    main()
