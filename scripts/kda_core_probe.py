#!/usr/bin/env python3
"""The delta rule's core alone on one chip: ``jax.numpy`` against the kernels.

At solar2-train-t8192's shape for one run of heads (q, k, v, g ``[1,
8192, 16 x 128]``, chunks of 64, bfloat16) time, forward and forward +
backward:

- ``numpy``: ``kda.chunked_delta(..., per=None)`` — batched ``jax.numpy``
  around a ``lax.scan``, the kernels' oracle;
- ``fused/<per>``: ``kda.kernel_delta`` — ONE kernel a pass (chunk-local
  values, carry and read-out in one grid step), `per` heads a step;
- ``split/<per>`` (forward only): the same values in TWO kernels, a
  chunk-local one whose grid steps are independent and which writes W,
  U, Kd, Qd, P and exp(G_C) to HBM, and a carry that reads them — the
  published kernels' split, built here from ``kda._within``.

and print each kernel form's largest gap to ``numpy`` relative to the
largest value, of o, the last state and every cotangent. PERF.md 6
(PR 47) quotes this table for "one kernel or two" and for the heads a
grid step takes.

    chiprun -- python scripts/kda_core_probe.py [--pers 1 2 4] [--t 8192]

``--interpret 1`` is its CPU twin at a toy size (results, no times).
"""

import argparse
import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from ompi_tpu.ops import kda  # noqa: E402

F32 = jnp.float32


def operands(seed, b, t, h, width, dtype):
    """The core's operands as the mixer hands them on, [B, T, H K]."""
    ks = jax.random.split(jax.random.key(seed), 5)
    shape = (b, t, h, width)
    q = kda.l2norm(jax.random.normal(ks[0], shape), 1e-6) * width ** -0.5
    k = kda.l2norm(jax.random.normal(ks[1], shape), 1e-6)
    v = jax.random.normal(ks[2], shape)
    g = -jnp.exp(jax.random.uniform(ks[3], shape, minval=-7.0, maxval=-1.0))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    flat = [a.reshape(b, t, -1) for a in (q.astype(dtype), k.astype(dtype),
                                          v.astype(dtype), g)]
    return (*flat, beta)


def numpy_form(heads, chunk):
    def run(q, k, v, g, beta):
        b, t, _ = q.shape
        o, last = kda.chunked_delta(
            *(a.reshape(b, t, heads, -1) for a in (q, k, v, g)), beta, chunk)
        return o.reshape(b, t, -1), jnp.swapaxes(last, -1, -2)
    return run


def fused_form(heads, chunk, per, interpret):
    return functools.partial(kda.kernel_delta, heads=heads, chunk=chunk,
                             per=per, interpret=interpret)


# -- the forward pass in two kernels -------------------------------------------

def _local_kernel(q, k, v, g, beta, w, u, kd, qd, pairs, grown, *, per):
    dtype = v.dtype
    width = k.shape[1] // per
    c = k.shape[0]
    for h in range(per):
        ks = slice(h * width, (h + 1) * width)
        m = kda._within(q[:, ks], k[:, ks], v[:, ks], g[:, ks],
                        beta[:, h:h + 1])
        w[:, ks] = m["w"].astype(dtype)
        u[:, ks] = m["u"].astype(dtype)
        kd[:, ks] = (k[:, ks].astype(F32) * m["fade"]).astype(dtype)
        qd[:, ks] = (q[:, ks].astype(F32) * m["decay"]).astype(dtype)
        pairs[:, h * c:(h + 1) * c] = m["pairs"].astype(dtype)
        grown[:, ks] = m["grown"]


def _carry_kernel(w, u, kd, qd, pairs, grown, o, last, s_s, *, per):
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _():
        s_s[...] = jnp.zeros_like(s_s)

    dtype = o.dtype
    width = w.shape[1] // per
    c = w.shape[0]
    for h in range(per):
        ks = slice(h * width, (h + 1) * width)
        s = s_s[h]
        s_b = s.astype(dtype)
        vp = (u[:, ks].astype(F32) - kda._mxu(w[:, ks], s_b, kda._NT)
              ).astype(dtype)
        o[:, ks] = (kda._mxu(qd[:, ks], s_b, kda._NT) + kda._mxu(
            pairs[:, h * c:(h + 1) * c], vp)).astype(dtype)
        s_s[h] = grown[:, ks] * s + kda._mxu(vp, kd[:, ks], kda._TN)

    @pl.when(n == pl.num_programs(2) - 1)
    def _():
        last[...] = s_s[...]


def split_form(heads, chunk, per, interpret):
    """The forward pass as `kda_chunk_fwd` + `kda_carry_fwd` (keys and
    values of one width)."""
    def run(q, k, v, g, beta):
        b, t, hk = q.shape
        nc, width, dtype = t // chunk, hk // heads, v.dtype
        grid = (b, heads // per, nc)
        rows = pl.BlockSpec((None, chunk, per * width),
                            lambda b, h, c: (b, c, h))
        square = pl.BlockSpec((None, chunk, per * chunk),
                              lambda b, h, c: (b, c, h))
        one = pl.BlockSpec((None, None, 1, per * width),
                           lambda b, h, c: (b, c, 0, h))
        beta_spec = pl.BlockSpec((None, None, chunk, per),
                                 lambda b, h, c: (b, h, c, 0))
        like = jax.ShapeDtypeStruct((b, t, hk), dtype)
        like_p = jax.ShapeDtypeStruct((b, t, heads * chunk), dtype)
        like_g = jax.ShapeDtypeStruct((b, nc, 1, hk), F32)

        def params(*semantics):
            return dict(grid=grid, interpret=interpret,
                        compiler_params=pltpu.CompilerParams(
                            dimension_semantics=semantics,
                            vmem_limit_bytes=kda.VMEM_LIMIT_BYTES))

        grouped = jnp.moveaxis(beta.reshape(b, t, heads // per, per), 2, 1)
        w, u, kd, qd, pairs, grown = pl.pallas_call(
            functools.partial(_local_kernel, per=per), name="kda_chunk_fwd",
            out_shape=(like, like, like, like, like_p, like_g),
            in_specs=[rows, rows, rows, rows, beta_spec],
            out_specs=(rows, rows, rows, rows, square, one),
            **params("parallel", "parallel", "parallel"))(q, k, v, g, grouped)
        return pl.pallas_call(
            functools.partial(_carry_kernel, per=per), name="kda_carry_fwd",
            out_shape=(like, jax.ShapeDtypeStruct((b, heads, width, width),
                                                  F32)),
            in_specs=[rows, rows, rows, rows, square, one],
            out_specs=(rows, pl.BlockSpec((None, per, width, width),
                                          lambda b, h, c: (b, h, 0, 0))),
            scratch_shapes=[pltpu.VMEM((per, width, width), F32)],
            **params("parallel", "parallel", "arbitrary"))(
                w, u, kd, qd, pairs, grown)
    return run


# -- timing and gaps -----------------------------------------------------------

def weighed(fn):
    def loss(q, k, v, g, beta, a, f):
        o, last = fn(q, k, v, g, beta)
        return (o.astype(F32) * a).sum() + (last * f).sum()
    return loss


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
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--t", type=int, default=8192)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--width", type=int, default=128)
    ap.add_argument("--chunk", type=int, default=64)
    ap.add_argument("--pers", type=int, nargs="*", default=[1, 2, 4, 8])
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--interpret", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/kda_core_probe.txt")
    args = ap.parse_args()
    interpret = bool(args.interpret)
    device = jax.devices()[0]
    if not interpret and device.platform != "tpu":
        raise SystemExit("a time comes from the chip: no TPU here "
                         "(--interpret 1 for the CPU twin)")
    dtype = F32 if interpret else jnp.bfloat16
    ops = operands(args.seed, 1, args.t, args.heads, args.width, dtype)
    ka, kf = jax.random.split(jax.random.key(args.seed + 1))
    weights = (jax.random.normal(ka, ops[0].shape),
               jax.random.normal(kf, (1, args.heads, args.width, args.width)))
    forms = {"numpy": (numpy_form(args.heads, args.chunk), True)}
    for per in args.pers:
        forms[f"fused/{per}"] = (fused_form(args.heads, args.chunk, per,
                                            interpret), True)
        if per * args.chunk % 128 == 0:
            forms[f"split/{per}"] = (split_form(args.heads, args.chunk, per,
                                                interpret), False)
    lines = [f"device {device.platform} {device.device_kind}; "
             f"[1, {args.t}, {args.heads} x {args.width}] chunk {args.chunk} "
             f"{jnp.dtype(dtype).name}; ms a call over {args.calls} calls; "
             "gaps to numpy: o, last | dq dk dv dg dbeta"]
    print(lines[0], flush=True)
    ref = None
    for name, (fn, differentiable) in forms.items():
        try:
            (o, last), fwd_ms = timed(jax.jit(fn), ops, args.calls)
            grads, both_ms = None, float("nan")
            if differentiable:
                (_, grads), both_ms = timed(
                    jax.jit(jax.value_and_grad(weighed(fn), (0, 1, 2, 3, 4))),
                    ops + weights, args.calls)
        except Exception as e:  # noqa: BLE001 - a form the compiler refuses
            lines.append(f"{name:10s} FAILED {str(e)[-400:]!r}")
            print(lines[-1], flush=True)
            continue
        if ref is None:
            ref = (o, last, grads)
        gaps = [gap(o, ref[0]), gap(last, ref[1])]
        line = (f"{name:10s} fwd {fwd_ms:8.3f}  fwd+bwd {both_ms:8.3f}  "
                f"gaps {gaps[0]:.2e} {gaps[1]:.2e}")
        if grads is not None:
            line += " | " + " ".join(f"{gap(a, r):.2e}"
                                     for a, r in zip(grads, ref[2]))
        lines.append(line)
        print(line, flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        f.write("\n".join(lines) + "\n")


if __name__ == "__main__":
    main()
