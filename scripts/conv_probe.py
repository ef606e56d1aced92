#!/usr/bin/env python3
"""The four-tap causal convolution alone on one chip: three forms.

At the two cells' shapes — ``solar2-train-t8192``'s run of heads (``[1,
8192, 2048]`` bfloat16, no bias, time in the sublanes both ways) and
``nemotron-train-t8192``'s ``xBC`` (``[1, 6144, 8192]``, a bias, time in
the lanes both ways: the layout the step's arrays lie in) — time,
forward and backward (the cotangents of x, w, b under a given dy):

- ``shifted``: ``ssm.shifted_conv``, the ``jax.numpy`` form of K
  shifted sums over a padded float32 copy and autodiff's backward pass
  (the parent's form, the kernels' oracle);
- ``kernels/<time>x<channels>``: ``ops/causal_conv.conv`` at that block
  (``ssm.conv_tile`` answers 1024x512 at both shapes);
- ``depthwise``: ``lax.conv_general_dilated`` with ``feature_group_count
  = C`` of float32 operands at the default precision (ROADMAP S14.2
  named it; for the record only);

and print each form's GB/s of the bytes that MUST move (forward: read x,
write y; backward: read x and dy, write dx) and its largest gap to
``shifted`` relative to the largest value. PERF.md 6 (PR 49) quotes the
table.

    chiprun -- python scripts/conv_probe.py [--tiles 1024x512 512x512]
        [--chunks 32x128 128x128]

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
from jax import lax  # noqa: E402

from ompi_tpu.ops import causal_conv as ck  # noqa: E402
from ompi_tpu.ops import ssm  # noqa: E402

F32 = jnp.float32


def shifted_form(time_last):
    def run(x, w, b):
        if not time_last:
            return ssm.shifted_conv(x, w, b)
        return jnp.swapaxes(ssm.shifted_conv(jnp.swapaxes(x, 1, 2), w, b),
                            1, 2)
    return run


def kernel_form(tile, time_last, interpret):
    # `ck.conv` on arrays that lie as the kernels read them
    return lambda x, w, b: ck._conv(
        ck.Tile(*tile), time_last, b is not None, interpret)(
            x, w, *(() if b is None else (b,)))


def depthwise_form(time_last):
    def run(x, w, b):
        k = w.shape[1]
        lhs = ("NCW" if time_last else "NWC")
        pre = lax.conv_general_dilated(
            x.astype(F32), w.T[:, None, :].astype(F32), (1,), [(k - 1, 0)],
            dimension_numbers=(lhs, "WIO", lhs),
            feature_group_count=w.shape[0])
        if b is not None:
            pre = pre + (b[:, None] if time_last else b).astype(F32)
        return jax.nn.silu(pre).astype(x.dtype)
    return run


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
    ap.add_argument("--channels", type=int, nargs=2, default=[2048, 6144],
                    help="of the [B, T, C] shape and of the [B, C, T] one")
    ap.add_argument("--taps", type=int, default=4)
    ap.add_argument("--tiles", nargs="*", default=["1024x512", "512x512",
                                                   "1024x256", "2048x512"])
    ap.add_argument("--chunks", nargs="*", default=[],
                    help="also, at the first block: the kernels with a "
                    "chunk of SUBLANESxLANES (ops/causal_conv._CHUNK's "
                    "entry of the layout) in place of the module's")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--interpret", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/conv_probe.txt")
    args = ap.parse_args()
    interpret = bool(args.interpret)
    device = jax.devices()[0]
    if not interpret and device.platform != "tpu":
        raise SystemExit("a time comes from the chip: no TPU here "
                         "(--interpret 1 for the CPU twin)")
    dtype = jnp.bfloat16
    tiles = [tuple(int(n) for n in t.split("x")) for t in args.tiles]
    lines = [f"device {device.platform} {device.device_kind}; "
             f"{jnp.dtype(dtype).name}, {args.taps} taps; ms a call over "
             f"{args.calls} calls, GB/s of the bytes that must move "
             "(forward 2 arrays, backward 3); gaps to shifted: y | dx dw db"]
    print(lines[0], flush=True)
    for time_last, c, bias in ((False, args.channels[0], False),
                               (True, args.channels[1], True)):
        shape = (1, c, args.t) if time_last else (1, args.t, c)
        ks = jax.random.split(jax.random.key(args.seed + c), 4)
        x = jax.random.normal(ks[0], shape).astype(dtype)
        dy = jax.random.normal(ks[1], shape).astype(dtype)
        w = jax.random.normal(ks[2], (c, args.taps)) * 0.5
        b = jax.random.normal(ks[3], (c,)) if bias else None
        nbytes = x.size * x.dtype.itemsize
        lines.append(f"x {list(shape)} (time in the "
                     f"{'lanes' if time_last else 'sublanes'}), "
                     f"{'a' if bias else 'no'} bias, {nbytes / 1e6:.0f} MB")
        print(lines[-1], flush=True)
        forms = {"shifted": shifted_form(time_last)}
        for tile in tiles:
            if args.t % tile[0] == 0 and c % tile[1] == 0:
                forms[f"kernels/{tile[0]}x{tile[1]}"] = kernel_form(
                    tile, time_last, interpret)
        forms["depthwise"] = depthwise_form(time_last)
        chunks = {"": ck._CHUNK}
        for chunk in args.chunks:
            entry = tuple(int(n) for n in chunk.split("x"))
            both = list(ck._CHUNK)
            both[int(time_last)] = entry
            chunks[chunk] = tuple(both)
            forms[f"kernels/{tiles[0][0]}x{tiles[0][1]} chunk {chunk}"] = \
                kernel_form(tiles[0], time_last, interpret)
        ref = None
        for name, fn in forms.items():
            def back(x, w, b, dy, fn=fn):
                return jax.vjp(fn, x, w, b)[1](dy)
            # a kernel's body reads the chunk when it is traced, and the
            # two passes are jitted: make them trace again
            ck._CHUNK = chunks[name.partition(" chunk ")[2]]
            ck.forward.clear_cache()
            ck.backward.clear_cache()
            try:
                y, fwd_ms = timed(jax.jit(fn), (x, w, b), args.calls)
                grads, bwd_ms = timed(jax.jit(back), (x, w, b, dy),
                                      args.calls)
            except Exception as e:  # noqa: BLE001 - a form the compiler refuses
                lines.append(f"{name:18s} FAILED {str(e)[-400:]!r}")
                print(lines[-1], flush=True)
                continue
            grads = [g for g in grads if g is not None]
            if ref is None:
                ref = (y, grads)
            line = (f"{name:18s} fwd {fwd_ms:7.3f} ms {2 * nbytes / fwd_ms / 1e6:6.1f} GB/s"
                    f"  bwd {bwd_ms:7.3f} ms {3 * nbytes / bwd_ms / 1e6:6.1f} GB/s"
                    f"  gaps {gap(y, ref[0]):.2e} | " + " ".join(
                        f"{gap(a, r):.2e}" for a, r in zip(grads, ref[1])))
            lines.append(line)
            print(line, flush=True)
        ck._CHUNK = chunks[""]
        ck.forward.clear_cache()
        ck.backward.clear_cache()
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        f.write("\n".join(lines) + "\n")


if __name__ == "__main__":
    main()
