"""What every runner needs and the program must not be able to change:
the table of peaks, the compile-request counter, the device facts and
the one way ranks print."""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
RESULT_TAG = "BENCH_RESULT "


def say(msg: str) -> None:
    sys.stdout.write(msg + "\n")  # one write: ranks share the pipe
    sys.stdout.flush()


def peaks(device_kind: str) -> dict:
    """The published peaks of exactly this device kind. A kind that is
    not in the table is an error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no peaks recorded for device_kind {device_kind!r} "
            f"(benchmark/peaks.json has {sorted(table)})")
    return table[device_kind]


def compile_requests() -> list:
    """Count XLA compile requests from here on, through jax's own
    event (copied from chip_smoke._compile_requests). The event fires
    once per compilation the jit machinery asks for, served from the
    persistent cache or not; a window that reads a delta of 0 compiled
    nothing."""
    import jax

    box = [0]

    def on_event(event: str, **kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            box[0] += 1

    jax.monitoring.register_event_listener(on_event)
    return box


def device_facts(want_platform: str, want_count: int) -> dict:
    """What jax gave this process; anything but the platform and the
    number of chips the cell asks for is an error (no CPU fallback)."""
    import jax

    devs = jax.devices()
    facts = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
    if facts["platform"] != want_platform or facts["count"] != want_count:
        raise RuntimeError(
            f"the cell wants {want_count} x {want_platform}, jax gave "
            f"{facts}")
    return facts


def memory_stats() -> dict:
    import jax

    stats = jax.local_devices()[0].memory_stats() or {}
    return {k: int(stats[k]) for k in
            ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
            if k in stats}
