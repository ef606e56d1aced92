"""trace_reduce.py on a small trace recorded on the chip (PR 23: the
first 0.745 s of the traced window of one opt30b-train-t1024 run, cut
by tools/cut_trace.py): busy time, idle share and one operation's
duration recomputed here the slow way, and pinned."""

import os

import pytest

pytest.importorskip("jax")
from jax.profiler import ProfileData  # noqa: E402

from benchmark import trace_reduce as tr  # noqa: E402
from benchmark.layer_metrics import (_trace, device_idle,  # noqa: E402
                                     step_device_ms, step_roofline)

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "data", "train_t1024_two_steps.xplane.pb")


def _raw():
    """(window, ops, modules) straight from the file."""
    window, ops, modules = None, [], []
    for plane in ProfileData.from_file(FIXTURE).planes:
        for line in plane.lines:
            for e in line.events:
                iv = (e.name, e.start_ns, e.start_ns + e.duration_ns)
                if e.name == tr.WINDOW + "train":
                    window = iv[1:]
                elif plane.name == "/device:TPU:0" \
                        and line.name == tr.OPS_LINE:
                    ops.append(iv)
                elif plane.name == "/device:TPU:0" \
                        and line.name == tr.MODULES_LINE:
                    modules.append(iv)
    return window, ops, modules


def _busy_by_sweep(ops, lo, hi) -> float:
    """Busy nanoseconds by walking the sorted edges with a counter of
    open operations (not the interval merge trace_reduce uses)."""
    edges = []
    for _, a, b in ops:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            edges += [(a, 1), (b, -1)]
    busy, open_, last = 0.0, 0, None
    for t, d in sorted(edges, key=lambda e: (e[0], -e[1])):
        if open_ > 0:
            busy += t - last
        open_, last = open_ + d, t
    return busy


def test_busy_idle_and_one_operation_recomputed():
    red = tr.reduce_file(FIXTURE)
    win = red["windows"]["train"]
    (lo, hi), ops, modules = _raw()
    assert len(ops) == 1027 and len(modules) == 1
    busy = _busy_by_sweep(ops, lo, hi)
    assert win["busy_s"] == pytest.approx(busy / 1e9, rel=1e-12)
    assert win["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert win["idle_share"] == pytest.approx(1 - busy / (hi - lo))
    # pinned, as read from the chip (PR 23)
    assert win["window_s"] == pytest.approx(0.745)
    assert win["busy_s"] == pytest.approx(0.740606739, abs=1e-9)
    assert red["busy_s"] == win["busy_s"]
    assert red["window_s"] == win["window_s"]
    # one operation: the tied head's weight-gradient fusion, the
    # longest of the step
    name, seconds = red["breakdown"]["device_ops"][0]
    assert name == "bitcast_convert_fusion"
    mine = [b - a for n, a, b in ops if tr.short(n) == name]
    assert len(mine) == 2
    assert seconds == pytest.approx(sum(mine) / 1e9)
    assert win["op_durations_us"][name] == pytest.approx(
        [d / 1e3 for d in mine])
    # at most ten of each, idle gaps named by the benchmark's spans
    assert len(red["breakdown"]["device_ops"]) == 10
    gaps = dict(red["breakdown"]["idle_gaps"])
    assert set(gaps) <= {"dispatch step", "wait for loss",
                         "no benchmark span"}
    assert sum(gaps.values()) == pytest.approx(
        (hi - lo - busy) / 1e9)


def test_the_step_program_and_the_readers():
    red = tr.reduce_file(FIXTURE)
    (name, launches), = red["windows"]["train"]["modules"].items()
    assert name.startswith("jit_step(")
    assert launches[0]["busy_us"] == pytest.approx(370319.711)
    run = {"trace": red, "facts": {"flops_per_step": 54.855e12},
           "peaks": {"bf16_flops_per_s": 197e12}}
    assert _trace.median_program_us(run, "train") \
        == pytest.approx(370319.711)
    assert step_device_ms.read(run) == pytest.approx(370.319711)
    assert step_roofline.read(run) == pytest.approx(
        100 * 54.855e12 / 197e12 / 0.370319711)
    assert device_idle.read(run) == pytest.approx(
        100 * (1 - 0.740606739 / 0.745))
    # a reader that finds nothing to read returns nothing
    empty = dict(run, trace={"windows": {}})
    assert step_device_ms.read(empty) is None
    assert step_roofline.read(empty) is None
    assert device_idle.read(empty) is None


def test_union_and_short():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 11)]) \
        == [(0, 3), (5, 8), (10, 11)]
    assert tr.short("%fusion.225 = (bf16[7168]{0}) fusion(%a = b)") \
        == "fusion.225"
    assert tr.short("jit_step(123)") == "jit_step(123)"


def test_sweep_readers_on_reduced_numbers():
    """The sweep's readers, on the numbers its first chip trace gave
    (PR 23): the collective's program is the one with most device time,
    not the 0.55 us broadcast launched beside it."""
    from benchmark.layer_metrics import (coll_device_us, dispatch_us,
                                         ici_share)

    def window(coll_us, idle):
        return {"idle_share": idle, "busy_s": sum(coll_us) / 1e6,
                "modules": {
            "jit_broadcast_in_dim(1)": [{"busy_us": 0.55}] * 3,
            "jit__lambda(2)": [{"busy_us": u} for u in coll_us]}}

    run = {"trace": {"windows": {
        "small": window([4.7, 4.84, 324.0], 0.99),
        "large": window([18871.0, 18875.0, 18880.0], 0.07)}},
        "facts": {"host_small_median_us": 1461.23,
                  "collective": "allreduce",
                  "large_bytes": 1 << 30},
        "peaks": {"ici_bytes_per_s": 200e9}, "ranks": 4}
    assert coll_device_us.read(run) == pytest.approx(4.84)
    assert dispatch_us.read(run) == pytest.approx(1461.23 - 4.84)
    # 1.5 x 2**30 B over 200 GB/s is 8,053 us of 18,875
    assert ici_share.read(run) == pytest.approx(
        100 * 1.5 * 2**30 / 200e9 * 1e6 / 18875.0)
    # the window in which the device was busiest: the large pass
    assert device_idle.read(run) == pytest.approx(7.0)
    none = dict(run, trace={"windows": {}})
    assert coll_device_us.read(none) is None
    assert dispatch_us.read(none) is None
    assert ici_share.read(none) is None
