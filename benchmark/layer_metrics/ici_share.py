"""The large-message collective's share of its roofline. ICI-bound:
the least time is the bytes each chip's links must carry (the
collective's own rule, benchmark/collectives/<name>.py: 2(n-1)/n x
message for an all-reduce) over the published interconnect rate of one
chip; the HBM traffic of the reduction is a few passes at four times
that rate. Share = least time / median device-busy time of the
collective's program."""

import importlib

from benchmark.layer_metrics import _trace


def read(run: dict):
    us = _trace.median_program_us(run, "large")
    facts = run["facts"]
    if us is None or not run.get("peaks") or not facts.get("collective"):
        return None
    coll = importlib.import_module(
        "benchmark.collectives." + facts["collective"])
    bus = coll.bus_bytes(facts["large_bytes"], run["ranks"])
    least_us = bus / run["peaks"]["ici_bytes_per_s"] * 1e6
    return 100.0 * least_us / us
