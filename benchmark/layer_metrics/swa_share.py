"""The share of the step's device-busy time spent under scope
`attn_window` (swa_attn_ms.py over step_device_ms.py's step): how much
of a step the three windowed layers' attention cores are."""

from benchmark.layer_metrics import _mellum, _trace


def read(run: dict):
    swa, step_us = _mellum.part_ms("attn_window"), _trace.median_program_us(
        run, "train")
    if swa is None or not step_us:
        return None
    return swa / (step_us / 1e3)
