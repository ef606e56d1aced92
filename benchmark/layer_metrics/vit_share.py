"""The share of the step's device-busy time spent under scope `vision`
(vit_ms.py over step_device_ms.py's step): how much of a
vision-language step is the tower's."""

from benchmark.layer_metrics import _kimi, _trace


def read(run: dict):
    vit, step_us = _kimi.part_ms("vit"), _trace.median_program_us(run,
                                                                  "train")
    if vit is None or not step_us:
        return None
    return vit / (step_us / 1e3)
