"""The share of the step's device-busy time spent under scope `kda`
(kda_ms.py over step_device_ms.py's step): how much of a hybrid step
is the delta-rule layers'."""

from benchmark.layer_metrics import _solar, _trace


def read(run: dict):
    kda, step_us = _solar.part_ms("kda"), _trace.median_program_us(run,
                                                                  "train")
    if kda is None or not step_us:
        return None
    return kda / (step_us / 1e3)
