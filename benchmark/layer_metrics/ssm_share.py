"""The share of the step's device-busy time spent under scope `ssm`
(ssm_ms.py over step_device_ms.py's step): how much of a hybrid step
is the state-space layers'."""

from benchmark.layer_metrics import _nemo, _trace


def read(run: dict):
    ssm, step_us = _nemo.part_ms("ssm"), _trace.median_program_us(run,
                                                                  "train")
    if ssm is None or not step_us:
        return None
    return ssm / (step_us / 1e3)
