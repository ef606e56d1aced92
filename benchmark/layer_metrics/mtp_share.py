"""The share of the step's device-busy time spent in the
multi-token-prediction module (mtp_ms.py — every op of the `layer_<n>`
that holds `attn_proj/mtp_merge`, and its head under `head_loss/mtp`,
forward, recomputation and backward — over step_device_ms.py's step):
how much of a step the module behind the trunk is. In
`kexaone-train-t8192` it is a full-attention expert layer behind a
trunk whose last layer is windowed, 24% of the required operations
(benchmark/flops_kexaone.py `mtp_flops_per_step`). A trace without the
module's names (a program without it) gives None."""

from benchmark.layer_metrics import _glm, _trace


def read(run: dict):
    mtp, step_us = _glm.part_ms("mtp"), _trace.median_program_us(
        run, "train")
    if mtp is None or not step_us:
        return None
    return mtp / (step_us / 1e3)
