"""The train step's share of its roofline. Compute-bound: the least
time is the operations the step REQUIRES (benchmark/flops.py: matmuls
and causal attention, nothing recomputed) over the chip's peak bf16
rate; the bytes a step must move (weights, gradients, activations
once) over the HBM peak come to a small fraction of that at these
shapes. Share = least time / device-busy time of the step."""

from benchmark.layer_metrics import step_device_ms


def read(run: dict):
    ms = step_device_ms.read(run)
    flops = run["facts"].get("flops_per_step")
    if ms is None or not flops or not run.get("peaks"):
        return None
    least_ms = flops / run["peaks"]["bf16_flops_per_s"] * 1e3
    return 100.0 * least_ms / ms
