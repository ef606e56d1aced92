"""The scan's share of its roofline. The least time is the larger of
two, both of REQUIRED work (benchmark/flops_nemotron.py): the scan's
operations (the causal half inside a chunk, the chunk states, one
multiply-add a chunk for the carry, the read-out; forward and backward,
nothing recomputed) at the chip's peak bf16 rate, and its bytes (`x`,
`B`, `C`, `dt` read and `y` written once forward; those and `dy` read
and the four cotangents written once backward) at the chip's peak HBM
rate. Both are lower bounds on what any form of the scan must do, so
the share cannot pass 100%; the program's float32 decay matrices and
states, its layout changes and the recomputed forward only lower it.
Share = least time / device-busy time under `ssm/ssm_scan`
(ssm_scan_ms.py)."""

from benchmark.layer_metrics import _nemo


def read(run: dict):
    return _nemo.roofline(run, _nemo.part_ms("ssm_scan"),
                          "ssm_scan_flops_per_step",
                          "ssm_scan_bytes_per_step")
