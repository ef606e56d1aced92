"""Device-busy milliseconds per train step of the experts of every MoE
layer (scope `moe_experts`: the grouped matmuls over the ragged groups
— the TPU compiler's own `ragged-dot-*` kernels, which carry no op
path and are counted here by name — and the activation), forward and
backward together, the median over the traced steps
(layer_metrics/_moe.py)."""

from benchmark.layer_metrics import _moe


def read(run: dict):
    return _moe.part_ms("moe_experts")
