"""The indexer's share of its roofline. Compute-bound: the least time
is the operations it REQUIRES (benchmark/flops_glm5.py
`dsa_index_flops_per_step`: its three projections and the score
products over every causal pair, forward and backward, nothing
recomputed) over the chip's peak bf16 rate. Share = least time /
device-busy time under the two `dsa_index*` scopes (dsa_index_ms.py),
which also hold the float32 sum over the heads and the top-k."""

from benchmark.layer_metrics import _glm


def read(run: dict):
    return _glm.roofline(run, "dsa_index", "dsa_index_flops_per_step")
