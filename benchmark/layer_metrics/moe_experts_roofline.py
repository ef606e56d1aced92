"""The experts' share of their roofline. Compute-bound: the least time
is the operations the grouped matmuls REQUIRE (benchmark/flops_olmoe.py:
the three matrices of the top_k experts of every token, forward and
backward, nothing for experts a token was not routed to) over the
chip's peak bf16 rate; their bytes (each expert's weights and their
gradient once, the rows twice) over the HBM peak come to a fifth of
that at this cell's 512 rows an expert. Share = least time /
device-busy time under `moe_experts` (moe_experts_ms.py)."""

from benchmark.layer_metrics import moe_experts_ms


def read(run: dict):
    ms = moe_experts_ms.read(run)
    flops = run["facts"].get("moe_experts_flops_per_step")
    if not ms or not flops or not run.get("peaks"):
        return None
    least_ms = flops / run["peaks"]["bf16_flops_per_s"] * 1e3
    return 100.0 * least_ms / ms
