"""Device-busy milliseconds per train step of the chunked gated delta rule (scope `kda/kda_core`: the l2 norms, the log-decay, its sums and exponentials, the pair sums, the intra-chunk system, the carry's kernels `kda_carry_fwd` / `kda_carry_bwd` and the outputs) of every delta-rule layer, forward,
recomputed forwards and backward together, the median over the traced
steps: from the `tf_op` path of the step's `XLA Ops` events
(layer_metrics/_solar.py)."""

from benchmark.layer_metrics import _solar


def read(run: dict):
    return _solar.part_ms("kda_core")
