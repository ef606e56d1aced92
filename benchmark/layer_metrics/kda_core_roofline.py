"""The delta rule's core's share of its roofline. The least time is the
larger of two, both of REQUIRED work (benchmark/flops_solar2.py): the
chunked form's operations at the configuration's chunk (the pair sums
and the intra-chunk system over the causal half, the carry's three
products, the pairs applied; forward and backward, nothing recomputed)
at the chip's peak bf16 rate, and its bytes (`q`, `k`, `v`, `g`, `beta`
read and `o` written once forward; those and `do` read and the five
cotangents written once backward, all at 2 bytes) at the chip's peak
HBM rate. Both are lower bounds on what any form of the core must do,
so the share cannot pass 100%; the program's float32 decays, sums and
exponentials, its per-chunk systems, the entering states it writes and
reads, its layout changes and the recomputed forwards only lower it.
Share = least time / device-busy time under `kda/kda_core`
(kda_core_ms.py)."""

from benchmark.layer_metrics import _nemo, _solar


def read(run: dict):
    return _nemo.roofline(run, _solar.part_ms("kda_core"),
                          "kda_core_flops_per_step",
                          "kda_core_bytes_per_step")
