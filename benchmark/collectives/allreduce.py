"""Collective `allreduce`: MPI_Allreduce(MPI_SUM) on a device buffer.

One file per collective, found by the name under `collective` in the
configuration's file, so that a Bcast or an Alltoall cell is this file's
sibling and data, and no file that is here is edited. A collective's
file gives:

    call(comm, x, **kw)        the program's public blocking call (the
                               system under test; the one place it is
                               named)
    bus_bytes(nbytes, ranks)   bytes each rank's links must carry for
                               one call on nbytes per rank
    rank_input(seed, size_index, rank, n_elems, dtype)
                               a rank's seeded send buffer, on its device
    checks(comm, xs, results, seed, sizes, dtype, traffic, limits)
                               the comparison with the plain reference,
                               as (name, value, limit) — the same on
                               every rank
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import allreduce_sum

rank_input = allreduce_sum.rank_input


def call(comm, x, **kw):
    return comm.Allreduce(x, **kw)


def bus_bytes(nbytes: int, ranks: int) -> float:
    """OSU / NCCL bus-bandwidth convention: 2(n-1)/n x the message."""
    return 2.0 * (ranks - 1) / ranks * nbytes


def checks(comm, xs: dict, results: dict, seed: int, sizes: list,
           dtype: str, traffic: dict, limits: dict) -> list:
    """Every size's last result against the float64 sum of the ranks'
    seeded inputs, and `deterministic="linear"` bit for bit against
    the rank-order fold at the sizes the traffic names."""
    n, itemsize = comm.size, np.dtype(dtype).itemsize
    worst = 0.0
    for i, s in enumerate(sizes):
        worst = max(worst, allreduce_sum.result_gap(
            results.pop(s), seed, i, n, s // itemsize, dtype,
            traffic["check_sample"]))
    worst = max(comm.allgather(worst))
    bits = 0
    for s in traffic["linear_bytes"]:
        i = sizes.index(s)
        bits += allreduce_sum.linear_fold_mismatches(
            call(comm, xs[s], deterministic="linear"), seed, i, n,
            s // itemsize, dtype)
    bits = sum(comm.allgather(bits))
    return [("sum_gap", worst, limits["sum_gap"]),
            ("linear_fold_mismatched_elements", bits, 0)]
