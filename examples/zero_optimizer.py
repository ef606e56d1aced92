"""ZeRO-sharded data parallel — the O(1/n) optimizer-state story.

``Allreduce_multi`` (examples/fused_gradients.py) gives every rank
the full reduced gradient, so every rank also carries a full copy of
the optimizer state. ZeRO (Rajbhandari et al., SC'20) observes that
rank r only ever *updates* 1/n of the parameters: reduce_scatter the
gradients (each rank receives just its shard, already summed), update
the shard locally, and allgather the parameters back. Optimizer state
— here SGD momentum — never exists outside the shard, so per-rank
state is total/n.

``ZeroOptimizer`` runs that cycle over the fused zero collectives
(``Reduce_scatter_multi`` / ``Allgather_multi`` — one compiled launch
per dtype bucket, same ZeroPlan both directions). ``overlap=True``
swaps the gradient step for ``Preduce_scatter_init``: each leaf is
pushed as the "backward" produces it and a bucket's reduce_scatter
dispatches the moment its last member arrives
(``zero_overlap_flushes`` counts buckets that beat the final push).

Run:  python -m ompi_tpu.runtime.launcher -n 2 --mca device_plane on \
          --mca coll_xla_bucket_bytes 16384 \
          examples/zero_optimizer.py

(The small bucket target splits this toy model into several buckets
so mid-backward flushes are visible; real models exceed the 4 MiB
default many times over.)
"""

import jax
import jax.numpy as jnp
import numpy as np

from ompi_tpu import mpi
from ompi_tpu.core import pvar
from ompi_tpu.prof import ledger as prof
from ompi_tpu.zero import ZeroOptimizer

comm = mpi.Init()
rank, size = comm.rank, comm.size

# phase ledger (no-op unless --mca prof_enable 1): setup/optimizer
# construction is "staging", the step loop is "train" — the
# attribution python -m ompi_tpu.prof merges
with prof.phase("staging"):
    params = {
        "embed": jnp.ones((256, 32), jnp.float32),
        "layers": [
            {"w": jnp.ones((64, 64), jnp.float32),
             "b": jnp.zeros((64,), jnp.float32)}
            for _ in range(4)
        ],
    }

    opt = ZeroOptimizer(comm, params, lr=0.1, momentum=0.9,
                        overlap=True, deterministic="linear")

# the O(1/n) claim: params + momentum shards on this rank vs the
# replicated optimizer they replace (pad waste is the only slack)
per_rank = opt.state.shard_bytes
replicated = opt.state.replicated_bytes
assert abs(per_rank - replicated / size) <= opt.state.params.plan.pad_bytes + 8, \
    (per_rank, replicated, size)

s = pvar.session()
paths = [jax.tree_util.keystr(p) for p, _ in
         jax.tree_util.tree_flatten_with_path(params)[0]]
with prof.phase("train"):
    for step in range(3):
        # "backward pass": every rank contributes rank+1; the
        # averaged gradient is the same on all ranks, so params stay
        # replicated
        grads = jax.tree.map(
            lambda p: jnp.full(p.shape, float(rank + 1), p.dtype),
            params)
        params = opt.step(grads)

# every rank reassembled identical parameters (mean grad = (n+1)/2)
ref = np.asarray(params["embed"])[0, 0]
got = comm.allreduce(ref) / size
np.testing.assert_allclose(ref, got, rtol=0, atol=0)

flushes = s.read("zero_overlap_flushes")
assert size == 1 or flushes > 0, "no bucket beat the final push"

if rank == 0:
    print(f"per-rank optimizer state {per_rank} B vs {replicated} B "
          f"replicated (n={size}); 3 steps: "
          f"{s.read('zero_rs_launches')} reduce_scatter + "
          f"{s.read('zero_ag_launches')} allgather launches, "
          f"{flushes} buckets flushed before the final push")
    ph = prof.phase_seconds()
    if ph:
        print("phase ledger: " + ", ".join(
            f"{k}={v:.3f}s" for k, v in sorted(ph.items())))
mpi.Finalize()
