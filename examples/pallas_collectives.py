"""coll/pallas — hand-rolled ring collectives over the device plane.

coll/xla lets the XLA compiler lower every collective; coll/pallas
(opt-in, priority 60) replaces the supported ones with explicit
Pallas kernels — ``make_async_remote_copy`` DMA rings
on TPU, the identical chunk schedule in interpret mode + ``ppermute``
hops everywhere else — and adds the two fused compute+comm kernels
the backend exists for (ZeRO reduce_scatter+update, matmul-overlapped
allgather). This demo proves the stacking and the contracts on CPU:

- the pallas providers actually own the slots (opt-in stacking),
- deterministic='linear' allreduce/reduce_scatter match coll/xla BIT
  FOR BIT (the reproducibility contract tier-1 verifies on >= 3 mesh
  sizes), the default ring is numerically equivalent,
- an unsupported dtype (int16) falls through to coll/xla with the
  same result (``pallas_fallthrough`` counts the delegation),
- ``fused=True`` ZeroOptimizer reproduces the unfused cycle bitwise
  under 'linear'.

Run:  python -m ompi_tpu.runtime.launcher -n 2 \
          --mca device_plane on --mca coll_pallas on \
          examples/pallas_collectives.py

Set OMPI_TPU_PALLAS_ARTIFACT=<path> to drop a JSON summary (the CI
smoke lane uploads it).
"""

import json
import os

import jax.numpy as jnp
import numpy as np

from ompi_tpu import mpi
from ompi_tpu.coll import xla as coll_xla
from ompi_tpu.core import pvar
from ompi_tpu.zero import ZeroOptimizer

comm = mpi.Init()
rank, size = comm.rank, comm.size

assert comm.coll.providers["allreduce_dev"] == "pallas", \
    comm.coll.providers.get("allreduce_dev")
s = pvar.session()

# -- bit-identity: pallas 'linear'/'ring' vs the coll/xla lowering ----------
rng = np.random.default_rng(17)
h = (rng.standard_normal(1024)
     * (10.0 ** rng.integers(-3, 4, 1024))).astype(np.float32)
x = jnp.asarray(np.roll(h, rank * 13))
bitwise = {}
for det in ("linear", "ring"):
    p = np.asarray(comm.coll.allreduce_dev(comm, x, deterministic=det))
    r = np.asarray(coll_xla.allreduce_dev(comm, x, deterministic=det))
    bitwise[det] = bool((p.view(np.uint32) == r.view(np.uint32)).all())
    assert bitwise[det], f"pallas {det} allreduce != coll/xla bitwise"
default_close = bool(np.allclose(
    np.asarray(comm.coll.allreduce_dev(comm, x)),
    np.asarray(coll_xla.allreduce_dev(comm, x)), rtol=1e-5, atol=1e-5))
assert default_close, "default ring allreduce diverged from coll/xla"

# -- staged fallthrough: int16 is outside the support matrix ----------------
xi = (jnp.arange(64) % 9 + rank).astype(jnp.int16)
got = np.asarray(comm.coll.allreduce_dev(comm, xi))
exp = sum((np.arange(64) % 9 + rr).astype(np.int16) for rr in range(size))
np.testing.assert_array_equal(got, exp)
fallthroughs = s.read("pallas_fallthrough")
assert fallthroughs >= 1, "int16 did not fall through to coll/xla"

# -- fused ZeRO: one kernel reduce_scatters + updates, bitwise under linear -
params = {"w": jnp.asarray(rng.standard_normal((8, 8)
                                               ).astype(np.float32)),
          "b": jnp.asarray(rng.standard_normal((9,)).astype(np.float32))}
grads = {"w": jnp.full((8, 8), float(rank + 1), jnp.float32),
         "b": jnp.full((9,), float(rank + 1), jnp.float32)}
base = ZeroOptimizer(comm, params, lr=0.1, momentum=0.9,
                     deterministic="linear")
fused = ZeroOptimizer(comm, params, lr=0.1, momentum=0.9,
                      deterministic="linear", fused=True)
fused_bitwise = True
for _ in range(2):
    ref, out = base.step(grads), fused.step(grads)
    for k in ref:
        fused_bitwise = fused_bitwise and bool(
            (np.asarray(ref[k]).view(np.uint32)
             == np.asarray(out[k]).view(np.uint32)).all())
assert fused_bitwise, "fused ZeRO 'linear' != unfused bitwise"

summary = {
    "ranks": size,
    "bitwise_linear": bitwise["linear"],
    "bitwise_ring": bitwise["ring"],
    "default_allclose": default_close,
    "fused_zero_bitwise": fused_bitwise,
    "pallas_launches": s.read("pallas_launches"),
    "pallas_fused_launches": s.read("pallas_fused_launches"),
    "pallas_fallthrough": fallthroughs,
    "ring_bytes": s.read("pallas_ring_bytes"),
    "linear_bytes": s.read("pallas_linear_bytes"),
}
art = os.environ.get("OMPI_TPU_PALLAS_ARTIFACT")
if art and rank == 0:
    with open(art, "w", encoding="utf-8") as f:
        json.dump(summary, f, indent=1)
if rank == 0:
    print(f"pallas collectives over {size} ranks: linear/ring bitwise "
          f"vs coll/xla, fused ZeRO bitwise under 'linear'; "
          f"{summary['pallas_launches']} kernel launches, "
          f"{summary['pallas_fused_launches']} fused launches, "
          f"{summary['pallas_fallthrough']} staged fallthroughs")
mpi.Finalize()
