"""ompi_tpu/part — the MPI-4 partitioned communication subsystem.

Reference: ompi/mca/part (part.h:124-185) and the part/persist
component: partitioned operations are persistent requests whose
payload is split into partitions the application hands over one by
one, so communication of the early pieces overlaps production of the
late ones. Three layers live under this name:

- :mod:`ompi_tpu.part.host` — partitioned point-to-point
  (``Comm.Psend_init`` / ``Precv_init`` returning requests with
  ``Pready`` / ``Pready_range`` / ``Pready_list`` / ``Parrived``),
  riding the regular PML one message per partition. Attaches the
  Communicator methods at import.
- the device-path payoff, ``Comm.Pallreduce_init`` (coll/xla's
  ``PartitionedAllreduceRequest``): a partitioned FUSED allreduce
  whose partitions are gradient-pytree leaves — each dtype bucket's
  single compiled psum launches the moment its last member leaf is
  marked ready, overlapping bucket communication with backward-pass
  gradient production (bound in :mod:`ompi_tpu.mpi`).
- :mod:`ompi_tpu.part.overlap` — :class:`GradientSync`, the
  DDP/Horovod backward-hook-style wrapper over ``Pallreduce_init``
  for training loops, and :class:`ZeroGradientSync`, the same surface
  over ``Preduce_scatter_init`` yielding sharded gradients for the
  zero/ optimizer cycle.
- :mod:`ompi_tpu.part.partial` — :class:`PartialAvailability`, the
  shared ``Parrived``/``Parrived_range``/``Parrived_list`` probe
  mixin (MPI 4.0 §4.2 erroneous-call policy included). The recv
  request implements it for wire partitions; the streaming ingest
  plane (:mod:`ompi_tpu.ingest`) implements it for host->device
  upload units, so "start on the first ready shards" reads the same
  both places.
"""

from ompi_tpu.part import host  # noqa: F401  (attaches at import)
from ompi_tpu.part.host import (  # noqa: F401
    MAX_PARTITIONS, MAX_TAG, PartitionedRecvRequest,
    PartitionedSendRequest,
)
from ompi_tpu.part.overlap import (  # noqa: F401
    GradientSync, LayerPrefetcher, ZeroGradientSync,
)
from ompi_tpu.part.partial import PartialAvailability  # noqa: F401
