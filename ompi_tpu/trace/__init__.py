"""trace/ — span-structured distributed tracing over the MPI_T planes.

The fourth observability plane (after cvars, pvars/SPC, and MPI-4
events): a per-process bounded ring-buffer span recorder
(:mod:`~ompi_tpu.trace.recorder`) instrumented at every layer a
training step touches — MPI API entry/exit (through the PMPI
interposition chain), coll/xla plan/compile/launch, part/ Pready ->
bucket-flush causality, and pml/btl send/recv. Export is Chrome
trace-event JSON loadable in Perfetto
(:mod:`~ompi_tpu.trace.export`), per-rank files merge into one
timeline with ``python -m ompi_tpu.trace merge``
(:mod:`~ompi_tpu.trace.merge`), and log2-binned latency histograms
ride the pvar plane so ``mpit`` sessions can read them.

The device path, the API table and ``mpi.Init()`` record through ONE
span source (:func:`recorder.span`) with two sinks: any live
``jax.profiler`` session (spans land in its ``.xplane.pb`` as
``ompi:<subsys>.<name>``, on the chip's clock line) and the ring.

Cost model: one guard per instrumented site while no sink is up
(``recorder.active()``: an attribute load, a branch and
``TraceAnnotation.is_enabled()``; host-plane sites still branch on
``recorder.RECORDER is None``) — no span objects are ever constructed.
The ring: cvar ``trace_enable``, env ``OMPI_TPU_TRACE``, or
:func:`recorder.enable`. The profiler sink needs nothing.
"""

from ompi_tpu.trace import export, merge, recorder  # noqa: F401
