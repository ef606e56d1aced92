"""Host microseconds of the runtime's execute call inside
`ompi:coll_xla.launch` per small-message collective (median over the
traced small pass; the event `_runtime.ROLES["execute"]` names): the
runtime's enqueue of one program across four processes — output
buffers allocated, the program handed to the chip's queue."""

from benchmark.layer_metrics import _runtime


def read(run: dict):
    return _runtime.metric("launch_execute_us")
