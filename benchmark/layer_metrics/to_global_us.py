"""Host microseconds of `ompi:coll_xla.to_global` per small-message
collective, the median over the traced small pass: the residency
check, the `x[None]` that dispatches a second executable, and
`make_array_from_single_device_arrays`."""

from benchmark.layer_metrics import _program


def read(run: dict):
    s = _program.span(_program.window("small"),
                      _program.OMPI + "coll_xla.to_global")
    return s["median_us"] if s else None
