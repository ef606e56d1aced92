"""Host microseconds of `ompi:coll_xla.launch` (`cold=0`) per
small-message collective, the median over the traced small pass: the
call of the compiled collective program, i.e. jax's multi-process
dispatch."""

from benchmark.layer_metrics import _program


def read(run: dict):
    s = _program.span(_program.window("small"), _program.LAUNCH)
    return s["median_us"] if s else None
