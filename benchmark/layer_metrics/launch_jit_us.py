"""Host microseconds of `ompi:coll_xla.launch` that are NOT the
runtime's execute call, per small-message collective (median over the
traced small pass): the program's own lines in `launch` and jax above
the runtime — argument parsing, the C++ fast path or its miss, output
wrapping. With `launch_execute_us` it adds up to `launch_us`."""

from benchmark.layer_metrics import _runtime


def read(run: dict):
    return _runtime.metric("launch_jit_us")
