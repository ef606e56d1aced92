"""Executables launched on the chip per API call: launches on the
`XLA Modules` line inside the traced small window, over the
`ompi:api.*` spans there. 2 today: `jit_broadcast_in_dim` (the global
view's `x[None]`) and the collective's own program."""

from benchmark.layer_metrics import _program


def read(run: dict):
    win = _program.window("small")
    api = win and _program.span(win, _program.api_span_name(win["spans"]))
    if not api or not win["module_launches"]:
        return None
    return sum(win["module_launches"].values()) / api["count"]
