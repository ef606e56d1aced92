"""Host microseconds a small-message collective spends in its coll/xla
slot outside `to_global`, `launch` and `my_shard`: self time of the
`ompi:coll_xla.<op>` span, the median over the traced small pass (op
check, monitors, the comm's context, cache key and lookup, observer
and flight guards)."""

from benchmark.layer_metrics import _program


def read(run: dict):
    return _program.self_us(_program.window("small"), _program.slot_span_name)
