"""Host microseconds a small-message collective spends in the MPI API
binding itself: self time (its span less its children's) of the
`ompi:api.<Call>` span the traced small pass makes, the median
(revoke/failed checks, buffer parsing, device pack/unpack, the
coll-table dispatch)."""

from benchmark.layer_metrics import _program


def read(run: dict):
    return _program.self_us(_program.window("small"), _program.api_span_name)
