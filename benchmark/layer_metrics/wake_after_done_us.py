"""Host microseconds from the end of the runtime's completion event
(`_runtime.ROLES["done"]`, joined to the launch by `run_id`) to the
end of the caller's wait, per small-message collective (median over
the traced small pass): the caller thread's share of the wake. Signed
(the caller can wake while the event is still open); None where the
trace has no such event."""

from benchmark.layer_metrics import _runtime


def read(run: dict):
    return _runtime.metric("wake_after_done_us")
