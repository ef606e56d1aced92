"""Share of the traced window in which no operation ran on the chip:
1 - union of the device-operation intervals / window. Where a run
traces several windows, the one in which the device was busiest
stands for the cell (the sweep's large-message pass; its small pass is
all but idle by nature and `dispatch_us` says why)."""

from benchmark.layer_metrics import _trace


def read(run: dict):
    win = _trace.busiest_window(run)
    return None if win is None else 100.0 * win["idle_share"]
