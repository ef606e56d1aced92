"""What several readers share: picking a window out of the reduced
trace and the program that did the work in it."""

from __future__ import annotations

import statistics


def window(run: dict, name: str):
    trace = run.get("trace")
    if not trace or name not in trace["windows"]:
        return None
    return trace["windows"][name]


def busiest_window(run: dict):
    """The traced window with most device-busy time, or None."""
    trace = run.get("trace")
    if not trace or not trace["windows"]:
        return None
    return max(trace["windows"].values(), key=lambda w: w["busy_s"])


def program_busy_us(win: dict):
    """Per launch, the device-busy microseconds of the executable that
    took most of the window's device time (the train step; the
    collective's program), or None where no executable ran."""
    if not win or not win["modules"]:
        return None
    runs = max(win["modules"].values(),
               key=lambda launches: sum(x["busy_us"] for x in launches))
    return [x["busy_us"] for x in runs]


def median_program_us(run: dict, name: str):
    busy = program_busy_us(window(run, name))
    return statistics.median(busy) if busy else None
