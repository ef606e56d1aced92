"""Seconds of the warm-up calls that compile (or load from the
persistent cache) the cell's own programs (host clock)."""


def read(run: dict):
    return run["spans"].get("compile_s")
