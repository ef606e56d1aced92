"""XLA compile requests between the window's first and last
iteration, counted by jax's own event. Must read 0."""


def read(run: dict):
    return run["counters"].get("compiles_in_window")
