"""Seconds from a rank's start to the return of mpi.Init(), the
largest over the ranks (host clock in the rank)."""


def read(run: dict):
    return run["spans"].get("init_s")
