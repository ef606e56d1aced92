"""Of the job's own programs' requests to the persistent compilation
cache, the share it answered: the program's always-on counters
`compile_cache_hits` / `compile_cache_requests` (`_compile.py`). 1.0 in
every run after a checkout's first; a machine whose cache refuses an
entry reads less."""

from benchmark.layer_metrics import _program


def read(run: dict):
    requests = _program.counter("compile_cache_requests")
    if requests is None:
        return None
    return (_program.counter("compile_cache_hits") or 0) / requests
