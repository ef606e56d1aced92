"""What the program's span source (ompi_tpu/trace/recorder.py) costs a blocking
device collective, site by site: its guards while no sink is up, and one span
while a jax.profiler session is live. One process, no collective; a tool for
`chiprun -- python benchmark/tools/guard_cost.py` (host times of that machine;
PERF.md section 6, PR 24), not part of a run."""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
import jax, jax.numpy as jnp
from ompi_tpu.trace import recorder as R
from ompi_tpu.coll import xla as cx
N = 300000
def per_call(f):
    best = 1e9
    for _ in range(5):
        t = time.perf_counter()
        for _ in range(N): f()
        best = min(best, (time.perf_counter() - t) / N)
    return best * 1e9
R._profiler_live()  # bind to TraceAnnotation.is_enabled, as after jax's import
empty = per_call(lambda: None)
print(f"loop+lambda {empty:.1f} ns; active() {per_call(R.active) - empty:.1f} ns; "
      f"span() off {per_call(lambda: R.span('a', 'b')) - empty:.1f} ns")
# the wrappers: an API entry and a slot around a no-op body
from ompi_tpu import mpi
entry = mpi._api_entry("Noop", lambda self: None, True)
bare = lambda self: None
print(f"api entry (errhandled) {per_call(lambda: entry(None)) - per_call(lambda: bare(None)):.1f} ns over the bare call")
slot = cx._slot("noop")(lambda comm, buf: None)
print(f"slot wrapper {per_call(lambda: slot(None, None)) - per_call(lambda: bare(None)):.1f} ns over the bare call")
ctx = cx._Ctx.local()
x = jnp.ones(256, jnp.float32)
fn = ctx.compiled(cx._key(x, "allreduce", "MPI_SUM", None), lambda: ctx.smap(lambda a: a[0], out_varying=True))
g = ctx.to_global(x); jax.block_until_ready(ctx.launch(fn, g))
print(f"cold-set lookup {per_call(lambda: fn in ctx._cold) - empty:.1f} ns")
# with a profiler session live: what one span costs the path it measures
opts = jax.profiler.ProfileOptions(); opts.python_tracer_level = 0
jax.profiler.start_trace("chiprun_out/guard_cost", profiler_options=opts)
N = 20000
from jax.profiler import TraceAnnotation
def raw():
    with TraceAnnotation("ompi:coll_xla.launch", program="ompi_allreduce", nbytes=1024, cold=0, call=5): pass
def sp():
    with R.span("launch", "coll_xla", program="ompi_allreduce", nbytes=1024, cold=0): pass
def sp_set():
    with R.span("to_global", "coll_xla") as s: s.set(resident=1)
def api():
    with R.api_span("Allreduce"): pass
print(f"LIVE: raw TraceAnnotation(4 kwargs) {per_call(raw) - empty:.0f} ns; span(3 kwargs) {per_call(sp) - empty:.0f} ns; "
      f"span+set {per_call(sp_set) - empty:.0f} ns; api_span {per_call(api) - empty:.0f} ns; active() {per_call(R.active) - empty:.1f} ns")
jax.profiler.stop_trace()
