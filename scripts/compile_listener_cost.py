"""What the compile ledger's listeners cost (PERF.md section 6, PR 34):
microseconds per listener call and per program on synthetic events —
the seven calls jax makes for one program served from the cache — with
no sink up, then with the ring up. Host-only (no jax, no chip):

    python scripts/compile_listener_cost.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from ompi_tpu.prof import compile as cl
from ompi_tpu.trace import recorder
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
REQ = "/jax/compilation_cache/compile_requests_use_cache"
HIT = "/jax/compilation_cache/cache_hits"
RET = "/jax/compilation_cache/cache_retrieval_time_sec"
def program(led, name, t):
    # the 8 listener calls of one program served from the cache
    led.on_scalar(TRACE, t, fun_name=name)
    led.on_span(TRACE, t, t + 1, fun_name=name)
    led.on_span(LOWER, t + 1, t + 2, fun_name="jit(%s)" % name)
    led.on_event(REQ); led.on_event(HIT); led.on_duration(RET, 0.5)
    led.on_span(BACKEND, t + 2, t + 3, fun_name="jit(%s)" % name)
    return 7
for sink in ("no sink", "ring up"):
    if sink == "ring up":
        recorder.enable(capacity=1 << 16)
    for names in (["ompi_train_step"], ["ompi_p%d" % i for i in range(200)], ["foreign_%d" % i for i in range(200)]):
        led = cl.Ledger()
        n = 0; reps = 20000 // len(names)
        t0 = time.perf_counter()
        for r in range(reps):
            for nm in names:
                n += program(led, nm, 1000.0 + r)
        dt = time.perf_counter() - t0
        print(f"{sink}: {len(names)} name(s): {n} listener calls, {dt / n * 1e6:.2f} us a call, {dt / (n / 7) * 1e6:.1f} us a program")
