"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls, at the
full width of the flagship dense transformer (depth as the repo uses it
on one chip: d7168 / L3 / ff28672 / B4 / T1024, bf16, seeded random
weights), and checks what comes out by the repo's own means. Run it from
the checkout root, on a machine with a TPU:

    python chip_smoke.py

The parent process never imports jax (a process that touched jax holds
the chip, and a child that needs it then fails or hangs). Every leg is a
child, or a launcher job, that owns the chip(s) for its lifetime; the
parent waits for every process of a leg to exit before the next starts.

  leg 0  probe    versions, devices, device_kind, peaks known for it
  leg 1  rank1    launcher -n 1 -> mpi.Init() -> make_train_step, 1+3
                  steps, one compilation; device-buffer Allreduce/Bcast;
                  an osc/pallas window against the host window
  leg 2  mesh4    one process, 2x2 dp x tp shard_map train step; the
                  Pallas ring kernels compiled, against lax.psum
  leg 3  ranks4   launcher -n 4, one rank per chip: the BASELINE.json
                  collectives on TPU-resident buffers against the host
                  plane; three data-parallel steps over Allreduce_multi
  leg 4  pallas4  the same four ranks under coll_pallas: Allreduce
                  through the DMA kernels, then every other Pallas
                  kernel a public entry point reaches (linear, fused
                  ZeRO update, allgather-matmul, osc fence round), up
                  to the most each holds in VMEM, and the counted
                  fallthrough one size past it (to a flagship leaf)

Legs 2-4 need four chips and print ``not run: <N> device`` otherwise.
The last stdout line is one JSON object and nothing else,
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``
with the device as jax reports it; the line before it, ``SUMMARY {...}``,
carries each leg's facts (also written to
``chiprun_out/chip_smoke/summary.json``). Exit status is 0 only if every
leg that ran passed ("ok": false and status 1 when one failed). Without a
TPU, or without the rest of the checkout, it exits non-zero in seconds
and prints no result at all.

``--cpu-dryrun`` runs the same legs at toy width on 4 virtual CPU
devices / gloo with Pallas in interpret mode, prefixes every line with
``DRYRUN platform=cpu``, and is the only way this script touches a CPU
backend. It proves the script, never the chip.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")
RESULT_TAG = "LEG_RESULT "
SUMMARY_TAG = "SUMMARY "
DRYRUN_TAG = "DRYRUN platform=cpu "

#: the width the repo uses on one chip, and the toy the dry run uses
FULL = dict(vocab=32768, d_model=7168, n_layers=3, n_heads=56,
            d_ff=28672, max_seq=1024, batch=4, seq=1024)
TOY = dict(vocab=512, d_model=128, n_layers=2, n_heads=4, d_ff=512,
           max_seq=64, batch=4, seq=64)
SEED = 0
LR = 1e-2
#: depth of the per-rank replica in leg 3: params + grads + the new
#: arrays Allreduce_multi returns are live at once (3 x 1.7 GB at L=1)
DP_LAYERS = 1
LEG_TIMEOUT_S = 900


# ---------------------------------------------------------------------------
# parent: never imports jax


def _child_env(dryrun: bool, devices: int) -> dict:
    env = dict(os.environ)
    # the script, not the calling shell, names the platform: an
    # inherited JAX_PLATFORMS=cpu must not turn the smoke into a CPU run
    env["JAX_PLATFORMS"] = "cpu" if dryrun else "tpu"
    env.pop("XLA_FLAGS", None)
    if dryrun and devices > 1:
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    return env


def _run_leg(name: str, argv: list, env: dict, dryrun: bool) -> dict:
    """Run one leg to the end of every process it started; echo its
    output; return the LEG_RESULT it printed (or a failure record)."""
    os.makedirs(OUT_DIR, exist_ok=True)
    tag = DRYRUN_TAG if dryrun else ""
    print(f"{tag}=== leg {name}: {' '.join(argv[1:])}", flush=True)
    t0 = time.monotonic()
    result, last = None, ""
    with open(os.path.join(OUT_DIR, f"{name}.log"), "w") as log:
        proc = subprocess.Popen(
            argv, env=env, cwd=HERE, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True, start_new_session=True)
        killer = _Deadline(proc, LEG_TIMEOUT_S)
        try:
            for line in proc.stdout:
                log.write(line)
                line = line.rstrip("\n")
                if line.startswith(RESULT_TAG):
                    result = json.loads(line[len(RESULT_TAG):])
                else:
                    print(f"{tag}[{name}] {line}", flush=True)
                    last = line.strip() or last
            rc = proc.wait()
        finally:
            killer.cancel()
            _kill_group(proc)  # no straggler keeps a chip
    wall = time.monotonic() - t0
    if result is None or rc != 0:
        result = {**(result or {}), "ok": False,
                  "error": (f"timed out after {LEG_TIMEOUT_S}s"
                            if killer.fired else f"exit {rc}")
                  + f": {last[:300]}"}
    result["wall_s"] = round(wall, 1)
    print(f"{tag}leg {name}: {'PASS' if result['ok'] else 'FAIL'} "
          f"({wall:.1f}s){'' if result['ok'] else ' ' + str(result.get('error'))}",
          flush=True)
    return result


class _Deadline:
    """Kill a leg's process group when it outlives its time limit."""

    def __init__(self, proc, seconds: float) -> None:
        import threading

        self.fired = False
        self._proc = proc
        self._timer = threading.Timer(seconds, self._fire)
        self._timer.daemon = True
        self._timer.start()

    def _fire(self) -> None:
        self.fired = True
        _kill_group(self._proc)

    def cancel(self) -> None:
        self._timer.cancel()


def _kill_group(proc) -> None:
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass


def _launcher(n: int, mca: dict, leg: str, dryrun: bool) -> list:
    argv = [sys.executable, "-m", "ompi_tpu.runtime.launcher",
            "-n", str(n), "--timeout", str(LEG_TIMEOUT_S - 30)]
    mca = {"device_plane": "on",
           "device_plane_platform": "cpu" if dryrun else "tpu", **mca}
    for k, v in mca.items():
        argv += ["--mca", k, v]
    argv += [os.path.join(HERE, "chip_smoke.py"), "--leg", leg]
    return argv + (["--cpu-dryrun"] if dryrun else [])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-dryrun", action="store_true",
                    help="toy width on 4 virtual CPU devices; proves "
                         "the script, never the chip")
    ap.add_argument("--leg", default=None, help=argparse.SUPPRESS)
    ns = ap.parse_args()
    if ns.leg is not None:
        return _LEGS[ns.leg](ns.cpu_dryrun)

    dryrun = ns.cpu_dryrun
    tag = DRYRUN_TAG if dryrun else ""
    t0 = time.monotonic()
    me = os.path.join(HERE, "chip_smoke.py")
    child = [sys.executable, me] + (["--cpu-dryrun"] if dryrun else [])
    legs = {}

    legs["probe"] = probe = _run_leg(
        "probe", child + ["--leg", "probe"], _child_env(dryrun, 4),
        dryrun)
    if not probe["ok"]:
        print(f"{tag}chip_smoke: the probe failed — no usable "
              f"{'CPU backend' if dryrun else 'TPU'} for this checkout: "
              f"{probe.get('error')}", file=sys.stderr, flush=True)
        return 1
    device = probe["device"]
    n = device["count"]

    # the coll list makes the host plane's Allreduce coll/basic's
    # rank-order fold, the reference 'linear' must match bit for bit
    coll = "basic,accelerator,xla,libnbc"
    legs["rank1"] = _run_leg(
        "rank1", _launcher(1, {"osc_pallas": "on"}, "rank1", dryrun),
        _child_env(dryrun, 1), dryrun)
    if n >= 4:
        legs["mesh4"] = _run_leg(
            "mesh4", child + ["--leg", "mesh4"], _child_env(dryrun, 4),
            dryrun)
        legs["ranks4"] = _run_leg(
            "ranks4", _launcher(4, {"coll": coll}, "ranks4", dryrun),
            _child_env(dryrun, 1), dryrun)
        legs["pallas4"] = _run_leg(
            "pallas4", _launcher(
                4, {"coll": coll.replace("xla", "xla,pallas"),
                    "coll_pallas": "on", "osc_pallas": "on",
                    "coll_pallas_interpret": "auto" if dryrun else "off",
                    "osc_pallas_interpret": "auto" if dryrun else "off"},
                "pallas4", dryrun),
            _child_env(dryrun, 1), dryrun)
        # same seed, same batch: sharded == unsharded on the chip
        # (bf16 weights, f32 loss: the two differed by 2.4e-5 of the
        # loss on the chip in PR 21; one training step moves it 6e-3)
        a, b = legs["rank1"].get("losses"), legs["mesh4"].get("losses")
        if a and b and not math.isclose(a[0], b[0], rel_tol=1e-3):
            legs["mesh4"]["ok"] = False
            legs["mesh4"]["error"] = (
                f"step-0 loss {b[0]} != leg 1's {a[0]} within 1e-3")
            print(f"{tag}leg mesh4: FAIL {legs['mesh4']['error']}",
                  flush=True)
    else:
        for name in ("mesh4", "ranks4", "pallas4"):
            legs[name] = {"ok": None, "skipped": f"not run: {n} device"}
            print(f"{tag}leg {name}: not run: {n} device", flush=True)

    ok = all(leg["ok"] is not False for leg in legs.values())
    assert "jax" not in sys.modules, "the chip_smoke parent imported jax"
    summary = {"ok": ok, "device": device, "legs": legs,
               "wall_s": round(time.monotonic() - t0, 1)}
    with open(os.path.join(OUT_DIR, "summary.json"), "w") as fh:
        json.dump(summary, fh, indent=1)
    if not ok:
        bad = [k for k, v in legs.items() if v["ok"] is False]
        print(f"{tag}chip_smoke: FAILED legs: {bad}", file=sys.stderr,
              flush=True)
    # a leg that failed ON a device still ends in a result, "ok": false;
    # only "no device at all" (above) prints none. The per-leg facts go
    # on the line before: the last line is the driver's contract and
    # holds exactly these keys, the device as jax reported it.
    print(f"{tag}{SUMMARY_TAG}{json.dumps(summary)}", flush=True)
    print(tag + json.dumps(_verdict(ok, device)), flush=True)
    return 0 if ok else 1


def _verdict(ok: bool, device: dict) -> dict:
    """The last stdout line: these keys and no others."""
    return {"ok": bool(ok),
            "device": {"platform": str(device["platform"]),
                       "kind": str(device["kind"]),
                       "count": int(device["count"])}}


# ---------------------------------------------------------------------------
# children: everything below runs in a process that owns its chip(s)


def say(msg: str) -> None:
    sys.stdout.write(msg + "\n")  # one write: ranks share the pipe
    sys.stdout.flush()


def _passed(**facts) -> int:
    """A leg that got this far passed (a failed check raised)."""
    say(RESULT_TAG + json.dumps({"ok": True, **facts}))
    return 0


def _device_facts(dryrun: bool, who: str = "") -> dict:
    """Say what jax gave this process, and refuse the wrong platform."""
    import jax

    d = jax.devices()[0]
    facts = {"platform": d.platform, "kind": d.device_kind,
             "count": len(jax.devices())}
    say(f"{who}platform={d.platform} device_kind={d.device_kind!r} "
        f"count={facts['count']} local={jax.local_device_count()}")
    assert d.platform == ("cpu" if dryrun else "tpu"), facts
    return facts


def _watch_pallas_calls() -> list:
    """Record the ``interpret=`` of every pallas_call this process
    makes: on the chip nothing may reach the interpreter."""
    from jax.experimental import pallas as pl

    seen: list = []
    real = pl.pallas_call

    def pallas_call(*a, **kw):
        seen.append(kw.get("interpret", False))
        return real(*a, **kw)

    pl.pallas_call = pallas_call
    return seen


def _no_interpreter(seen: list, dryrun: bool, some: bool = True) -> None:
    """On the chip no pallas_call may be interpreted — and a leg that
    exists to run Pallas kernels (``some``) must have reached one."""
    say(f"pallas_call sites reached: {len(seen)}, "
        f"interpreted: {sum(1 for i in seen if i is not False)}")
    if not dryrun:
        assert all(i is False for i in seen) and (seen or not some), \
            f"pallas_call sites on the chip: {seen}"


def _config(dryrun: bool, n_layers=None):
    import ml_dtypes

    from ompi_tpu.models import transformer as tfm

    w = dict(TOY if dryrun else FULL)
    batch, seq = w.pop("batch"), w.pop("seq")
    if n_layers is not None:
        w["n_layers"] = n_layers
    return tfm.Config(param_dtype=ml_dtypes.bfloat16, **w), batch, seq


def _device_init(cfg, seed: int, shardings=None):
    """Seeded weights with init_params' tree, shapes, dtypes and
    scales, made on the device(s) by one jitted jax.random program.
    Local to this script: tfm.init_params draws 2.09 B numbers with
    numpy on one core (minutes), which is most of a smoke's wall."""
    import jax
    import jax.numpy as jnp

    d, f, v = cfg.d_model, cfg.d_ff, cfg.vocab
    s_emb = 1.0 / math.sqrt(d)
    pdt = jnp.dtype(cfg.param_dtype)
    ones, zeros = ("fill", 1.0), ("fill", 0.0)

    def ln():
        return {"g": ((d,), ones), "b": ((d,), zeros)}

    plan = {
        "embed": ((v, d), s_emb), "pos": ((cfg.max_seq, d), 0.02),
        "ln_f": ln(),
        "layers": [{
            "ln1": ln(), "ln2": ln(),
            "wq": ((d, d), s_emb), "wk": ((d, d), s_emb),
            "wv": ((d, d), s_emb),
            "wo": ((d, d), s_emb / math.sqrt(2 * cfg.n_layers)),
            "w1": ((d, f), s_emb), "w2": ((f, d), 1.0 / math.sqrt(f)),
        } for _ in range(cfg.n_layers)],
    }
    is_leaf = lambda t: isinstance(t, tuple)  # noqa: E731
    leaves, treedef = jax.tree.flatten(plan, is_leaf=is_leaf)

    def make(key):
        out = []
        for k, (shape, how) in zip(jax.random.split(key, len(leaves)),
                                   leaves):
            if isinstance(how, tuple):
                out.append(jnp.full(shape, how[1], pdt))
            else:
                out.append((jax.random.normal(k, shape, jnp.float32)
                            * how).astype(pdt))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(make, out_shardings=shardings)(jax.random.key(seed))


def _check_tree_matches_library() -> None:
    """_device_init must build what tfm.init_params builds (checked at
    toy width, where the host draw costs nothing)."""
    import jax
    import numpy as np

    from ompi_tpu.models import transformer as tfm

    cfg, _, _ = _config(True)
    lib = tfm.init_params(np.random.default_rng(SEED), cfg)
    mine = _device_init(cfg, SEED)
    sig = lambda t: jax.tree.map(  # noqa: E731
        lambda a: (tuple(a.shape), str(a.dtype)), t)
    assert sig(lib) == sig(mine), "device init drifted from init_params"
    for a, b in zip(jax.tree.leaves(lib), jax.tree.leaves(mine)):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        if a.std() > 0:  # same scale, not the same draw
            assert 0.8 < b.std() / a.std() < 1.25, (a.std(), b.std())
        else:
            assert (a == b).all()


def _batch(cfg, batch: int, seq: int, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, cfg.vocab, (batch, seq)).astype(np.int32)
    return tokens, np.roll(tokens, -1, axis=1).astype(np.int32)


def _compile_requests():
    """Count XLA compile requests from here on (jax's own event)."""
    import jax

    box = [0]

    def on_event(event: str, **kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            box[0] += 1

    jax.monitoring.register_event_listener(on_event)
    return box


def _train(step, params, tokens, labels, steps: int = 4):
    """One compile step and ``steps - 1`` more on the repeated batch.
    Returns (params, losses, compile_s, steps_s); asserts the loss is
    finite and strictly decreasing and that only the first step
    compiled (needs the persistent cache wired, as mpi.Init does)."""
    import jax

    requests = _compile_requests()
    t0 = time.perf_counter()
    params, loss = step(params, tokens, labels)
    losses = [float(loss)]
    compile_s = time.perf_counter() - t0
    after_first = requests[0]
    t0 = time.perf_counter()
    for _ in range(steps - 1):
        params, loss = step(params, tokens, labels)
        losses.append(float(loss))
    jax.block_until_ready(params)
    steps_s = time.perf_counter() - t0
    say(f"losses: {losses}")
    say(f"compile requests: first step {after_first}, "
        f"later steps {requests[0] - after_first}; "
        f"jit cache size {step._cache_size()}")
    assert all(math.isfinite(x) for x in losses), losses
    assert all(b < a for a, b in zip(losses, losses[1:])), \
        f"loss not strictly decreasing: {losses}"
    # the event counts real XLA compiles (the jit's own cache may hold
    # a second entry for an equivalent sharding without compiling)
    assert after_first >= 1, "the compile counter saw no compile"
    assert requests[0] == after_first, \
        "a step after the first compiled again"
    return params, losses, compile_s, steps_s


def _osc_matches_host_window(comm, base, epoch) -> None:
    """Run ``epoch(win, conv)`` between two Fences on an osc/pallas
    window over ``base`` and on the host window over a copy: the device
    window must hold the same bits."""
    import jax.numpy as jnp
    import numpy as np

    from ompi_tpu import osc
    from ompi_tpu.osc.pallas import PallasWindow

    wd = osc.win_create(comm, jnp.asarray(base), disp_unit=4)
    assert isinstance(wd, PallasWindow), type(wd).__name__
    wh = osc.Window(comm, base.copy(), disp_unit=4)
    for win, conv in ((wd, jnp.asarray), (wh, lambda a: a)):
        win.Fence()
        epoch(win, conv)
        win.Fence()
    assert np.array_equal(np.asarray(wd.array).view(np.uint32),
                          wh.base.view(np.uint32)), \
        "osc/pallas != host window"
    wd.Free()
    wh.Free()


def _memory(dev) -> dict:
    stats = dev.memory_stats() or {}
    return {k: int(stats[k]) for k in
            ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
            if k in stats}


def leg_probe(dryrun: bool) -> int:
    from importlib import metadata

    import jax

    from ompi_tpu.accelerator import current
    from ompi_tpu.core import native

    def version(pkg: str) -> str:
        try:
            return metadata.version(pkg)
        except metadata.PackageNotFoundError:
            return "not installed"

    versions = {p: version(p) for p in ("jax", "jaxlib", "libtpu")}
    say(f"versions: {versions}")
    say(f"jax.devices(): {jax.devices()}")
    facts = _device_facts(dryrun)
    acc = current()
    peaks = {"peak_bf16_tflops": acc.peak_flops(),
             "hbm_gbps": acc.mem_bandwidth()}
    say(f"accelerator component {acc.NAME!r}: {peaks}")
    say(f"native core: {native.status()}")
    if not dryrun:
        # an unknown kind is an error, not a null utilisation later
        assert None not in peaks.values(), \
            f"no peaks recorded for device_kind {facts['kind']!r}"
    return _passed(device=facts, versions=versions, peaks=peaks,
                   native=native.status())


def leg_rank1(dryrun: bool) -> int:
    """One chip through the launcher: flagship train step, then the
    device-buffer MPI surface at size 1."""
    t_start = time.perf_counter()
    from ompi_tpu import mpi

    comm = mpi.Init()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ompi_tpu import op as op_mod
    from ompi_tpu.accelerator import current
    from ompi_tpu.core import native, pvar
    from ompi_tpu.models import transformer as tfm
    from ompi_tpu.runtime import device_plane

    seen = _watch_pallas_calls()
    facts = _device_facts(dryrun)
    say(f"native core: {native.status()}")
    assert comm.size == 1 and device_plane.active()
    assert jax.local_device_count() == 1 and facts["count"] == 1, facts
    dev = jax.local_devices()[0]
    acc = current()
    _check_tree_matches_library()
    cache = pvar.session()

    cfg, batch, seq = _config(dryrun)
    ax = tfm.Axes()
    specs = tfm.param_specs(cfg, ax)
    t0 = time.perf_counter()
    start_s = t0 - t_start
    params = jax.block_until_ready(_device_init(cfg, SEED))
    init_s = time.perf_counter() - t0
    n_params = sum(x.size for x in jax.tree.leaves(params))
    say(f"config: {cfg}")
    say(f"B={batch} T={seq} params={n_params:,} "
        f"({sum(x.nbytes for x in jax.tree.leaves(params)):,} bytes)")
    t0 = time.perf_counter()
    tokens, labels = (acc.to_device(a)
                      for a in _batch(cfg, batch, seq, SEED))
    jax.block_until_ready((tokens, labels))
    upload_s = time.perf_counter() - t0
    step = jax.jit(tfm.make_train_step(cfg, ax, specs, lr=LR),
                   donate_argnums=(0,))
    params, losses, compile_s, steps_s = _train(
        step, params, tokens, labels)
    mem = _memory(dev)
    say(f"memory_stats: {mem or 'not reported by this backend'}")
    if not dryrun:
        assert mem["peak_bytes_in_use"] < mem["bytes_limit"], mem
    say("set-up split (information only): "
        f"imports+Init {start_s:.1f}s, device init "
        f"{init_s:.1f}s, batch upload {upload_s:.2f}s, compile+step 1 "
        f"{compile_s:.1f}s, steps 2-4 {steps_s:.2f}s")
    del params

    # the framework's chunked H2D path on one weight-sized leaf
    leaf = np.ones((cfg.d_model, cfg.d_ff), cfg.param_dtype)
    t0 = time.perf_counter()
    up = acc.to_device(leaf)
    assert up.shape == leaf.shape and bool(jnp.all(up == 1))
    say(f"to_device of one {leaf.nbytes:,} B leaf: intact "
        f"({time.perf_counter() - t0:.2f}s, information only)")
    del up, leaf

    # device-buffer MPI surface at size 1
    s = pvar.session()
    x = jnp.arange(1 << 18, dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(comm.Allreduce(x)),
                                  np.asarray(x))
    np.testing.assert_array_equal(np.asarray(comm.Bcast(x, root=0)),
                                  np.asarray(x))
    assert s.read("coll_accelerator_staged") == 0
    say(f"Allreduce/Bcast on a device buffer: providers "
        f"{comm.coll.providers['allreduce_dev']}/"
        f"{comm.coll.providers['bcast_dev']}, staged 0")

    # osc/pallas window: Put + Accumulate to self inside a Fence pair,
    # applied on the device, equal to the host window bit for bit
    rng = np.random.default_rng(SEED)
    put = rng.standard_normal(300).astype(np.float32)
    acc_buf = rng.standard_normal(700).astype(np.float32)

    def epoch(win, conv):
        win.Put(conv(put), 0, disp=1001)
        win.Accumulate(conv(acc_buf), 0, disp=1150, op=op_mod.SUM)

    _osc_matches_host_window(
        comm, rng.standard_normal(5000).astype(np.float32), epoch)
    assert s.read("osc_pallas_fallthrough") == 0
    say("osc/pallas Put+Accumulate under Fence: bit-identical to the "
        "host window")
    _no_interpreter(seen, dryrun, some=False)  # size 1: no transport
    hits = cache.read("prof_compile_cache_hits")
    misses = cache.read("prof_compile_cache_misses")
    say(f"persistent compile cache "
        f"{jax.config.jax_compilation_cache_dir}: "
        f"prof_compile_cache_hits={hits} misses={misses}")
    mpi.Finalize()
    return _passed(device=facts, losses=losses, memory=mem,
                   compile_s=round(compile_s, 1),
                   steps_s=round(steps_s, 2), cache_hits=hits,
                   cache_dir=jax.config.jax_compilation_cache_dir)


def _ring_kernel_checks(mesh, axis: str, interpret, dryrun: bool):
    """ring reduce_scatter / allgather / allreduce (ring and bidir)
    against lax.psum / all_gather, exactly: f32, integer-valued."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from ompi_tpu.coll import pallas_kernels as K
    from ompi_tpu.util import jaxcompat

    n = mesh.devices.size
    add = lambda a, b: a + b  # noqa: E731

    def smap(fn):
        return jax.jit(jaxcompat.shard_map(
            lambda a: fn(a[0])[None], mesh=mesh, in_specs=P(axis),
            out_specs=P(axis), check_vma=False))

    pairs = {
        "reduce_scatter": (
            lambda x: K.ring_reduce_scatter(x, axis, add,
                                            interpret=interpret),
            lambda x: lax.psum_scatter(x, axis, tiled=True)),
        "allgather": (
            lambda x: K.ring_allgather(x, axis, interpret=interpret),
            lambda x: lax.all_gather(x, axis, tiled=True)),
        "allreduce/ring": (
            lambda x: K.ring_allreduce(x, axis, add,
                                       interpret=interpret),
            lambda x: lax.psum(x, axis)),
        "allreduce/bidir": (
            lambda x: K.ring_allreduce(x, axis, add, bidir=True,
                                       interpret=interpret),
            lambda x: lax.psum(x, axis)),
    }
    # 64 KiB, 1 MiB, and a length no tile (8 x 128 x 4 B) divides
    lengths = (1 << 12, 1 << 14, 1003 * n) if dryrun \
        else (1 << 14, 1 << 18, 250007 * n)
    rng = np.random.default_rng(SEED)
    for m in lengths:
        x = jnp.asarray(
            rng.integers(-64, 64, (n, m)).astype(np.float32))
        for name, (mine, ref) in pairs.items():
            got, want = smap(mine)(x), smap(ref)(x)
            assert bool(jnp.array_equal(got, want)), (name, m)
        say(f"ring kernels at {4 * m:,} B: "
            f"{', '.join(pairs)} == lax, exactly")


def leg_mesh4(dryrun: bool) -> int:
    """Four chips, one process: 2x2 dp x tp train step; ring kernels."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ompi_tpu import parallel, prof
    from ompi_tpu.models import transformer as tfm
    from ompi_tpu.util import jaxcompat

    say(f"persistent compile cache: {prof.wire_compile_cache()}")
    seen = _watch_pallas_calls()
    facts = _device_facts(dryrun)
    assert facts["count"] >= 4, facts

    mesh = parallel.make_mesh(("dp", "tp"), (2, 2))
    ax = tfm.Axes(dp="dp", tp="tp")
    cfg, batch, seq = _config(dryrun)
    specs = tfm.param_specs(cfg, ax)
    shard = lambda spec: NamedSharding(mesh, spec)  # noqa: E731
    params = _device_init(
        cfg, SEED, jax.tree.map(shard, specs,
                                is_leaf=lambda s: isinstance(s, P)))
    data = P("dp", None)
    tokens, labels = (jax.device_put(a, shard(data))
                      for a in _batch(cfg, batch, seq, SEED))
    step = jax.jit(jaxcompat.shard_map(
        tfm.make_train_step(cfg, ax, specs, lr=LR), mesh=mesh,
        in_specs=(specs, data, data), out_specs=(specs, P()),
        check_vma=False), donate_argnums=(0,))
    params, losses, compile_s, steps_s = _train(
        step, params, tokens, labels)

    # every device holds its dp x tp share of the weights, no more
    held = {d: 0 for d in mesh.devices.flat}
    for leaf in jax.tree.leaves(params):
        for sh in leaf.addressable_shards:
            held[sh.device] += sh.data.nbytes
    total = sum(x.nbytes for x in jax.tree.leaves(params))
    tp_sharded = sum(
        x.nbytes for x, s in zip(
            jax.tree.leaves(params),
            jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P)))
        if "tp" in tuple(s))
    predicted = total - tp_sharded // 2
    say(f"weights: {total:,} B; predicted per device {predicted:,} B; "
        f"held {sorted(held.values())}")
    assert set(held.values()) == {predicted}, (held, predicted)
    mems = [_memory(d) for d in mesh.devices.flat]
    say(f"memory_stats per device: "
        f"{mems if mems[0] else 'not reported by this backend'}")
    if not dryrun:
        in_use = [m["bytes_in_use"] for m in mems]
        assert min(in_use) >= predicted, (in_use, predicted)
        assert max(in_use) <= 1.25 * min(in_use), \
            f"weights not balanced over the chips: {in_use}"
        assert all(m["peak_bytes_in_use"] < m["bytes_limit"]
                   for m in mems), mems
    say(f"compile+step 1 {compile_s:.1f}s, steps 2-4 {steps_s:.2f}s "
        f"(information only)")
    del params

    ring = parallel.make_mesh(("ring",), (4,))
    _ring_kernel_checks(ring, "ring", True if dryrun else False, dryrun)
    _no_interpreter(seen, dryrun)
    return _passed(device=facts, losses=losses, memory=mems,
                   compile_s=round(compile_s, 1),
                   steps_s=round(steps_s, 2))


def _rank_pattern(n_elems: int, rank: int):
    """Integer-valued f32 contribution of ``rank`` (device array)."""
    import jax.numpy as jnp

    return (jnp.arange(n_elems, dtype=jnp.int32) % 97 + rank).astype(
        jnp.float32)


def _assert_four_ranks(comm, dryrun: bool) -> dict:
    import jax

    from ompi_tpu.runtime import device_plane

    facts = _device_facts(dryrun, f"rank {comm.rank}: ")
    assert comm.size == 4 and device_plane.active()
    assert jax.local_device_count() == 1 and facts["count"] == 4, facts
    return facts


def leg_ranks4(dryrun: bool) -> int:
    """Four chips, four ranks: BASELINE.json's collectives on
    device-resident buffers against the host plane; data-parallel
    steps over Allreduce_multi."""
    t0 = time.perf_counter()
    from ompi_tpu import mpi

    comm = mpi.Init()
    init_s = time.perf_counter() - t0
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ompi_tpu import op as op_mod
    from ompi_tpu.core import pvar
    from ompi_tpu.models import transformer as tfm

    rank, size = comm.rank, comm.size
    facts = _assert_four_ranks(comm, dryrun)
    say(f"rank {rank}: mpi.Init() with device-plane bootstrap took "
        f"{init_s:.1f}s (device_plane_timeout is 60s)")
    assert comm.coll.providers["allreduce"] == "basic"
    s = pvar.session()

    def host(a):
        return np.asarray(a)

    # Allreduce(SUM) f32 at 1 KiB / 1 MiB / 64 MiB
    big = 1 << (14 if dryrun else 24)
    for n_elems in (1 << 8, 1 << 18, big):
        x = _rank_pattern(n_elems, rank)
        got = comm.Allreduce(x)
        want = size * _rank_pattern(n_elems, 0) + sum(range(size))
        assert bool(jnp.array_equal(got, want)), n_elems
        if n_elems <= 1 << 18:  # the host plane on the numpy copy
            ref = np.empty(n_elems, np.float32)
            comm.Allreduce(host(x), ref)
            np.testing.assert_array_equal(host(got), ref)
    # 'linear' == coll/basic's rank-order fold, bit for bit
    rng = np.random.default_rng(7)
    h = (rng.standard_normal(1 << 18)
         * (10.0 ** rng.integers(-3, 4, 1 << 18))).astype(np.float32)
    h = np.roll(h, rank)
    ref = np.empty_like(h)
    comm.Allreduce(h, ref)
    got = host(comm.Allreduce(jnp.asarray(h), deterministic="linear"))
    assert got.view(np.uint32).tolist() == ref.view(np.uint32).tolist()
    # Bcast 1 MiB
    x = _rank_pattern(1 << 18, rank)
    ref = host(x).copy()
    comm.Bcast(ref, root=1)
    np.testing.assert_array_equal(host(comm.Bcast(x, root=1)), ref)
    # Reduce_scatter_block, Allgather, Alltoall (int32)
    k = 1 << 16
    x = _rank_pattern(size * k, rank)
    ref = np.empty(k, np.float32)
    comm.Reduce_scatter_block(host(x), ref)
    np.testing.assert_array_equal(host(comm.Reduce_scatter_block(x)),
                                  ref)
    x = _rank_pattern(k, rank)
    ref = np.empty(size * k, np.float32)
    comm.Allgather(host(x), ref)
    np.testing.assert_array_equal(host(comm.Allgather(x)).reshape(-1),
                                  ref)
    xi = (jnp.arange(size * k, dtype=jnp.int32) * 7 + rank)
    ref = np.empty(size * k, np.int32)
    comm.Alltoall(host(xi), ref)
    np.testing.assert_array_equal(host(comm.Alltoall(xi)).reshape(-1),
                                  ref)
    launches = s.read("coll_xla_launches")
    staged = s.read("coll_accelerator_staged")
    say(f"rank {rank}: five collectives == host plane; "
        f"coll_xla_launches={launches} coll_accelerator_staged={staged}")
    assert launches > 0 and staged == 0, (launches, staged)

    # three data-parallel steps: full width, depth cut to fit
    cfg, batch, seq = _config(dryrun, n_layers=DP_LAYERS)
    ax = tfm.Axes()
    params = _device_init(cfg, SEED)
    tokens, labels = (jnp.asarray(a)
                      for a in _batch(cfg, batch, seq, SEED + 1 + rank))

    def loss_fn(p):
        nll, cnt = tfm.loss_local(p, tokens, labels, cfg, ax)
        return nll / cnt

    value_and_grad = jax.jit(jax.value_and_grad(loss_fn))
    update = jax.jit(
        lambda p, g: jax.tree.map(
            lambda a, b: (a - (LR / size) * b.astype(a.dtype)).astype(
                a.dtype), p, g),
        donate_argnums=(0,))
    losses = []
    for _ in range(3):
        loss, grads = value_and_grad(params)
        grads = comm.Allreduce_multi(grads)
        params = update(params, grads)
        del grads
        losses.append(float(comm.Allreduce(loss.reshape(1))[0]) / size)
    checksum = sum(jnp.sum(x.astype(jnp.float32))
                   for x in jax.tree.leaves(params)).reshape(1)
    lo = float(comm.Allreduce(checksum, op=op_mod.MIN)[0])
    hi = float(comm.Allreduce(checksum, op=op_mod.MAX)[0])
    mem = _memory(jax.local_devices()[0])
    say(f"rank {rank}: data-parallel L={DP_LAYERS} mean losses "
        f"{losses}; params checksum min {lo} max {hi}; memory {mem}")
    assert all(b < a for a, b in zip(losses, losses[1:])), losses
    assert lo == hi and math.isfinite(lo), (lo, hi)
    assert s.read("coll_accelerator_staged") == 0
    mpi.Finalize()
    if rank:
        return 0
    return _passed(device=facts, dp_layers=DP_LAYERS,
                   dp_losses=losses, init_s=round(init_s, 1),
                   memory=mem)


def _census(name: str, got, want) -> str:
    """One more Pallas path behind a public entry point, against its
    coll/xla or host-window reference. A kernel that cannot run on the
    chip raises MPIError with the compiler's message (it never drops
    to the interpreter) — which fails this leg, as a wrong answer
    does."""
    import jax.numpy as jnp

    want = jnp.asarray(want)
    assert bool(jnp.array_equal(jnp.asarray(got).reshape(want.shape),
                                want)), f"census {name}: wrong result"
    return _census_pass(name)


_census_t = [0.0]


def _census_pass(name: str) -> str:
    """Say so, with the host seconds since the previous entry (compile
    included; information only)."""
    now = time.perf_counter()
    took = f" ({now - _census_t[0]:.1f}s)" if _census_t[0] else ""
    _census_t[0] = now
    say(f"census {name}: PASS{took}")
    return name


def leg_pallas4(dryrun: bool) -> int:
    """The four ranks again under coll_pallas / osc_pallas: every
    Pallas kernel a public entry point reaches, at toy width and at
    the edge of what it can hold in VMEM, and — one size past that
    edge, up to a flagship leaf — the counted fallthrough."""
    from ompi_tpu import mpi

    comm = mpi.Init()
    import jax.numpy as jnp
    import numpy as np

    from ompi_tpu.coll import pallas as coll_pallas
    from ompi_tpu.coll import pallas_kernels as K
    from ompi_tpu.coll import xla as coll_xla
    from ompi_tpu.core import pvar
    from ompi_tpu.zero import ZeroOptimizer

    seen = _watch_pallas_calls()
    rank, size = comm.rank, comm.size
    facts = _assert_four_ranks(comm, dryrun)
    assert comm.coll.providers["allreduce_dev"] == "pallas"
    s = pvar.session()
    x = _rank_pattern(1 << 18, rank)  # 1 MiB
    want = coll_xla.allreduce_dev(comm, x)
    for det in (None, "ring"):  # bidir at this size, then the ring
        got = comm.Allreduce(x, deterministic=det)
        assert bool(jnp.array_equal(got, want)), det
    launches = s.read("pallas_launches")
    fell = s.read("pallas_fallthrough")
    say(f"rank {rank}: 1 MiB Allreduce through coll/pallas == coll/xla;"
        f" pallas_launches={launches} pallas_fallthrough={fell}")
    assert launches >= 2 and fell == 0, (launches, fell)

    def falls(by: int) -> None:
        """The TPU path declined ``by`` more calls since the last look
        (the interpret path of the dry run holds nothing in VMEM)."""
        nonlocal fell
        now = s.read("pallas_fallthrough")
        assert dryrun or now - fell == by, (now, fell, by)
        fell = now

    passed = []
    k = 1 << 14
    xs = _rank_pattern(size * k, rank)
    passed.append(_census(
        "reduce_scatter_block/ring",
        comm.Reduce_scatter_block(xs, deterministic="ring"),
        coll_xla.reduce_scatter_block_dev(comm, xs)))
    passed.append(_census(
        "allgather", comm.Allgather(xs[:k]),
        coll_xla.allgather_dev(comm, xs[:k])))
    passed.append(_census(
        "allreduce/linear", comm.Allreduce(x, deterministic="linear"),
        want))
    passed.append(_census(
        "reduce_scatter_block/linear",
        comm.Reduce_scatter_block(xs, deterministic="linear"),
        coll_xla.reduce_scatter_block_dev(comm, xs)))
    for dt in (jnp.bfloat16, jnp.int32):
        xd = (x % 8).astype(dt)
        passed.append(_census(
            f"allreduce/ring {jnp.dtype(dt).name}",
            comm.Allreduce(xd, deterministic="ring"),
            coll_xla.allreduce_dev(comm, xd, deterministic="ring")))
    falls(0)
    # the largest payload whose ring the kernel holds in VMEM (a
    # length no tile divides), then twice that: coll/xla serves it
    bound = coll_pallas._dma_max_var.get()
    edge = 1003 * size if dryrun else bound * size // (2 * size + 1) \
        // (4 * size) * size
    assert K.ring_vmem_bytes(size, 4 * edge) <= bound
    for n_elems, by in ((edge, 0), (2 * edge, 1)):
        xe = _rank_pattern(n_elems, rank)
        passed.append(_census(
            f"allreduce/ring {4 * n_elems:,} B"
            + (" (falls through)" if by and not dryrun else ""),
            comm.Allreduce(xe, deterministic="ring"),
            coll_xla.allreduce_dev(comm, xe, deterministic="ring")))
        falls(by)

    # fused reduce_scatter + ZeRO update against the unfused cycle:
    # two small leaves in one padded bucket; one 4 MiB bucket with
    # momentum (12 MiB of VMEM); one flagship attention leaf (d_model
    # x d_model bf16, 103 MB), which no kernel holds: the optimizer
    # must run the unfused sequence and say so
    def zero_step(params, grads, key, fused: bool, mu: float):
        opt = ZeroOptimizer(comm, params, lr=0.5, momentum=mu,
                            fused=fused)
        try:
            return opt.step(grads)[key]
        finally:
            opt.free()

    def fill(shapes, value, dtype):
        return {name: jnp.full(shape, value, dtype)
                for name, shape in shapes.items()}

    full = FULL if not dryrun else TOY
    for name, shapes, dt, mu, by in (
            ("2 leaves", {"w": (64, 96), "b": (1000,)}, jnp.float32,
             0.0, 0),
            ("one bucket" if dryrun else "4 MiB bucket",
             {"b": (64, 64) if dryrun else (1024, 1024)}, jnp.float32,
             0.5, 0),
            ("flagship leaf", {"b": (full["d_model"], full["d_model"])},
             jnp.bfloat16, 0.5, 1)):
        params, grads = fill(shapes, 1.0, dt), fill(shapes, rank + 1.0, dt)
        fused_before = s.read("pallas_fused_launches")
        passed.append(_census(
            f"fused_rs_update {name}"
            + (" (falls through)" if by and not dryrun else ""),
            zero_step(params, grads, "b", True, mu),
            zero_step(params, grads, "b", False, mu)))
        assert dryrun or by or \
            s.read("pallas_fused_launches") > fused_before
        falls(by)
        del params, grads

    # allgather-matmul: f32 toy, a bf16 block at the VMEM edge, then
    # the flagship's tensor-parallel block (B*T/4 x d_model against
    # d_model x d_model), which falls through to allgather + dot
    rows = full["batch"] * full["seq"] // size
    for name, (m, d, f), dt, by in (
            ("f32", (16, 128, 256), jnp.float32, 0),
            ("bf16", (16, 128, 256) if dryrun else (512, 1024, 1024),
             jnp.bfloat16, 0),
            ("bf16 flagship block",
             (rows, full["d_model"], full["d_model"]), jnp.bfloat16, 1)):
        xm = ((jnp.arange(m * d, dtype=jnp.int32).reshape(m, d) % 7 == 0)
              * (1 + rank % 2)).astype(dt)
        w = (jnp.arange(d * f, dtype=jnp.int32).reshape(d, f) % 5
             == 0).astype(dt)
        gathered = jnp.asarray(coll_xla.allgather_dev(comm, xm)).reshape(
            size * m, d)
        passed.append(_census(
            f"allgather_matmul {name}"
            + (" (falls through)" if by and not dryrun else ""),
            comm.coll.allgather_matmul_dev(comm, xm, w),
            jnp.dot(gathered, w)))
        falls(by)
        del xm, w, gathered

    # osc/pallas fence epoch: every rank Puts to its right neighbour,
    # bit-identical to the host window — 4 KB, the largest round the
    # DMA kernel holds (4 MiB), and twice that over XLA's permute
    rng = np.random.default_rng(40 + rank)
    big = 1 << 10 if dryrun else bound // 12
    for n_put, by in ((1000, 0), (big, 0), (2 * big, 1)):
        put = rng.standard_normal(n_put).astype(np.float32)
        _osc_matches_host_window(
            comm, rng.standard_normal(2 * n_put + 96).astype(np.float32),
            lambda win, conv: win.Put(conv(put), (rank + 1) % size,
                                      disp=77))
        osc_fell = s.read("osc_pallas_fallthrough")
        assert dryrun or (osc_fell > 0) == bool(by), (n_put, osc_fell)
        passed.append(_census_pass(
            f"osc_fence_put {4 * n_put:,} B"
            + (" (falls through)" if by and not dryrun else "")))
    _no_interpreter(seen, dryrun)
    mpi.Finalize()
    if rank:
        return 0
    return _passed(device=facts, census=passed, vmem_bound=bound,
                   pallas_launches=s.read("pallas_launches"),
                   pallas_fallthrough=fell,
                   osc_pallas_fallthrough=osc_fell)


_LEGS = {"probe": leg_probe, "rank1": leg_rank1, "mesh4": leg_mesh4,
         "ranks4": leg_ranks4, "pallas4": leg_pallas4}

if __name__ == "__main__":
    sys.exit(main())
