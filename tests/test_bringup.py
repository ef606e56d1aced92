"""Bring-up contracts (PR 21): nothing on the device path may quietly
run somewhere other than where it was asked to.

Unit tests, no rank pools: which platform a rank's jax may use and which
chip it owns (launcher.build_env), a requested plane that did not come
up (device_plane), where the compile cache goes (prof), a stale native
core (core/native). One launcher job: asking for TPU ranks on a box
without a TPU is an error, not a CPU run that exits 0.
"""

import os
import subprocess
import sys
import tempfile

import pytest

from ompi_tpu import errors
from ompi_tpu.runtime import launcher

STORE = ("127.0.0.1", 1)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(rank, size, mca, base=None, **kw):
    return launcher.build_env(rank, size, STORE, "job", mca,
                              base_env=dict(base or {}), **kw)


def test_build_env_cpu_pins_ranks_to_cpu_whatever_the_shell_says():
    env = _env(1, 4, {}, base={"JAX_PLATFORMS": "tpu",
                               "OMPI_TPU_RANK_JAX_PLATFORMS": "tpu"})
    assert env["JAX_PLATFORMS"] == "cpu"  # the old second knob is gone
    assert not any(k.startswith("TPU_") for k in env)


def test_build_env_tpu_gives_rank_i_chip_i():
    envs = [_env(r, 4, {"device_plane_platform": "tpu"},
                 base={"JAX_PLATFORMS": "cpu"}) for r in range(4)]
    for r, env in enumerate(envs):
        assert env["JAX_PLATFORMS"] == "tpu"
        assert env["TPU_VISIBLE_CHIPS"] == str(r)
        assert env["CLOUD_TPU_TASK_ID"] == str(r)
        assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert env["TPU_PROCESS_BOUNDS"] == "2,2,1"
        assert env["TPU_PROCESS_PORT"] == \
            env["TPU_PROCESS_ADDRESSES"].split(",")[r].split(":")[1]
    assert len({e["TPU_PROCESS_ADDRESSES"] for e in envs}) == 1
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4
    one = _env(0, 1, {}, base={"OMPI_TPU_DEVICE_PLANE_PLATFORM": "tpu"})
    assert (one["JAX_PLATFORMS"], one["TPU_PROCESS_BOUNDS"],
            one["TPU_VISIBLE_CHIPS"]) == ("tpu", "1,1,1", "0")


def test_build_env_tpu_refuses_what_it_cannot_partition():
    with pytest.raises(ValueError, match=r"\[1, 4\] ranks"):
        _env(0, 2, {"device_plane_platform": "tpu"})
    with pytest.raises(ValueError, match="single host"):
        _env(2, 8, {"device_plane_platform": "tpu"}, local_rank=0,
             local_size=4)
    with pytest.raises(ValueError, match=r"\[1, 4\] ranks"):
        _env(0, 8, {"device_plane_platform": "tpu"})  # never run: no row


def test_plane_on_the_wrong_platform_is_an_mpierror(monkeypatch):
    """jax hands back a CPU device where a TPU was asked for: every
    rank raises out of init_plane (here the singleton world)."""
    import jax

    from ompi_tpu.runtime import device_plane, rte

    rte.init()
    # keep the test process on its CPU backend: only the check runs
    monkeypatch.setattr(jax.config, "update", lambda *a, **kw: None)
    monkeypatch.setattr(device_plane._platform, "_value", "tpu")
    with pytest.raises(errors.MPIError,
                       match="asked for platform 'tpu', jax gave 'cpu'"):
        device_plane.init_plane()
    assert not device_plane.active()


def test_leader_whose_setup_failed_still_releases_its_peers(monkeypatch):
    """Peers block on the coordinator key: a leader that fails before
    it has an address writes the FAILED sentinel, and a peer that
    reads it reports instead of joining a cluster nobody started."""
    import jax

    from ompi_tpu.runtime import device_plane, rte

    store = {}

    class Client:
        def put(self, key, value):
            store[key] = value

        def get(self, key, wait=False):
            return store[key]

    def refuse(*a, **kw):
        raise RuntimeError("no such platform")

    monkeypatch.setattr(rte, "size", 2)
    monkeypatch.setattr(rte, "client", Client)
    monkeypatch.setattr(jax.config, "update", refuse)
    monkeypatch.setattr(jax.distributed, "initialize", refuse)
    assert device_plane._bootstrap("tpu").startswith("jax setup failed")
    assert list(store.values()) == [device_plane._FAILED]
    monkeypatch.setattr(rte, "rank", 1)
    monkeypatch.setattr(jax.config, "update", lambda *a, **kw: None)
    assert device_plane._bootstrap("tpu") == \
        "the leader rank could not start a coordinator"


def test_tpu_ranks_without_a_tpu_fail_the_job():
    """Seed behaviour: exit 0 after a CPU run over gloo."""
    with tempfile.NamedTemporaryFile("w", suffix=".py",
                                     delete=False) as fh:
        fh.write("from ompi_tpu import mpi\nmpi.Init()\n"
                 "print('RAN ANYWAY')\nmpi.Finalize()\n")
    try:
        r = subprocess.run(
            [sys.executable, "-m", "ompi_tpu.runtime.launcher", "-n",
             "4", "--timeout", "120", "--mca", "device_plane", "on",
             "--mca", "device_plane_platform", "tpu", fh.name],
            capture_output=True, text=True, cwd=REPO, timeout=180)
    finally:
        os.unlink(fh.name)
    assert r.returncode != 0
    assert "RAN ANYWAY" not in r.stdout
    assert "did not come up" in r.stderr, r.stderr[-2000:]


# -- chip_smoke.py's contract with the driver --------------------------------


def _chip_smoke():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_result_line_holds_exactly_the_contract_keys():
    """The driver refuses any other key on the last stdout line (it
    refused PR 21's first try, which put the per-leg facts there)."""
    import json

    probe = {"platform": "tpu", "kind": "TPU v5 lite", "count": 4,
             "anything_else": 1}
    line = json.loads(json.dumps(_chip_smoke()._verdict(True, probe)))
    assert line == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4}}
    assert type(line["device"]["count"]) is int


def test_chip_smoke_alone_or_without_a_tpu_fails_with_no_result(tmp_path):
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    for cwd in (str(tmp_path), REPO):
        r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                           env=env, capture_output=True, text=True,
                           timeout=120)
        assert r.returncode != 0, r.stdout[-2000:]
        assert '"ok"' not in r.stdout, r.stdout[-2000:]
        assert "no usable TPU" in r.stderr, r.stderr[-2000:]


# -- compile cache placement -------------------------------------------------


@pytest.fixture
def cache_updates(monkeypatch):
    """Every jax_compilation_cache_dir update wire_compile_cache makes."""
    import jax

    from ompi_tpu import prof

    seen = []
    real = jax.config.update

    def update(name, value):
        if name == "jax_compilation_cache_dir":
            seen.append(value)
        else:
            real(name, value)

    monkeypatch.setattr(jax.config, "update", update)
    monkeypatch.setattr(prof._cache_dir_var, "_value", "")
    return seen


def test_cache_dir_from_the_environment_is_not_set_in_code(
        tmp_path, monkeypatch, cache_updates):
    from ompi_tpu import prof

    d = str(tmp_path / "placed_from_outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    monkeypatch.setattr(prof._cache_dir_var, "_value",
                        str(tmp_path / "cvar_loses"))
    assert prof.wire_compile_cache() == d
    assert os.path.isdir(d)
    assert cache_updates == []


def test_cache_dir_defaults_to_a_fixed_path_in_the_checkout(
        monkeypatch, cache_updates, tmp_path):
    from ompi_tpu import prof

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert prof.wire_compile_cache() == want == prof.DEFAULT_CACHE_DIR
    assert cache_updates == [want]
    assert not want.startswith(tempfile.gettempdir())
    cvar_dir = str(tmp_path / "from_cvar")
    monkeypatch.setattr(prof._cache_dir_var, "_value", cvar_dir)
    assert prof.wire_compile_cache() == cvar_dir
    assert cache_updates == [want, cvar_dir]


def test_cache_dir_that_cannot_be_made_is_an_error(
        tmp_path, monkeypatch, cache_updates):
    from ompi_tpu import prof

    blocker = tmp_path / "a_file"
    blocker.write_text("")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(blocker / "below_a_file"))
    with pytest.raises(errors.MPIError, match="cannot create"):
        prof.wire_compile_cache()


def test_cache_hit_miss_accounting(cache_updates, pvar_clean):
    """jax fires compile_requests_use_cache and (only on a hit)
    cache_hits INSIDE the backend event of the program that asked;
    the compile ledger counts the request once, when that event
    ends, as a hit or as a miss."""
    from jax import monitoring as jmon

    from ompi_tpu import prof
    from ompi_tpu.core import pvar

    prof.wire_compile_cache()
    s = pvar.session()
    req = "/jax/compilation_cache/compile_requests_use_cache"
    backend = "/jax/core/compile/backend_compile_duration"
    jmon.record_event(req)
    jmon.record_event_time_span(backend, 1.0, 2.0, fun_name="jit(f)")
    assert (s.read("prof_compile_cache_misses"),
            s.read("prof_compile_cache_hits")) == (1, 0)
    jmon.record_event(req)
    jmon.record_event("/jax/compilation_cache/cache_hits")
    jmon.record_event_time_span(backend, 3.0, 4.0, fun_name="jit(f)")
    assert (s.read("prof_compile_cache_misses"),
            s.read("prof_compile_cache_hits")) == (1, 1)


# -- native core -------------------------------------------------------------


def test_native_loader_rebuilds_a_stale_library():
    from ompi_tpu.core import native

    if not native.available():
        pytest.skip("no C compiler for the native core")
    try:
        os.utime(native._SRC)  # the source is now newer than the .so
        native.reset_for_testing()
        assert native.status() == "native: built in this run"
        assert os.path.getmtime(native._SO) >= \
            os.path.getmtime(native._SRC)
        native.reset_for_testing()
        assert native.status() == "native: loaded an up-to-date build"
    finally:
        native.reset_for_testing()
