"""Device plane bootstrap — multi-controller jax over the store.

Reference analog: the reference's one-process-per-GPU model where NCCL
communicators are bootstrapped through PMIx modex
(ompi/runtime/ompi_rte.c:580 proc naming;
opal/mca/btl/tcp/btl_tcp_component.c:1191-1240 endpoint exchange). The
TPU-first equivalent is **multi-controller jax**: every MPI rank runs
``jax.distributed.initialize`` against a coordinator brokered through
the kv store, after which ``jax.devices()`` spans all ranks' chips and
XLA collectives (psum/all_gather/...) execute directly over ICI/DCN —
this is what :mod:`ompi_tpu.coll.xla` compiles communicator collectives
onto.

Deployment modes (cvar ``device_plane_platform``):

- ``cpu`` (default): ranks use the virtual CPU backend with gloo
  cross-process collectives — the single-host test/dev configuration
  (and the CI stand-in for a pod).
- ``tpu``: one rank per chip. The launcher hands local rank i chip i
  (``launcher._tpu_chip_env``); here we broker the coordinator and
  check that jax really gave this rank a TPU.

The plane is opt-in (cvar ``device_plane=on``, e.g. ``tpurun --mca
device_plane on``): initialization is collective over the world and
pulls jax into every rank, which pure host-MPI jobs shouldn't pay for.
The outcome is agreed through the modex so every rank sees the same
answer — a rank-divergent coll table would deadlock. A plane that was
asked for and did not come up, on the platform that was asked for, on
every rank, is an ``MPIError`` out of ``MPI_Init`` on every rank: a
device job never continues on host staging behind the user's back.
"""

from __future__ import annotations

import importlib
import socket
import threading
from typing import Dict, Optional

from ompi_tpu import errors
from ompi_tpu.core import cvar, output
from ompi_tpu.runtime import rte
from ompi_tpu.runtime.state import init_phase

_out = output.stream("device_plane")

_enabled = cvar.register(
    "device_plane", "off", str,
    help="multi-controller device plane: 'on' initializes "
         "jax.distributed across all ranks at MPI_Init so device-buffer "
         "collectives execute on device (coll/xla); 'off' leaves device "
         "buffers to the staging path (coll/accelerator)",
    choices=["on", "off"], level=3)

_platform = cvar.register(
    "device_plane_platform", "cpu", str,
    help="rank device platform: 'cpu' = virtual CPU devices with gloo "
         "collectives (single-host/test), 'tpu' = one rank per real chip "
         "(pod deployment, native ICI collectives)",
    choices=["cpu", "tpu"], level=3)

_timeout = cvar.register(
    "device_plane_timeout", 60, int,
    help="seconds to wait for jax.distributed bootstrap before a rank "
         "reports failure (the modex agreement then fails MPI_Init on "
         "every rank instead of hanging it)", level=6)

_lock = threading.Lock()
_state: Optional[dict] = None  # {"devices": {world_rank: Device}, "my": Device}

_FAILED = "FAILED"  # coordinator-key sentinel: rank 0 could not bootstrap


def _free_port() -> int:
    s = socket.socket()
    s.bind(("0.0.0.0", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _my_ip() -> str:
    """This host's address as reachable by peers: the outbound interface
    toward the store (multi-host pods must not get loopback)."""
    store = rte.client().addr if hasattr(rte.client(), "addr") else None
    host = store[0] if store else "127.0.0.1"
    if host in ("127.0.0.1", "localhost", ""):
        return "127.0.0.1"
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        s.connect((host, 1))
        return s.getsockname()[0]
    except OSError:
        return socket.gethostbyname(socket.gethostname())
    finally:
        s.close()


def requested() -> bool:
    return _enabled.get() == "on"


def active() -> bool:
    return _state is not None


def my_device():
    assert _state is not None
    return _state["my"]


def device_for_world_rank(world_rank: int):
    """The device owned by a world rank (None if that rank has none)."""
    if _state is None:
        return None
    return _state["devices"].get(world_rank)


def _bootstrap(platform: str) -> Optional[str]:
    """Point jax at ``platform``, join the world's jax.distributed
    cluster (phase ``distributed``) and check what jax handed back
    (phase ``client``: the first ``jax.local_devices()`` makes the
    backend's client). Returns None, or why this rank has no device on
    that platform. Never raises: every rank must reach the modex
    agreement, failed or not."""
    with init_phase("devplane.distributed"):
        why = _join_cluster(platform)
    if why is not None:
        return why
    import jax

    if platform == "tpu":
        # jax's import of its Pallas packages takes ~1 s of Python, paid
        # at first use by whoever runs a kernel on this plane (the
        # model's blockwise attention, coll/pallas, osc/pallas). The
        # TPU client's start below waits 6-10 s outside the
        # interpreter: the import runs beside it.
        threading.Thread(target=importlib.import_module,
                         args=("jax.experimental.pallas.tpu",),
                         name="pallas-import", daemon=True).start()
    with init_phase("devplane.client"):
        try:
            dev = jax.local_devices()[0]
        except Exception as exc:  # noqa: BLE001 — e.g. no TPU to attach
            return f"no local {platform} device: {exc!r}"
    if dev.platform != platform:
        return (f"asked for platform {platform!r}, jax gave "
                f"{dev.platform!r} ({dev.device_kind})")
    return None


def _join_cluster(platform: str) -> Optional[str]:
    why = None
    try:
        import jax

        # the cvar, not an inherited JAX_PLATFORMS, names the backend
        jax.config.update("jax_platforms", platform)
        if platform == "cpu" and rte.size > 1:
            jax.config.update(
                "jax_cpu_collectives_implementation", "gloo")
    except Exception as exc:  # noqa: BLE001 — must reach agreement
        why = f"jax setup failed: {exc!r}"
    if rte.size > 1:
        # world-namespaced: a spawned world bootstraps its OWN
        # jax.distributed cluster; its leader is its first world
        # rank (rte.world_offset), not global rank 0
        key = f"devplane:{rte.jobid}:{rte.world_offset}:coord"
        if rte.rank == rte.world_offset:
            # peers block on this key, so the leader writes it whatever
            # happened to it — _FAILED when its own setup failed — and
            # before any blocking work of its own
            coord = _FAILED
            if why is None:
                try:
                    coord = f"{_my_ip()}:{_free_port()}"
                except OSError as exc:
                    why = f"no coordinator address: {exc!r}"
            rte.client().put(key, coord)
        elif why is None:
            coord = rte.client().get(key, wait=True)
            if coord == _FAILED:
                why = "the leader rank could not start a coordinator"
        if why is None:
            try:
                jax.distributed.initialize(
                    coordinator_address=coord,
                    num_processes=rte.size,
                    process_id=rte.rank - rte.world_offset,
                    initialization_timeout=_timeout.get())
            except Exception as exc:  # noqa: BLE001
                why = f"jax.distributed bootstrap failed: {exc!r}"
    return why


def init_plane() -> None:
    """Collective over the world job: bring up jax.distributed on the
    ``device_plane_platform`` and exchange the rank->device map.
    Raises ``MPIError`` on EVERY rank (agreement via modex) when any
    rank failed, naming the ranks and their reasons."""
    global _state
    with _lock:
        if _state is not None:
            return
        platform = _platform.get()
        why = _bootstrap(platform)
        dev_id = None
        if why is None:
            import jax

            dev_id = jax.local_devices()[0].id
        else:
            _out.verbose(1, "device plane: rank %d: %s", rte.rank, why)
        with init_phase("devplane.fence"):  # waiting for the slowest rank
            rte.modex_send("devplane",
                           {"error": why, "device_id": dev_id})
            rte.fence("devplane")
            peers: Dict[int, dict] = {
                r: rte.modex_recv("devplane", r)
                for r in rte.world_ranks()}
        bad = {r: (p or {}).get("error", "published nothing")
               for r, p in peers.items()
               if not p or p.get("error") is not None}
        if bad:
            raise errors.MPIError(
                errors.ERR_OTHER,
                f"device plane requested (--mca device_plane on, "
                f"platform {platform!r}) but it did not come up: "
                + "; ".join(f"rank {r}: {w}"
                            for r, w in sorted(bad.items())))
        import jax

        by_id = {d.id: d for d in jax.devices()}
        missing = sorted(r for r, p in peers.items()
                         if p["device_id"] not in by_id)
        if missing:
            raise errors.MPIError(
                errors.ERR_OTHER,
                f"device plane: the devices of rank(s) {missing} are "
                f"not among the {len(by_id)} global jax devices")
        devices = {r: by_id[p["device_id"]] for r, p in peers.items()}
        _state = {"devices": devices, "my": devices[rte.rank]}
        _out.verbose(2, "device plane up: %d global device(s), mine=%s",
                     len(by_id), _state["my"])
