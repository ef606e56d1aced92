"""Test config: force an 8-device virtual CPU mesh for sharding tests.

Must set the flags before jax initializes its backends (first jax import in
the process), so this conftest is the import gate for every test.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# the config-level spelling too: tests always run on the 8-device
# virtual CPU mesh, whatever the environment offers
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture
def pvar_clean():
    from ompi_tpu.core import pvar

    pvar.reset()
    yield
    pvar.reset()


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described (not attached) v5e host, as a sharding:
    the TPU compiler is installed here and compiles for it. Asked for by
    name, never autouse, and the topology is described only once a test
    that wants it runs (not at import): the process that does holds the
    TPU library from then on."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def kernels_on_cpu(monkeypatch):
    """ops/kda.py's core as on a TPU — the rule `carry_tile` answering
    with heads a grid step, the Pallas kernels in interpret mode —:
    everything else is the program's own path."""
    import functools

    from ompi_tpu.ops import kda

    monkeypatch.setattr(kda, "carry_tile",
                        lambda backend, t, heads, *a: next(
                            n for n in (2, 1) if heads % n == 0))
    monkeypatch.setattr(kda, "kernel_delta", functools.partial(
        kda.kernel_delta, interpret=True))
