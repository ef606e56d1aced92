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
