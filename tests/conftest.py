"""Test config: run JAX on a virtual 8-device CPU mesh.

Must set the env vars before jax is imported anywhere (mirrors the driver's
dryrun harness, which uses xla_force_host_platform_device_count to validate
multi-chip sharding without real chips).
"""

import os

# Tests validate semantics and multi-device sharding on a virtual 8-device
# CPU mesh, whatever the session's environment says about a chip.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# A pytest plugin may have imported jax before this file ran, freezing its
# snapshot of the environment: set the platform in the config as well.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from foundationdb_tpu.utils import enable_compilation_cache  # noqa: E402

enable_compilation_cache()  # cuts repeat suite runs by minutes

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)
