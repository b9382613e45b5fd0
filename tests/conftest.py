"""Test harness: force an 8-device virtual CPU mesh so multi-chip sharding
paths (parallel/) are exercised without TPU hardware.

Must set XLA flags before jax initialises any backend, hence module-level
os.environ mutation in conftest (imported before any test module).
"""

import os

# force-override whatever the environment presets: tests run on the
# virtual CPU mesh for speed and sharding coverage. The chip is reached
# only by `python chip_smoke.py` through the chip tool (README.md).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

# the jaxtyping pytest plugin imports jax before this conftest runs, so the
# env var alone is too late — update the live config (backend not yet
# initialised during collection, so this still takes effect)
jax.config.update("jax_platforms", "cpu")


@pytest.fixture
def rng():
    return np.random.default_rng(1234)
