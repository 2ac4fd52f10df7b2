"""Test harness: JAX on CPU with 8 virtual devices.

Mirrors the reference's test trick of simulating a cluster locally (a real Flask
parameter server + `local[2]` Spark, reference ``tests/dl_runner.py:26-40``): here
the *real* collective/sharding paths run on a virtual 8-device CPU mesh, so
multi-chip code is exercised without TPU hardware.

The platform is forced through the environment before JAX is imported, which
the installed JAX honours; the chip is reached through ``chip_smoke.py``, not
through this suite.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax

import numpy as np
import pytest


@pytest.fixture(scope="session")
def devices():
    return jax.devices()


@pytest.fixture(scope="session")
def dp_mesh():
    from jax.sharding import Mesh
    devs = np.array(jax.devices())
    return Mesh(devs.reshape(devs.size), ("dp",))


@pytest.fixture(scope="session")
def rng():
    return np.random.RandomState(12345)


@pytest.fixture(scope="session")
def sharded_attn_mesh():
    """2x4 {dp, tp} mesh for the sharded-jit attention tests."""
    import numpy as np
    from jax.sharding import Mesh

    import jax
    return Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("dp", "tp"))
