"""Test harness configuration.

The reference exercises multi-"node" behavior with an in-process Flink
MiniCluster (2 TM x 2 slots, ``UnboundedStreamIterationITCase.java:155-161``).
The TPU-native analog is a virtual 8-device CPU mesh: every sharding /
collective test runs real SPMD partitioning in one process.

The unit/IT suite always runs on the virtual CPU mesh, whatever the
environment says (the live-config update below overrides JAX_PLATFORMS);
real-TPU execution is exercised by chip_smoke.py and tests_tpu/.
"""

import os

# XLA_FLAGS is read lazily at CPU-client creation, so it is enough to set
# it before the first device use.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture
def cpu_mesh_8():
    """All 8 virtual CPU devices on one ``data`` axis (the MiniCluster
    analog)."""
    from flink_ml_tpu.parallel.mesh import device_mesh

    return device_mesh({"data": 8})
