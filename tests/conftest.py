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


@pytest.fixture(autouse=True)
def _no_kept_programs():
    """Every test starts with no fused program kept by ``iterate``
    (``iteration/core.py: clear_programs``): whether a fit builds or
    reuses then follows from the test alone, not from the tests the
    worker ran before it."""
    from flink_ml_tpu.iteration import clear_programs

    clear_programs()


@pytest.fixture
def fit_noting_reuse():
    """``fit(est, table) -> (model, reused)``: ``reused`` is what the
    fit's dispatch noted on ``iterate.dispatch.compile``, 1 where
    ``iterate`` enqueued a program this process had kept."""
    from flink_ml_tpu.obs.trace import tracer

    def fit(est, table):
        tracer.enable()
        try:
            model = est.fit(table)
            (span,) = tracer.find("iterate.dispatch.compile")
        finally:
            tracer.disable()
            tracer.clear()
        return model, span.ids["reused"]

    return fit
