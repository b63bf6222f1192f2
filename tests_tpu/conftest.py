"""TPU-parity tier — deliberately OUTSIDE tests/ so the unit suite's
conftest (which pins the virtual CPU mesh) never applies.  Run it on the
chip, one process:

    python -m pytest tests_tpu -m tpu -q

Every test here compiles a Mosaic kernel and parity-checks it against its
XLA twin, so a compiler refusal names one kernel instead of failing a
whole fit.  Without a TPU the tier FAILS: zero tests run is not a pass.
"""

import numpy as np
import pytest


@pytest.fixture(scope="session")
def tpu():
    import jax

    from flink_ml_tpu.utils.backend import enable_compile_cache

    device = jax.devices()[0]
    if device.platform != "tpu":
        pytest.fail(f"the tests_tpu tier needs a TPU; JAX found "
                    f"platform={device.platform!r} ({device.device_kind})",
                    pytrace=False)
    enable_compile_cache()
    return device


@pytest.fixture
def rng():
    return np.random.default_rng(7)
