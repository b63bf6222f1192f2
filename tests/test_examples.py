"""Examples must keep running — they are the migration surface a reference
user reads first, and nothing else executes them.

Each example is a self-contained script (it inserts the repo root into
``sys.path`` itself) run here as a subprocess on the virtual 8-device CPU
mesh.  The fast ones run in the default suite; the slow ones (real
training work, covered functionally by unit tests of the same surfaces)
run only with ``FLINK_ML_TPU_RUN_SLOW_EXAMPLES=1``.
"""

import os
import subprocess
import sys

import pytest

_EXAMPLES_DIR = os.path.join(os.path.dirname(__file__), "..", "examples")

# on the CPU mesh: fast <= ~12s each
_FAST = [
    "kmeans_example.py",
    "pipeline_example.py",
    "pod_sharded_lr_example.py",
    "streaming_ftrl_example.py",
    "text_pipeline_example.py",
    "criteo_e2e_pipeline_example.py",
]
_SLOW = [
    "als_example.py",
    "criteo_mixed_lr_example.py",
    "distributed_example.py",
    "graph_example.py",
    "iteration_example.py",
    "model_selection_example.py",
    "recommender_example.py",
    "widedeep_ctr_example.py",     # ~20s: 12 streamed epochs
]

_RUN_SLOW = os.environ.get("FLINK_ML_TPU_RUN_SLOW_EXAMPLES") == "1"


def _run(name: str) -> None:
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        flags = (flags + " --xla_force_host_platform_device_count=8").strip()
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=flags)
    proc = subprocess.run(
        [sys.executable, os.path.join(_EXAMPLES_DIR, name)],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, (
        f"{name} failed (rc={proc.returncode}):\n"
        f"--- stdout ---\n{proc.stdout[-2000:]}\n"
        f"--- stderr ---\n{proc.stderr[-2000:]}")


def test_example_inventory_complete():
    """Every example on disk is classified — a new example that is not
    added to _FAST or _SLOW fails here instead of silently rotting."""
    on_disk = sorted(f for f in os.listdir(_EXAMPLES_DIR)
                     if f.endswith(".py"))
    assert on_disk == sorted(_FAST + _SLOW)


@pytest.mark.parametrize("name", _FAST)
def test_fast_example(name):
    _run(name)


@pytest.mark.parametrize("name", _SLOW)
@pytest.mark.skipif(not _RUN_SLOW,
                    reason="slow example; set "
                           "FLINK_ML_TPU_RUN_SLOW_EXAMPLES=1")
def test_slow_example(name):
    _run(name)
