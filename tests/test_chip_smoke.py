"""The entry points refuse to look fine without the chip.

``chip_smoke.py`` and ``benchmarks/run.py`` take the backend JAX gives
them and fail when it is not a TPU — before any model code runs, with no
CPU fallback — and the compile-cache helper puts the cache where it can
be found again.  (The legs and the cells themselves only mean something on
the chip; the driver and ``chiprun`` run them there.)
"""

import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, tmp_path, **env):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"), **env}
    return subprocess.run(
        [sys.executable, "-X", "importtime", os.path.join(_REPO, script)],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)


def test_chip_smoke_without_tpu_fails_before_any_model_code(tmp_path):
    proc = _run("chip_smoke.py", tmp_path)
    assert proc.returncode == 1, proc.stdout + proc.stderr[-2000:]
    assert "platform=cpu" in proc.stdout, proc.stdout
    assert "not a TPU" in proc.stdout
    assert '"ok"' not in proc.stdout                 # no result line
    # -X importtime lists every module imported: none of the package's
    assert "flink_ml_tpu" not in proc.stderr
    assert not (tmp_path / "cache").exists()         # and nothing compiled
    assert not (tmp_path / "chiprun_out").exists()


@pytest.fixture
def benchmark_run(monkeypatch):
    """``benchmarks/run.py`` as a module of this process, whose JAX the
    suite holds to the CPU: a machine that has no TPU."""
    import importlib.util

    from flink_ml_tpu.utils import backend

    monkeypatch.syspath_prepend(os.path.join(_REPO, "benchmarks"))
    monkeypatch.setattr(backend, "enable_compile_cache", lambda: "unused")
    spec = importlib.util.spec_from_file_location(
        "benchmarks_run", os.path.join(_REPO, "benchmarks", "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    return run


@pytest.mark.parametrize("platforms", [None, ""])
def test_benchmark_without_tpu_does_not_fall_back(
        benchmark_run, platforms, monkeypatch, capsys):
    """JAX falls back to the CPU by itself when it finds no accelerator;
    the benchmark must not follow it unless JAX_PLATFORMS says cpu."""
    if platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
    with pytest.raises(SystemExit) as exit_:
        benchmark_run.main(["--workload", "kmeans_hibench.fit",
                            "--seed", "1", "--seconds", "1"])
    assert exit_.value.code == 1
    out, err = capsys.readouterr()
    assert out == ""                                 # no result line
    assert "does not fall back" in err
    assert "cpu-rehearsal" not in err


def test_benchmark_refuses_an_unknown_chip_kind(benchmark_run):
    """A roofline share is a share of a published peak: a chip the table
    lacks is an error, never a default."""
    from harness import files

    assert files.peaks("TPU v5 lite") == {
        "flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    with pytest.raises(SystemExit, match="no peaks for device kind"):
        files.peaks("TPU v9 imaginary")


def test_compile_cache_helper_env_wins_else_fixed_checkout_path(monkeypatch):
    import jax

    from flink_ml_tpu.utils import backend

    before = jax.config.jax_compilation_cache_dir
    updates = []
    monkeypatch.setattr(
        jax.config, "update",
        lambda name, value: updates.append((name, value)))

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert backend.enable_compile_cache() == "/somewhere/else"
    assert "jax_compilation_cache_dir" not in dict(updates)

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    first, second = (backend.enable_compile_cache(),
                     backend.enable_compile_cache())
    assert first == second == os.path.join(_REPO, ".jax_cache")
    assert dict(updates)["jax_compilation_cache_dir"] == first
    assert jax.config.jax_compilation_cache_dir == before   # test left it


def test_compile_cache_dir_is_set_in_exactly_one_place():
    hits = []
    for root, dirs, files in os.walk(_REPO):
        dirs[:] = [d for d in dirs if not d.startswith((".", "_"))
                   and d not in ("chiprun_out", "tests")]
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(root, name)
                if "jax_compilation_cache_dir" in open(path).read():
                    hits.append(os.path.relpath(path, _REPO))
    assert hits == ["flink_ml_tpu/utils/backend.py"], hits


def test_count_compiles_sees_a_compile_on_another_thread():
    """The reason the helper exists: jax's own test counters are
    thread-local, and serving compiles on the serve thread."""
    import threading

    import jax
    import jax.numpy as jnp

    from flink_ml_tpu.utils.backend import count_compiles

    fn = jax.jit(lambda x: x * 3 + 1)
    x = jnp.arange(7.0)
    with count_compiles() as count:
        worker = threading.Thread(target=lambda: fn(x).block_until_ready())
        worker.start()
        worker.join(timeout=60)
        assert not worker.is_alive()
        assert count() == 1
        fn(x).block_until_ready()                    # cached: no compile
        assert count() == 1


def test_tpu_tier_without_tpu_fails_rather_than_skips(tmp_path):
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "tests_tpu", "-m", "tpu", "-q",
         "-p", "no:cacheprovider", "-x"],
        cwd=_REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0, proc.stdout[-2000:]
    assert "needs a TPU" in proc.stdout
    assert "skipped" not in proc.stdout.splitlines()[-1]
