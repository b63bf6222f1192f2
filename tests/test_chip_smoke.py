"""The entry points refuse to look fine without the chip.

``chip_smoke.py`` and ``bench.py`` take the backend JAX gives them and
fail when it is not a TPU — before any model code runs, with no CPU
fallback — and the compile-cache helper puts the cache where it can be
found again.  (The legs themselves only mean something on the chip; the
driver and ``chiprun`` run them there.)
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


def test_bench_without_tpu_needs_an_explicit_cpu(tmp_path):
    """JAX falls back to the CPU by itself when it finds no accelerator;
    the bench must not follow it unless JAX_PLATFORMS says cpu."""
    import bench

    fake_cpu = {"platform": "cpu", "kind": "cpu", "count": 1}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(bench, "_device_line", lambda: fake_cpu)
        mp.delenv("JAX_PLATFORMS", raising=False)
        assert bench.main() == 1
        mp.setenv("JAX_PLATFORMS", "")
        assert bench.main() == 1


def _stub_legs(mp, bench, raising=()):
    def leg(name):
        def run(results):
            if name in raising:
                raise RuntimeError(f"{name} broke")
            results.setdefault("logreg_epochs_per_sec", 1.0)
            results.setdefault("vs_baseline", 1.0)
        run.__name__ = name
        return run

    for name in [n for n in vars(bench) if n.startswith("bench_")]:
        mp.setattr(bench, name, leg(name))
    mp.setenv("JAX_PLATFORMS", "cpu")


def test_a_raising_bench_leg_makes_the_exit_code_nonzero(capsys):
    import json

    import bench
    from flink_ml_tpu.utils import backend

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(backend, "enable_compile_cache", lambda: "unused")
        _stub_legs(mp, bench)
        assert bench.main() == 0
        ok_summary = json.loads(capsys.readouterr().out.splitlines()[-1])
        assert ok_summary["failed_legs"] == []
        assert ok_summary["device"]["platform"] == "cpu"

        _stub_legs(mp, bench, raising={"bench_kmeans"})
        assert bench.main() == 1
        lines = capsys.readouterr().out.splitlines()
        assert json.loads(lines[-1])["failed_legs"] == ["bench_kmeans"]
        # the other legs still ran and the full line still parses
        full = json.loads(lines[-2])
        assert "bench_kmeans_error" in full["notes"]
        assert full["value"] == 1.0
        assert "cpu_rehearsal" in full["notes"]


def test_mfu_only_for_a_chip_in_the_peaks_table(monkeypatch):
    import bench

    line = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(bench, "_device_line", lambda: line)
    assert bench._mfu(19.7e12, 4) == 0.1
    line["kind"] = "TPU v9 imaginary"
    with pytest.raises(KeyError, match="no published peak"):
        bench._mfu(1e12, 4)
    line.update(platform="cpu", kind="cpu")
    assert bench._mfu(1e12, 4) is None


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
