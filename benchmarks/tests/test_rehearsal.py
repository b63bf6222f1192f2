"""A CPU rehearsal of every cell that has a workload file (listed in
BENCHMARK.json or not yet) at tiny sizes: every line marked, no result
line, exit code 2; and an empty directory is an error."""

import os
import shutil
import subprocess
import sys

import pytest

from conftest import HERE, ROOT

MARK = "[cpu-rehearsal, not a device number] "
CELLS = sorted(name[:-len(".json")]
               for name in os.listdir(os.path.join(HERE, "workloads")))


def rehearse(cell, trace, cwd=ROOT, env=None):
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmarks", "run.py"),
         "--workload", cell, "--seed", "2147483999", "--seconds", "1",
         "--trace", str(trace)],
        cwd=cwd, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})})


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_is_marked_and_prints_no_result(cell, trace):
    run = rehearse(cell, trace)
    assert run.returncode == 2, run.stderr[-2000:]
    assert run.stdout.strip() == ""
    ours = [l for l in run.stderr.splitlines() if "compared" in l
            or "correct" in l or l.startswith(MARK)]
    assert ours and all(l.startswith(MARK) for l in ours)
    assert MARK + "correct: True" in run.stderr


def test_bare_directory_is_an_error(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: no program, so
    a non-zero exit and no result line."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    run = rehearse(CELLS[0], 0, cwd=str(tmp_path), env={"PYTHONPATH": ""})
    assert run.returncode not in (0, 2)
    assert run.stdout.strip() == ""
