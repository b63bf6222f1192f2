"""Tier-1 runs the benchmark's own path: every cell that has a workload
file under ``benchmarks/workloads/`` is rehearsed on the CPU at the cell's
``rehearsal`` sizes through ``benchmarks/run.py``, the command the driver
measures with, untraced and traced.  A PR that renames a span the readers
take, or breaks a cell's comparison with its reference, fails here and not
on the chip.  Nothing a rehearsal prints is a device number.
"""

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_MARK = "[cpu-rehearsal, not a device number] "
_CELLS = sorted(name[:-len(".json")] for name in os.listdir(
    os.path.join(_REPO, "benchmarks", "workloads")))
# what run.py and the program reach from the root of a checkout
_CHECKOUT = ("BENCHMARK.json", "benchmarks", "flink_ml_tpu", "native")
# seconds that are the device's busy time inside a span: a trace taken on
# the CPU has no device plane, and their readers then give None
_NEED_A_CHIP = {"host_only_s", "fit_upload_s", "fit_fetch_s"}


def _per_layer(cell):
    with open(os.path.join(_REPO, "BENCHMARK.json")) as f:
        return [m["name"] for m in json.load(f)["per_layer"]
                if cell in m.get("workloads", [cell])]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", _CELLS)
def test_cell_rehearses_correct(cell, trace, tmp_path):
    # run.py keeps its trace under the root it finds itself in: a view of
    # the checkout under tmp_path keeps the trace and the cache out of it
    for name in _CHECKOUT:
        os.symlink(os.path.join(_REPO, name), tmp_path / name)
    run = subprocess.run(
        [sys.executable, str(tmp_path / "benchmarks" / "run.py"),
         "--workload", cell, "--seed", "2147483999", "--seconds", "1",
         "--trace", str(trace)],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")})
    assert run.returncode == 2, run.stderr[-2000:]
    assert run.stdout.strip() == ""
    ours = [line for line in run.stderr.splitlines()
            if "compared" in line or "correct" in line
            or line.startswith(_MARK)]
    assert ours and all(line.startswith(_MARK) for line in ours)
    assert _MARK + "correct: True" in run.stderr, run.stderr[-2000:]

    (result,) = [json.loads(line[len(_MARK):]) for line in ours
                 if line.startswith(_MARK + "{")]
    assert result["failed"] == 0 and result["attempted"] >= 1
    if trace:
        # the readers of the program's spans found them: every per-layer
        # metric that is the length of a host span is there with a value
        spans = [m for m in _per_layer(cell)
                 if m.endswith("_s") and m not in _NEED_A_CHIP]
        missing = [m for m in spans if m not in result["metrics"]]
        assert spans and not missing, (missing, run.stderr[-2000:])
        assert (tmp_path / ".bench_trace" / cell).is_dir()
    else:
        assert set(result["metrics"]) == {"train_rows_per_s", "setup_s"}
