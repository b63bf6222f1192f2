"""A later PR adds a cell as two new JSON files and entries of the
manifest: it runs, and no file that existed is edited."""

import hashlib
import json
import os
import shutil

from conftest import HERE, ROOT
from test_rehearsal import MARK, rehearse


def digest(top):
    out = {}
    for folder, _, names in os.walk(top):
        if "__pycache__" in folder:
            continue
        for name in names:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, top)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def test_new_cell_is_two_files_and_manifest_entries(tmp_path):
    shutil.copytree(HERE, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = digest(tmp_path / "benchmarks")

    with open(os.path.join(HERE, "configs", "kmeans_hibench.json")) as f:
        config = json.load(f)
    config.update(name="kmeans_hibench_k4", k=4)
    config["reference_params"] = {**config["reference_params"], "k": 4}
    with open(tmp_path / "benchmarks/configs/kmeans_hibench_k4.json", "w") as f:
        json.dump(config, f)
    with open(os.path.join(HERE, "workloads",
                           "kmeans_hibench.fit.json")) as f:
        workload = json.load(f)
    workload.update(name="kmeans_hibench_k4.fit", config="kmeans_hibench_k4")
    with open(tmp_path / "benchmarks/workloads/kmeans_hibench_k4.fit.json",
              "w") as f:
        json.dump(workload, f)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "kmeans_hibench_k4", "source": config["source"],
        "file": "benchmarks/configs/kmeans_hibench_k4.json",
        "reduced": ["rows"], "why": "a test's configuration"})
    manifest["workloads"].append({
        "name": "kmeans_hibench_k4.fit", "config": "kmeans_hibench_k4",
        "traffic": "fit", "chips": 1, "why": "a test's cell"})
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(manifest, f)

    run = rehearse("kmeans_hibench_k4.fit", 0, cwd=str(tmp_path),
                   env={"PYTHONPATH": ROOT})
    assert run.returncode == 2, run.stderr[-2000:]
    assert MARK + "correct: True" in run.stderr
    after = digest(tmp_path / "benchmarks")
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        "configs/kmeans_hibench_k4.json", "workloads/kmeans_hibench_k4.fit.json"]
