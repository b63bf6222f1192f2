"""Everything the harness finds by name.  A later PR adds files and one
manifest entry; nothing here names a cell, a configuration or a metric."""

from __future__ import annotations

import functools
import importlib
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(name: str, rehearsal: bool = False) -> tuple:
    """``(workload file, configuration)`` of a cell; for a rehearsal the
    workload's ``rehearsal`` sizes are laid over the configuration's.  A
    cell is its two files: one that ``BENCHMARK.json`` does not list yet
    runs all the same, so that a PR can try it before it adds the entry."""
    try:
        workload = load_json("workloads", name + ".json")
    except FileNotFoundError:
        raise SystemExit(f"no benchmarks/workloads/{name}.json; BENCHMARK."
                         f"json lists {sorted(listed_cells())}")
    config = load_json("configs", workload["config"] + ".json")
    if rehearsal:
        config = overlaid(config, workload.get("rehearsal", {}))
    return workload, config


def listed_cells() -> set:
    return {w["name"] for w in manifest()["workloads"]}


def overlaid(config: dict, changes: dict) -> dict:
    """``config`` with ``changes`` laid over it; a group (a dict under a
    top-level key) keeps the keys the change does not name."""
    out = dict(config)
    for key, value in changes.items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            value = {**out[key], **value}
        out[key] = value
    return out


def generate(config: dict, seed: int) -> dict:
    """The cell's data from the seed: the configuration's generator, given
    the configuration's top-level keys and its ``generator_params``."""
    generator = module("generators", config["generator"])
    return generator.generate({**config, **config["generator_params"]}, seed)


def module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py``, imported by name."""
    return importlib.import_module(f"{kind}.{name}")


def peaks(device_kind: str) -> dict:
    table = load_json("harness", "peaks.json")["by_device_kind"]
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         "benchmarks/harness/peaks.json: add it with its "
                         "source, do not guess")
    return table[device_kind]
