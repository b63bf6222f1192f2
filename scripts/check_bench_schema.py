#!/usr/bin/env python
"""Guard against silent bench-schema drift (ISSUE 3 satellite).

Two checks, both cheap enough for tier-1:

1. **Metric-version cross-check** — every ``*metric_version`` literal in
   ``bench.py`` must appear in BENCH_SCHEMA.md's "Metric versions" table
   with the SAME value, and vice versa.  This is exactly the failure mode
   of the r6/r7 bumps: the version moved in code, the contract doc
   lagged, and downstream parsers compared across incompatible series.

2. **Emitted-key validation** — given ``BENCH_*.json`` paths (raw bench
   stdout lines, or the driver's capture files whose ``parsed`` object
   holds the summary line), every top-level key must be documented in
   BENCH_SCHEMA.md (a backticked name), a ``*_error`` degradation key, or
   a summary-line field.

Run with no arguments for check 1 plus validation of every
``BENCH_*.json`` in the repo root; pass explicit JSON paths to validate
just those.  Exit code 0 = clean, 1 = drift (with a per-finding report).
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")
SCHEMA = os.path.join(REPO, "BENCH_SCHEMA.md")

#: summary-line fields (also the driver capture's `parsed` object) and
#: envelope keys of the driver capture files themselves
_SUMMARY_KEYS = {"metric", "value", "unit", "vs_baseline", "summary",
                 "backend", "device", "lr_impl", "failed_legs"}
_CAPTURE_ENVELOPE = {"n", "cmd", "rc", "tail", "parsed"}


def bench_metric_versions(src: str) -> dict:
    """Every ``<name>metric_version`` literal assigned in bench.py, from
    both the dict-literal and the subscript-assignment forms."""
    found = {}
    for pat in (r'"((?:\w+_)?metric_version)":\s*(\d+)',
                r'\["((?:\w+_)?metric_version)"\]\s*=\s*(\d+)'):
        for name, val in re.findall(pat, src):
            found[name] = int(val)
    return found


def schema_metric_versions(doc: str) -> dict:
    """The 'Metric versions' table: | `name` ... | value |"""
    section = doc.split("## Metric versions", 1)
    if len(section) < 2:
        return {}
    body = section[1].split("\n## ", 1)[0]
    found = {}
    for name, val in re.findall(r"\|\s*`(\w+)`[^|]*\|\s*(\d+)\s*\|", body):
        found[name] = int(val)
    return found


def schema_documented_keys(doc: str) -> set:
    """Every backticked identifier in BENCH_SCHEMA.md (the documented
    vocabulary; dotted names count for their leading segment too)."""
    keys = set()
    for name in re.findall(r"`([A-Za-z0-9_.*]+)`", doc):
        keys.add(name)
        keys.add(name.split(".", 1)[0])
    return keys


def check_versions() -> list:
    bench_v = bench_metric_versions(open(BENCH).read())
    schema_v = schema_metric_versions(open(SCHEMA).read())
    problems = []
    for name, val in sorted(bench_v.items()):
        if name not in schema_v:
            problems.append(
                f"bench.py emits {name}={val} but BENCH_SCHEMA.md's "
                "'Metric versions' table does not list it")
        elif schema_v[name] != val:
            problems.append(
                f"{name}: bench.py says {val}, BENCH_SCHEMA.md says "
                f"{schema_v[name]} — bump both together")
    for name in sorted(set(schema_v) - set(bench_v)):
        problems.append(
            f"BENCH_SCHEMA.md documents {name} but bench.py no longer "
            "emits it")
    return problems


def _validate_line(obj: dict, documented: set, origin: str) -> list:
    problems = []
    for key in obj:
        ok = (key in documented or key in _SUMMARY_KEYS
              or key == "notes" or key.endswith("_error"))
        if not ok:
            problems.append(
                f"{origin}: top-level key {key!r} is not documented in "
                "BENCH_SCHEMA.md")
    return problems


def check_json(path: str, documented: set) -> list:
    text = open(path).read().strip()
    try:
        whole = json.loads(text)
    except json.JSONDecodeError:
        whole = None
    if isinstance(whole, dict) and "parsed" in whole:
        # driver capture: envelope + truncated tail + parsed summary line
        # (parsed is null when the round produced no parseable line)
        problems = []
        for key in set(whole) - _CAPTURE_ENVELOPE:
            problems.append(
                f"{path}: unexpected capture-envelope key {key!r}")
        if isinstance(whole["parsed"], dict):
            problems += _validate_line(whole["parsed"], documented,
                                       f"{path}:parsed")
        return problems
    problems = []
    for i, line in enumerate(text.splitlines()):
        line = line.strip()
        if not line:
            continue
        try:
            obj = json.loads(line)
        except json.JSONDecodeError:
            problems.append(f"{path}:{i + 1}: not a JSON line")
            continue
        if isinstance(obj, dict):
            problems += _validate_line(obj, documented, f"{path}:{i + 1}")
    return problems


def main(argv) -> int:
    problems = check_versions()
    paths = argv or sorted(glob.glob(os.path.join(REPO, "BENCH_*.json")))
    documented = schema_documented_keys(open(SCHEMA).read())
    for path in paths:
        problems += check_json(path, documented)
    for p in problems:
        print(f"SCHEMA DRIFT: {p}")
    if not problems:
        print(f"bench schema clean ({len(paths)} json file(s) checked)")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
