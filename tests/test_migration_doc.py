"""MIGRATION.md is the reference user's entry point and README.md every
reader's: each `flink_ml_tpu...` path and each file they cite must keep
resolving, or the docs rot exactly where newcomers land first."""

import importlib
import os
import re

import pytest

_REPO = os.path.join(os.path.dirname(__file__), "..")
# the doc, and the fewest names of the package it cites today
_DOCS = {"MIGRATION.md": 15, "README.md": 15}

# dotted paths inside backticks, e.g. `flink_ml_tpu.api.stage.Stage` or
# `flink_ml_tpu.api.pipeline.Pipeline/PipelineModel`
_PATTERN = re.compile(r"`(flink_ml_tpu(?:\.\w+)+(?:/[\w.]+)*)`")
# what an example imports: `from flink_ml_tpu.serving import serve_model`,
# the names in brackets over several lines or not
_IMPORT = re.compile(
    r"^from (flink_ml_tpu[\w.]*) import (\([^)]*\)|[^\n(]+)$", re.M)
# files and directories inside backticks, e.g. `chip_smoke.py`,
# `serving/scheduler.py`, `PERF.md` or `tests_tpu/`, from the root of
# the checkout, of the package or of the examples
_FILE = re.compile(r"`([\w./-]*\w(?:\.(?:py|md|cpp|json|jsonl)|/))`")
_FILE_ROOTS = ("", "flink_ml_tpu", "examples")


def _cited_paths(text: str) -> list:
    cites = set(_PATTERN.findall(text))
    for module, names in _IMPORT.findall(text):
        cites.update(f"{module}.{name.strip()}"
                     for name in names.strip("()").split(",")
                     if name.strip())
    return sorted(cites)


def _resolve(path: str) -> None:
    parts = path.split(".")
    # walk the longest importable module prefix, then getattr the rest
    for split in range(len(parts), 0, -1):
        mod_name = ".".join(parts[:split])
        try:
            obj = importlib.import_module(mod_name)
        except ImportError:
            continue
        for attr in parts[split:]:
            obj = getattr(obj, attr)   # AttributeError = broken citation
        return
    raise ImportError(f"no importable prefix for {path}")


@pytest.mark.parametrize("doc", sorted(_DOCS))
def test_every_cited_path_resolves(doc):
    text = open(os.path.join(_REPO, doc)).read()
    cites = _cited_paths(text)
    assert len(cites) >= _DOCS[doc], f"{doc} lost its citations?"
    for cite in cites:
        # `a.b.C/D` cites several names under one module
        base, *alts = cite.split("/")
        _resolve(base)
        prefix = base.rsplit(".", 1)[0]
        for alt in alts:
            _resolve(f"{prefix}.{alt}" if "." not in alt else
                     f"{base.rsplit('.', 1)[0]}.{alt}")


@pytest.mark.parametrize("doc", sorted(_DOCS))
def test_every_cited_file_exists(doc):
    text = open(os.path.join(_REPO, doc)).read()
    missing = [cite for cite in sorted(set(_FILE.findall(text)))
               if not any(os.path.exists(os.path.join(_REPO, root, cite))
                          for root in _FILE_ROOTS)]
    assert not missing, f"{doc} cites files that are gone: {missing}"
