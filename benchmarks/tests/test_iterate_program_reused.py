"""The reader of ``iterate_program_reused`` (PR 38) on a recorded trace:
``data/trace_program_scopes_dispatch.json`` with the note ``reused``
written beside ``cache_hit`` on each fit's ``iterate.dispatch.compile``,
as a program that keeps its fused programs notes it.  On the recorded
traces as they are (no such note: PR 37's program and its parents) it
reads as nothing, not 0."""

import copy
import os

import pytest

from harness import files
from harness import program_scopes as sc
from harness import trace_reduce as tr
from metrics import iterate_cache_hit, iterate_program_reused

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = ("trace_small.json", "trace_program_spans.json",
            "trace_program_scopes.json", "trace_program_scopes_dispatch.json",
            "trace_program_spans_kmeans_hibench.fit.json")


def load(name):
    return tr.load_json(os.path.join(HERE, "data", name))


def noting(reused_by_fit):
    """The dispatch trace's fits, every compile stage noting ``reused``
    (and, where the process's own entry answered, ``cache_hit`` 1) by its
    fit id."""
    raw = copy.deepcopy(load("trace_program_scopes_dispatch.json"))
    for plane in raw["planes"]:
        for line in plane["lines"]:
            for name, _, _, stats in line["events"]:
                if name == "iterate.dispatch.compile":
                    stats["reused"] = reused_by_fit[stats["fit"]]
                    stats["cache_hit"] = max(stats["cache_hit"],
                                             stats["reused"])
    return tuple(sc.fits(raw))


def ctx_over(monkeypatch, fits):
    monkeypatch.setattr(sc, "fits_of_cell", lambda cell: fits)
    return {"cell": "any.fit"}


def test_the_manifest_lists_it_for_every_cell_last():
    entry = files.manifest()["per_layer"][-1]
    assert entry == {"name": "iterate_program_reused", "unit": "count",
                     "better": "higher", "source": "program_counter",
                     "layer": "iteration and step",
                     "moves": "train_rows_per_s"}


@pytest.mark.parametrize("reused, share, hit", [
    ({1: 0, 2: 1, 3: 1}, 1.0, 1.0),      # the warm-up built it: not counted
    ({1: 0, 2: 1, 3: 0}, 0.5, 0.5),
    ({1: 1, 2: 0, 3: 0}, 0.0, 0.5)])
def test_it_reads_the_share_of_the_windows_fits_that_reused(
        reused, share, hit, monkeypatch):
    fits = noting(reused)
    # the warm-up's fit lies before the first mark
    assert [r["start_ns"] for r in fits] == [1100, 12100]
    ctx = ctx_over(monkeypatch, fits)
    assert iterate_program_reused.read(ctx) == share
    # a reuse is a fit XLA compiled nothing for
    assert iterate_cache_hit.read(ctx) == hit


@pytest.mark.parametrize("trace", RECORDED)
def test_a_program_that_notes_no_reuse_reads_as_nothing(trace, monkeypatch):
    fits = tuple(sc.fits(load(trace)))
    assert iterate_program_reused.read(ctx_over(monkeypatch, fits)) is None
