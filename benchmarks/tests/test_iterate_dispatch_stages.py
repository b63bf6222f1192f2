"""The six readers of ``iterate.dispatch``'s stages (PR 37) on a recorded
trace: ``data/trace_program_scopes_dispatch.json`` is
``trace_program_scopes.json`` with the five stage spans written into each
fit's ``iterate.dispatch`` (and into the warm-up's, which lies before the
first mark and must not count).  The numbers asserted are the ones
written into the file.  On a trace whose dispatch is one span (the
parent commit's) every one of the six reads as nothing, not 0."""

import os

import pytest

from harness import files
from harness import program_scopes as sc
from harness import trace_reduce as tr
from metrics import (iterate_cache_hit, iterate_dispatch_compile_s,
                     iterate_dispatch_enqueue_s, iterate_dispatch_lower_s,
                     iterate_dispatch_probe_s, iterate_dispatch_trace_s)

HERE = os.path.dirname(os.path.abspath(__file__))
STAGES = {"iterate_dispatch_probe_s": iterate_dispatch_probe_s,
          "iterate_dispatch_trace_s": iterate_dispatch_trace_s,
          "iterate_dispatch_lower_s": iterate_dispatch_lower_s,
          "iterate_dispatch_compile_s": iterate_dispatch_compile_s,
          "iterate_dispatch_enqueue_s": iterate_dispatch_enqueue_s}
READERS = {**STAGES, "iterate_cache_hit": iterate_cache_hit}
# the parent's traces: the benchmark's marks alone; fits whose dispatch is
# one span (another estimator's, Wide&Deep's, KMeans' on the chip)
WITHOUT = ("trace_small.json", "trace_program_spans.json",
           "trace_program_scopes.json",
           "trace_program_spans_kmeans_hibench.fit.json")


def fits_of(name):
    return tuple(sc.fits(tr.load_json(os.path.join(HERE, "data", name))))


def ctx_over(monkeypatch, fits):
    monkeypatch.setattr(sc, "fits_of_cell", lambda cell: fits)
    return {"cell": "any.fit"}


def test_the_manifest_lists_the_six_for_every_cell():
    listed = {m["name"]: m for m in files.manifest()["per_layer"]}
    for name in READERS:
        assert "workloads" not in listed[name], name
        assert listed[name]["moves"] == "train_rows_per_s"
    assert {listed[n]["layer"] for n in READERS} == {"iteration and step",
                                                     "compile"}
    assert listed["iterate_dispatch_compile_s"]["layer"] == "compile"
    assert listed["iterate_cache_hit"]["source"] == "program_counter"
    assert {listed[n]["unit"] for n in STAGES} == {"s"}


@pytest.mark.parametrize("name, seconds", [
    ("iterate_dispatch_probe_s", (60 + 80) / 2 * 1e-9),
    ("iterate_dispatch_trace_s", (70 + 70) / 2 * 1e-9),
    ("iterate_dispatch_lower_s", (150 + 130) / 2 * 1e-9),
    ("iterate_dispatch_compile_s", (90 + 100) / 2 * 1e-9),
    ("iterate_dispatch_enqueue_s", (20 + 10) / 2 * 1e-9)])
def test_a_stage_reads_the_mean_of_its_span_over_the_windows_fits(
        name, seconds, monkeypatch):
    ctx = ctx_over(monkeypatch,
                   fits_of("trace_program_scopes_dispatch.json"))
    assert STAGES[name].read(ctx) == pytest.approx(seconds)


def test_the_cache_hit_is_the_share_of_the_windows_fits_that_were_served(
        monkeypatch):
    fits = fits_of("trace_program_scopes_dispatch.json")
    # the warm-up's cold compile lies before the first mark: not a fit of
    # the window
    assert [r["start_ns"] for r in fits] == [1100, 12100]
    assert [r["notes"]["iterate.dispatch.compile"]["cache_hit"]
            for r in fits] == [1, 0]
    assert iterate_cache_hit.read(ctx_over(monkeypatch, fits)) == 0.5
    assert iterate_cache_hit.read(ctx_over(monkeypatch, fits[:1])) == 1.0


def test_the_stages_lie_in_order_inside_the_dispatch_and_leave_its_rest():
    raw = tr.load_json(os.path.join(
        HERE, "data", "trace_program_scopes_dispatch.json"))
    events = raw["planes"][0]["lines"][0]["events"]
    for fit, rest_ns in ((2, 10), (3, 10)):
        (whole,) = [e for e in events if e[0] == "iterate.dispatch"
                    and e[3]["fit"] == fit]
        parts = [e for e in events if e[0].startswith("iterate.dispatch.")
                 and e[3]["fit"] == fit]
        assert [e[0].rsplit(".", 1)[1] for e in parts] == [
            "probe", "trace", "lower", "compile", "enqueue"]
        assert all(a[1] + a[2] <= b[1] for a, b in zip(parts, parts[1:]))
        assert whole[1] <= parts[0][1]
        assert parts[-1][1] + parts[-1][2] <= whole[1] + whole[2]
        assert whole[2] - sum(e[2] for e in parts) == rest_ns
    # the one span the accepted reader takes is as it was
    a, b = sc.fits(raw)
    assert a["span_s"]["iterate.dispatch"] == pytest.approx(400e-9)
    assert b["span_s"]["iterate.dispatch"] == pytest.approx(400e-9)


@pytest.mark.parametrize("name", sorted(READERS))
@pytest.mark.parametrize("trace", WITHOUT)
def test_a_dispatch_of_one_span_reads_as_nothing(name, trace, monkeypatch):
    assert READERS[name].read(ctx_over(monkeypatch, fits_of(trace))) is None


def test_the_accepted_readers_read_the_extended_trace_as_the_original():
    """What this PR adds to a trace moves no accepted number: every span
    and scope of ``trace_program_scopes.json`` reads the same with the
    stages beside it."""
    before = fits_of("trace_program_scopes.json")
    after = fits_of("trace_program_scopes_dispatch.json")
    assert len(before) == len(after) == 2
    for b, a in zip(before, after):
        assert {k: v for k, v in a["span_s"].items()
                if not k.startswith("iterate.dispatch.")} == b["span_s"]
        assert a["scope_ns"] == b["scope_ns"]
        assert a["program_ns"] == b["program_ns"]
        assert {k: v for k, v in a["notes"].items()
                if not k.startswith("iterate.dispatch.")} == b["notes"]


def test_a_traced_rehearsal_reports_the_six_and_they_add_up(tmp_path):
    """On the CPU the spans are there (no chip needed): the five stages
    add up to ``iterate_dispatch_s`` but for the span's own bookkeeping,
    and the fits of the window are served by the cache the warm-up
    filled."""
    import json

    from test_rehearsal import MARK, rehearse

    run = rehearse("kmeans_hibench.fit", 1,
                   env={"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert run.returncode == 2, run.stderr[-2000:]
    (line,) = [l[len(MARK):] for l in run.stderr.splitlines()
               if l.startswith(MARK + '{"correct"')]
    metrics = json.loads(line)["metrics"]
    assert set(READERS) <= set(metrics)
    parts = sum(metrics[n]["value"] for n in STAGES)
    whole = metrics["iterate_dispatch_s"]["value"]
    assert parts <= whole
    assert parts == pytest.approx(whole, rel=0.1, abs=0.005)
    assert metrics["iterate_cache_hit"]["value"] == 1.0
    assert metrics["compiles_in_window"]["value"] == 0.0
