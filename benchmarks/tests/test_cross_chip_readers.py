"""The three readers PR 39 adds, on hand-made data: the all-reduce's scope
on a trace of four chips (the fullest chip's operations are read), how far
the mean chip lags the fullest, the ``shards`` note; ``None`` from each
where the program has no such scope, trace or note (the parent commit's,
another estimator's)."""

import pytest

from harness import files
from harness import program_scopes as sc
from harness import trace_reduce as tr
from metrics import (chip_busy_skew_pct, fit_shards, kmeans_reduce_ms,
                     kmeans_stats_ms, kmeans_unscoped_ms, kmeans_update_ms)

CELL = "kmeans_mnist8m_full.fit"
MS = 1e6


def _op(name, start, dur, scope=None):
    stats = {"tf_op": f"jit(run)/while/body/closed_call/{scope}/x:"} \
        if scope else {}
    return [name, start * MS, dur * MS, stats]


def _chip(n, slow=0.0):
    """One fit's fused program of two steps on chip ``n``: a step is the
    kernel (10 ms, ``slow`` more on this chip), the all-reduce (1 ms, and
    the wait for the slowest chip), the update (0.5 ms), a loop op."""
    ops, at = [], 100.0
    for _ in range(2):
        ops.append(_op("kmeans_update_stats.5 custom-call", at, 10 + slow,
                       "kmeans.stats"))
        ops.append(_op("all-reduce.3 all-reduce", at + 10 + slow, 2 - slow,
                       "kmeans.stats/shard_map/kmeans.reduce"))
        ops.append(_op("divide_select_fusion fusion", at + 12, 0.5,
                       "kmeans.update"))
        ops.append(_op("while.2 while", at + 12.5, 0.25))
        at += 12.75
    return {"name": f"/device:TPU:{n}", "lines": [
        {"name": tr.OPS_LINE, "events": ops},
        {"name": tr.MODULES_LINE,
         "events": [["jit_run(7)", 100.0 * MS, 25.5 * MS, {}]]}]}


def _trace(chips, notes=True):
    host = [["fit.call", 90 * MS, 50 * MS, {}],
            ["between_fits", 140 * MS, 1 * MS, {}],
            ["fit", 91 * MS, 48 * MS, {"fit": 1}],
            ["fit.arrange", 92 * MS, 1 * MS,
             {"shards": len(chips), "stats_plan": "k_tiled"} if notes
             else {}]]
    return {"planes": chips + [{"name": "/host:CPU", "lines": [
        {"name": "python", "events": host}]}]}


@pytest.fixture
def ctx(monkeypatch):
    def of(trace, chips=4):
        fits = tuple(sc.fits(trace))
        monkeypatch.setattr(sc, "fits_of_cell", lambda cell: fits)
        workload, config = files.cell(CELL)
        return {"cell": CELL, "peaks": None, "calls": [(0, 1, 2)],
                "workload": {**workload, "chips": chips},
                "config": {**config, "steps_per_pass": 1},
                "trace": tr.reduce(trace)}
    return of


def test_the_manifest_lists_the_three_and_the_cell_where_the_kernel_is_read():
    listed = {m["name"]: m for m in files.manifest()["per_layer"]}
    assert listed["kmeans_reduce_ms"]["workloads"] == [CELL]
    assert listed["kmeans_reduce_ms"]["layer"] == "collectives"
    assert listed["chip_busy_skew_pct"]["workloads"] == [CELL]
    assert listed["fit_shards"]["workloads"] == [
        "kmeans_mnist8m.fit", "kmeans_hibench.fit", CELL]
    for name in ("kmeans_stats_ms", "kmeans_update_ms", "kmeans_unscoped_ms",
                 "kmeans_stats_mfu_pct", "kmeans_mxu_pad_pct"):
        assert listed[name]["workloads"][-1] == CELL, name
    assert [w for w in files.manifest()["workloads"] if w["chips"] == 4] == [
        w for w in files.manifest()["workloads"] if w["name"] == CELL]


def test_four_chips_the_scopes_add_up_and_the_reduce_is_its_own(ctx):
    """Chips 0-2 wait a millisecond in the all-reduce for chip 3, whose
    kernel is slower; all four are as busy, the first of them is read, and
    its ``kmeans.reduce`` holds the wait: the innermost scope claims an
    operation, so the kernel's scope does not count the all-reduce."""
    c = ctx(_trace([_chip(0), _chip(1), _chip(2), _chip(3, slow=1.0)]))
    assert c["trace"]["device_plane"] == "/device:TPU:0"
    stats, reduce = kmeans_stats_ms.read(c), kmeans_reduce_ms.read(c)
    update, rest = kmeans_update_ms.read(c), kmeans_unscoped_ms.read(c)
    assert stats == pytest.approx(10.0) and reduce == pytest.approx(2.0)
    assert update == pytest.approx(0.5) and rest == pytest.approx(0.25)
    assert stats + reduce + update + rest == pytest.approx(12.75)
    assert fit_shards.read(c) == 4
    assert chip_busy_skew_pct.read(c) == pytest.approx(0.0)


def test_skew_is_the_mean_chips_lag_behind_the_fullest(ctx):
    idle = _chip(1)
    idle["lines"][0]["events"] = idle["lines"][0]["events"][:4]  # one step
    c = ctx(_trace([_chip(0), idle]))
    # busy 25.5 and 12.75 ms: the mean is 75% of the fullest
    assert chip_busy_skew_pct.read(c) == pytest.approx(25.0)


def test_one_chip_reads_no_reduce_no_skew_and_one_shard(ctx):
    chip = _chip(0)
    for line in chip["lines"]:
        line["events"] = [e for e in line["events"]
                          if "all-reduce" not in e[0]]
    trace = _trace([chip])
    c = ctx(trace, chips=1)
    assert kmeans_reduce_ms.read(c) is None
    assert chip_busy_skew_pct.read(c) is None
    assert fit_shards.read(c) == 1


def test_a_program_without_the_note_or_a_run_without_a_trace_reads_none(ctx):
    c = ctx(_trace([_chip(0)], notes=False))
    assert fit_shards.read(c) is None
    c["trace"] = None
    assert chip_busy_skew_pct.read(c) is None
