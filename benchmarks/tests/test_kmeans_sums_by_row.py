"""``kmeans_sums_by_row`` on hand-made traces: the ``sums`` note that
``KMeans.fit`` puts on ``fit.arrange`` read as 1 (``"by_row"``) or 0
(``"onehot"``), and ``None`` where a fit took the XLA body or the program
notes no such counter (the parent commit's)."""

import pytest

from harness import program_scopes as sc
from harness import trace_reduce as tr
from metrics import kmeans_sums_by_row

MS = 1e6


def _trace(*arranges):
    host = [["fit.call", 90 * MS, 50 * MS, {}]]
    for i, notes in enumerate(arranges):
        host += [["fit", (91 + 10 * i) * MS, 8 * MS, {"fit": i + 1}],
                 ["fit.arrange", (92 + 10 * i) * MS, 1 * MS, notes]]
    chip = {"name": "/device:TPU:0", "lines": [
        {"name": tr.OPS_LINE, "events": [
            ["kmeans_update_stats.5 custom-call", 95 * MS, 2 * MS, {}]]},
        {"name": tr.MODULES_LINE,
         "events": [["jit_run(7)", 95 * MS, 2 * MS, {}]]}]}
    return {"planes": [chip, {"name": "/host:CPU", "lines": [
        {"name": "python", "events": host}]}]}


@pytest.fixture
def read(monkeypatch):
    def of(trace):
        fits = tuple(sc.fits(trace))
        monkeypatch.setattr(sc, "fits_of_cell", lambda cell: fits)
        return kmeans_sums_by_row.read({"cell": "kmeans_mnist8m.fit"})
    return of


def test_a_fit_that_sums_by_row_reads_one_and_a_one_hot_zero(read):
    k_tiled = {"stats_plan": "k_tiled", "sums": "by_row"}
    feature_major = {"stats_plan": "feature_major", "sums": "onehot"}
    assert read(_trace(k_tiled, k_tiled)) == 1.0
    assert read(_trace(feature_major)) == 0.0


def test_the_xla_body_or_a_program_without_the_note_reads_none(read):
    assert read(_trace({"stats_plan": "xla"})) is None
    assert read(_trace({"stats_plan": "k_tiled"})) is None
    assert read(_trace({"stats_plan": "k_tiled", "sums": "by_row"},
                       {"stats_plan": "k_tiled"})) is None
