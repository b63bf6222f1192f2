"""The reader of the program's own spans (``harness/program_spans.py``)
on a small hand-made trace: which fits are the window's, a phase in
pieces, what is left unattributed, the device's busy time inside a span
and the transfer's wait, the readers over it; ``None`` from every reader
on a trace without program spans; and a CPU rehearsal whose traced result
holds every reader that needs no chip."""

import json
import os

import pytest

from harness import files
from harness import program_spans as ps
from harness import trace_reduce as tr
from metrics import (fit_arrange_init_s, fit_arrange_pad_s, fit_arrange_s,
                     fit_fetch_s, fit_gather_cast_s, fit_gather_s,
                     fit_gather_stack_s, fit_unattributed_s, fit_upload_s,
                     iterate_dispatch_s)
from test_rehearsal import CELLS, MARK, rehearse

HERE = os.path.dirname(os.path.abspath(__file__))
# the six that partition the root span, then the parts of a KMeans fit
PARTITION = {
    "fit_gather_s": fit_gather_s, "fit_arrange_s": fit_arrange_s,
    "fit_upload_s": fit_upload_s, "iterate_dispatch_s": iterate_dispatch_s,
    "fit_fetch_s": fit_fetch_s, "fit_unattributed_s": fit_unattributed_s,
}
KMEANS_PARTS = {
    "fit_gather_stack_s": fit_gather_stack_s,
    "fit_gather_cast_s": fit_gather_cast_s,
    "fit_arrange_init_s": fit_arrange_init_s,
    "fit_arrange_pad_s": fit_arrange_pad_s,
}
READERS = {**PARTITION, **KMEANS_PARTS}
NEED_A_CHIP = {"fit_upload_s", "fit_fetch_s"}


def recorded(name):
    return tr.load_json(os.path.join(HERE, "data", name))


@pytest.fixture
def fits():
    return ps.fits(recorded("trace_program_spans.json"))


def test_the_manifest_lists_the_readers_and_the_parts_for_kmeans_only():
    listed = {m["name"]: m for m in files.manifest()["per_layer"]}
    assert set(READERS) <= set(listed)
    for name in PARTITION:
        assert "workloads" not in listed[name]
    for name in KMEANS_PARTS:
        assert listed[name]["workloads"] == ["kmeans_hibench.fit"]


def test_only_the_fits_inside_a_fit_call_mark_count(fits):
    # the warm-up's root lies before the first mark; a span with no root
    # around it (another thread's) belongs to no fit
    assert [r["start_ns"] for r in fits] == [1100, 11200]
    assert [r["root_s"] for r in fits] == pytest.approx([8800e-9, 9700e-9])


def test_a_span_counts_whole_and_a_phase_in_pieces_is_their_sum(fits):
    a, b = fits
    assert a["total_s"]["fit.gather"] == pytest.approx(3000e-9)
    assert a["total_s"]["fit.gather.stack"] == pytest.approx(1500e-9)
    assert b["total_s"]["fit.gather"] == pytest.approx((500 + 700) * 1e-9)
    assert b["total_s"]["fit.upload"] == pytest.approx((2500 + 500) * 1e-9)
    assert b["total_s"]["fit.arrange.ell_layout"] == pytest.approx(1000e-9)
    assert "fit.arrange.pad" not in b["total_s"]


def test_the_root_less_the_union_of_its_phases_is_unattributed(fits):
    a, b = fits
    # 100 ns before the first phase, a hole of 200 ns between the upload
    # and the dispatch, 100 ns after the fetch
    assert a["root_s"] - a["phases_s"] == pytest.approx(400e-9)
    assert b["root_s"] - b["phases_s"] == pytest.approx(200e-9)
    for r in fits:
        assert sum(r["total_s"][p] for p in ps.PHASES) == pytest.approx(
            r["phases_s"])


def test_device_busy_time_lands_in_the_span_that_covers_it(fits):
    a, b = fits
    assert a["busy_s"]["fit"] == pytest.approx(1500e-9)
    assert a["busy_s"]["iterate.dispatch"] == pytest.approx(200e-9)
    assert a["busy_s"]["fit.fetch"] == pytest.approx(1300e-9)
    assert a["busy_s"]["fit.upload"] == 0.0
    assert b["busy_s"]["fit.upload"] == pytest.approx(100e-9)
    assert (b["busy_s"]["iterate.dispatch"] + b["busy_s"]["fit.fetch"]
            == pytest.approx(1000e-9))
    # what the fetch holds beyond the device's work is the transfer's wait
    assert ps.transfer_wait_s(a) == pytest.approx((1600 - 1300) * 1e-9)
    assert ps.transfer_wait_s(b) == pytest.approx((2600 - 800) * 1e-9)


def test_the_readers_take_the_mean_over_the_fits(fits, monkeypatch):
    monkeypatch.setattr(ps, "fits_of_cell", lambda cell: tuple(fits))
    got = {name: m.read({"cell": "a.cell"}) for name, m in PARTITION.items()}
    assert got == pytest.approx({
        "fit_gather_s": (3000 + 1200) / 2 * 1e-9,
        "fit_arrange_s": (1800 + 2000) / 2 * 1e-9,
        # the puts and the fetch's idle part; the fetch's busy part
        "fit_upload_s": (1500 + 300 + 3000 + 1800) / 2 * 1e-9,
        "iterate_dispatch_s": (500 + 700) / 2 * 1e-9,
        "fit_fetch_s": (1300 + 800) / 2 * 1e-9,
        "fit_unattributed_s": (400 + 200) / 2 * 1e-9,
    })
    # the six partition the root
    assert sum(got.values()) == pytest.approx((8800 + 9700) / 2 * 1e-9)
    # a part that one fit of the window lacks reads as nothing
    assert fit_gather_stack_s.read({"cell": "a.cell"}) is None
    monkeypatch.setattr(ps, "fits_of_cell", lambda cell: tuple(fits[:1]))
    assert fit_gather_stack_s.read({"cell": "a.cell"}) == pytest.approx(
        1500e-9)
    assert fit_arrange_init_s.read({"cell": "a.cell"}) == pytest.approx(
        800e-9)


def test_without_a_chip_in_the_trace_the_wait_reads_as_nothing(monkeypatch):
    raw = recorded("trace_program_spans.json")
    raw["planes"] = [p for p in raw["planes"]
                     if not p["name"].startswith("/device:")]
    fits = ps.fits(raw)
    assert len(fits) == 2 and fits[0]["busy_s"] == {}
    monkeypatch.setattr(ps, "fits_of_cell", lambda cell: tuple(fits))
    for name, reader in PARTITION.items():
        value = reader.read({"cell": "a.cell"})
        assert (value is None) == (name in NEED_A_CHIP), name


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_trace_without_program_spans_reads_as_nothing(name, monkeypatch):
    """``trace_small.json`` is what the parent commit leaves: the
    benchmark's marks and no span of the program."""
    without = ps.fits(recorded("trace_small.json"))
    assert without == []
    monkeypatch.setattr(ps, "fits_of_cell", lambda cell: tuple(without))
    assert READERS[name].read({"cell": "a.cell"}) is None


def test_recorded_chip_trace_puts_the_device_inside_the_fetch():
    """The first traced ``fit.call`` of ``kmeans_hibench.fit`` on the chip
    (PR 27, seed 2147484511): the program's spans and the device's
    operations are on one clock."""
    raw = recorded("trace_program_spans_kmeans_hibench.fit.json")
    (mark,) = tr.annotations(raw, {ps.CALL_MARK})
    (fit,) = ps.fits(raw)
    # the root lies just inside the benchmark's mark around the call
    assert fit["root_s"] == pytest.approx((mark[2] - mark[1]) / 1e9,
                                          rel=1e-4)
    assert list(fit["total_s"])[:2] == ["fit", "fit.gather"]
    assert [n for n in fit["total_s"] if n in ps.PHASES] == list(ps.PHASES)
    assert fit["root_s"] - fit["phases_s"] < 0.03 * fit["root_s"]
    # every busy second of the device falls inside dispatch + fetch; the
    # puts are asynchronous, so the upload span holds none of it and the
    # fetch is longer than the device's work by the wait for the transfer
    busy = fit["busy_s"]
    assert busy["fit"] == pytest.approx(0.578, abs=0.001)
    assert busy["iterate.dispatch"] + busy["fit.fetch"] >= 0.99 * busy["fit"]
    assert busy["fit.upload"] == 0.0
    assert fit["total_s"]["fit.upload"] < 0.01
    assert ps.transfer_wait_s(fit) > 2 * busy["fit.fetch"]
    assert fit_upload_s.seconds(fit) == pytest.approx(
        fit["total_s"]["fit.upload"] + fit["total_s"]["fit.fetch"]
        - busy["fit.fetch"])


def test_no_trace_on_disk_reads_as_nothing():
    assert ps.fits_of_cell("no.such.cell") == ()
    assert fit_gather_s.read({"cell": "no.such.cell"}) is None


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_rehearsal_reports_the_readers_that_need_no_chip(cell):
    run = rehearse(cell, 1)
    assert run.returncode == 2, run.stderr[-2000:]
    (line,) = [l[len(MARK):] for l in run.stderr.splitlines()
               if l.startswith(MARK + '{"correct"')]
    metrics = json.loads(line)["metrics"]
    # a CPU trace has no chip's plane, so no busy time to take the wait
    # from: the two that need it are left out, like the other readers of
    # the device
    assert set(PARTITION) - set(metrics) == NEED_A_CHIP
    assert (set(KMEANS_PARTS) <= set(metrics)) == cell.startswith("kmeans")
    fits = ps.fits_of_cell.__wrapped__(cell)    # the trace the run left
    spans = sum(r["phases_s"] for r in fits) / len(fits)
    rest = metrics["fit_unattributed_s"]["value"]
    # the phases and the rest add up to the root span, which lies just
    # inside the call that ``fit_s`` times from outside
    assert spans + rest == pytest.approx(metrics["fit_s"]["value"], rel=0.1)
