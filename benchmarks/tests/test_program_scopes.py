"""The reader of the program's named scopes, span notes and further span
parts (``harness/program_scopes.py``) on a small hand-made trace, the
thirteen readers over it, ``None`` from every one on a trace without
program spans or without scopes; and the wire reader
(``harness/xplane_wire.py``) on a message encoded by hand."""

import os

import pytest

from harness import files
from harness import program_scopes as sc
from harness import trace_reduce as tr
from harness import xplane_wire
from metrics import (fit_arrange_layout_s, fit_arrange_params_s,
                     fit_arrange_route_s, lookup_ms, lookup_roofline_pct,
                     optimizer_ms, optimizer_roofline_pct, route_unique_rows,
                     step_other_ms, table_grad_ms, table_grad_roofline_pct,
                     towers_mfu_pct, towers_ms)

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "widedeep_criteo.fit"
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
HOST = {"fit_arrange_layout_s": fit_arrange_layout_s,
        "fit_arrange_route_s": fit_arrange_route_s,
        "fit_arrange_params_s": fit_arrange_params_s,
        "route_unique_rows": route_unique_rows}
TIMES = {"lookup_ms": lookup_ms, "towers_ms": towers_ms,
         "table_grad_ms": table_grad_ms, "optimizer_ms": optimizer_ms,
         "step_other_ms": step_other_ms}
SHARES = {"towers_mfu_pct": towers_mfu_pct,
          "lookup_roofline_pct": lookup_roofline_pct,
          "table_grad_roofline_pct": table_grad_roofline_pct,
          "optimizer_roofline_pct": optimizer_roofline_pct}
READERS = {**HOST, **TIMES, **SHARES}


def recorded(name):
    return tr.load_json(os.path.join(HERE, "data", name))


@pytest.fixture
def ctx(monkeypatch):
    """Two fits of one pass of two steps each, at the cell's own shapes."""
    fits = tuple(sc.fits(recorded("trace_program_scopes.json")))
    monkeypatch.setattr(sc, "fits_of_cell", lambda cell: fits)
    _, config = files.cell(CELL)
    return {"cell": CELL, "peaks": PEAKS, "calls": [(0, 1, 1), (1, 2, 1)],
            "config": {**config, "steps_per_pass": 2}}


def test_the_manifest_lists_the_readers_for_the_cell_only():
    listed = {m["name"]: m for m in files.manifest()["per_layer"]}
    for name in READERS:
        assert listed[name]["workloads"] == [CELL], name
        assert listed[name]["moves"] == "train_rows_per_s"
    assert {listed[n]["unit"] for n in SHARES} == {"%"}


def test_only_the_fits_inside_a_mark_count_and_a_span_is_its_threads():
    fits = sc.fits(recorded("trace_program_scopes.json"))
    assert [r["start_ns"] for r in fits] == [1100, 12100]
    a, b = fits
    assert a["span_s"]["fit.arrange.route"] == pytest.approx(700e-9)
    assert a["notes"]["fit.arrange.route"]["placement"] == "scatter"
    assert b["notes"]["fit.arrange.route"]["unique_mean"] == 126100.0


def test_an_operation_counts_for_the_scope_in_its_tf_op():
    a, b = sc.fits(recorded("trace_program_scopes.json"))
    assert a["scope_ns"] == {"widedeep.lookup": 1000, "widedeep.towers": 500,
                             "widedeep.table_grad": 2000,
                             "widedeep.optimizer": 1500}
    # the fused program is the module with most device time; the loop
    # counts by its self time, the tables' draw is another program
    assert a["program_ns"] == 6000
    assert b["scope_ns"]["widedeep.lookup"] == 500
    assert sc.scope_of({"tf_op": "jit(run)/while/body/a.b/jvp()/c.d/add:"}) \
        == "c.d"
    assert sc.scope_of({"tf_op": "jit(run)/while/body/add:"}) is None
    assert sc.scope_of({}) is None


def test_the_readers_take_the_mean_over_the_fits(ctx):
    got = {name: m.read(ctx) for name, m in {**HOST, **TIMES}.items()}
    assert got == pytest.approx({
        "fit_arrange_layout_s": 300e-9, "fit_arrange_route_s": 700e-9,
        "fit_arrange_params_s": 200e-9, "route_unique_rows": 126000.0,
        # two steps a fit; nanoseconds to milliseconds
        "lookup_ms": (1000 + 500) / 2 / 2 / 1e6,
        "towers_ms": 500 / 2 / 1e6, "table_grad_ms": 2000 / 2 / 1e6,
        "optimizer_ms": 1500 / 2 / 1e6,
        # the loop's self time and the slice
        "step_other_ms": ((6000 - 5000) + (6000 - 4500)) / 2 / 2 / 1e6})


def test_a_share_is_the_least_time_over_the_scopes_time(ctx):
    counts = files.module("configs", "widedeep_criteo")
    config = ctx["config"]
    assert counts.tower_weights(config) == 1094912
    assert counts.towers_counts(config)["flops"] == 6.0 * 1094912 * 32768
    rows = 33762577 * 17 * 4.0
    assert counts.optimizer_counts(config)["bytes"] == 6 * rows
    assert counts.lookup_counts(config)["bytes"] == 851968 * 17 * 4.0
    assert counts.table_grad_counts(config)["bytes"] == (
        851968 + 125000) * 17 * 4.0
    step = counts.step_counts(config)
    assert step["flops"] == counts.towers_counts(config)["flops"]
    assert step["bytes"] == pytest.approx(
        32768 * 41 * 4.0 + 6 * rows + (2 * 851968 + 125000) * 17 * 4.0)
    assert counts.kernel_counts(config) == {"flops": 0.0, "bytes": 0.0}
    least = 6.0 * 1094912 * 32768 / 197e12
    assert towers_mfu_pct.read(ctx) == pytest.approx(
        100 * least / (500 / 2 / 1e9))
    assert optimizer_roofline_pct.read(ctx) == pytest.approx(
        100 * (6 * rows / 819e9) / (1500 / 2 / 1e9))
    assert lookup_roofline_pct.read(ctx) > table_grad_roofline_pct.read(ctx)
    # without the chip's peaks (a rehearsal) a share reads as nothing
    assert towers_mfu_pct.read({**ctx, "peaks": None}) is None


@pytest.mark.parametrize("name", sorted(READERS))
@pytest.mark.parametrize("trace", ["trace_small.json",
                                   "trace_program_spans.json"])
def test_a_trace_without_what_is_read_reads_as_nothing(name, trace, ctx,
                                                       monkeypatch):
    """``trace_small.json`` is what the parent commit leaves (the
    benchmark's marks, no span of the program); ``trace_program_spans
    .json`` has fits of another estimator: phases, no scope, no note."""
    without = tuple(sc.fits(recorded(trace)))
    assert len(without) == (0 if trace == "trace_small.json" else 2)
    monkeypatch.setattr(sc, "fits_of_cell", lambda cell: without)
    assert READERS[name].read(ctx) is None


def test_no_trace_on_disk_reads_as_nothing():
    assert sc.fits_of_cell("no.such.cell") == ()
    assert lookup_ms.read({"cell": "no.such.cell", "calls": [(0, 1, 1)],
                           "config": {"steps_per_pass": 1}}) is None


# ---- the wire reader, on a message encoded by hand

def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number, value):
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(number << 3 | 2) + varint(len(value)) + value


def test_the_wire_reader_lays_an_events_stats_over_its_metadatas(tmp_path):
    stat_names = {1: "tf_op", 2: "unique_mean", 3: "fold_passes",
                  4: "placement", 5: "scatter"}
    stat_meta = b"".join(
        field(5, field(1, k) + field(2, field(1, k) + field(2, name)))
        for k, name in stat_names.items())
    tf_op = "jit(run)/while/body/widedeep.towers/dot_general:"
    event_meta = field(4, field(1, 7) + field(2, (
        field(1, 7) + field(2, "%fusion.1 = f32[8]{0} fusion()")
        + field(4, "fusion.1") + field(5, field(1, 1) + field(5, tf_op)))))
    event_meta += field(4, field(1, 8) + field(2, field(1, 8)
                                               + field(2, "fit.arrange.route")))
    import struct
    stats = (field(4, field(1, 2) + varint(2 << 3 | 1)
                   + struct.pack("<d", 125900.5))
             + field(4, field(1, 3) + field(4, 15))
             + field(4, field(1, 4) + field(7, 5)))
    line = field(3, field(2, "XLA Ops") + field(3, 1000) + field(4, (
        field(1, 7) + field(2, 2_500_000) + field(3, 40_000)))
        + field(4, field(1, 8) + field(2, 0) + field(3, 1_000) + stats))
    plane = field(1, field(2, "/device:TPU:0") + line + event_meta
                  + stat_meta)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(plane + field(1, field(2, "/host:CPU")))
    trace = xplane_wire.read(str(path))
    assert [p["name"] for p in trace["planes"]] == ["/device:TPU:0",
                                                    "/host:CPU"]
    (only,) = trace["planes"][0]["lines"]
    assert only["name"] == "XLA Ops"
    assert only["events"][0] == ["fusion.1", 1000 + 2500.0, 40.0,
                                 {"tf_op": tf_op}]
    assert only["events"][1] == ["fit.arrange.route", 1000.0, 1.0, {
        "unique_mean": 125900.5, "fold_passes": 15, "placement": "scatter"}]
    kept = xplane_wire.read(str(path),
                            lambda plane, line, event: event == "fusion.1")
    assert [e[0] for e in kept["planes"][0]["lines"][0]["events"]] == [
        "fusion.1"]


def test_a_traced_rehearsal_reports_the_readers_that_need_no_chip():
    """A CPU trace has the program's spans and their notes, read from the
    ``.xplane.pb`` itself, and no chip's plane: the parts of
    ``fit.arrange`` and the route's counter are there, the scopes' times
    and shares are left out."""
    import json

    from test_rehearsal import MARK, rehearse

    run = rehearse(CELL, 1)
    assert run.returncode == 2, run.stderr[-2000:]
    (line,) = [l[len(MARK):] for l in run.stderr.splitlines()
               if l.startswith(MARK + '{"correct"')]
    metrics = json.loads(line)["metrics"]
    assert set(READERS) & set(metrics) == set(HOST)
    parts = sum(metrics[n]["value"] for n in HOST if n.endswith("_s"))
    assert parts == pytest.approx(metrics["fit_arrange_s"]["value"], rel=0.05)
    # 512 rows of 26 ids a step, most of them distinct at these sizes
    assert 26 <= metrics["route_unique_rows"]["value"] <= 512 * 26


def test_recorded_chip_trace_tells_the_scopes_apart():
    """An excerpt of the first traced ``fit.call`` of the cell on the chip
    (PR 29, seed 2147487404, from the wire reader): the program's spans of
    that fit, the other programs it ran, and the fused program's first two
    steps (the loops and the module cut to that stretch)."""
    raw = recorded("trace_widedeep_criteo.fit.json")
    assert [p["name"] for p in tr.device_planes(raw)] == ["/device:TPU:0"]
    (fit,) = sc.fits(raw)
    assert {"fit.arrange.layout", "fit.arrange.route",
            "fit.arrange.params"} <= set(fit["span_s"])
    parts = sum(fit["span_s"]["fit.arrange." + p]
                for p in ("layout", "route", "params"))
    assert parts == pytest.approx(fit["span_s"]["fit.arrange"], rel=0.01)
    notes = fit["notes"]["fit.arrange.route"]
    assert notes["placement"] == "scatter" and notes["fold_passes"] == 15
    assert 125000 < notes["unique_mean"] <= notes["unique_max"] < 127000
    assert notes["route_bytes"] == 32 * 4 * 2 * (851968 + notes["unique_max"])
    # every operation of a step is under one of the four scopes but the
    # loops' own time and the slices of the epoch tensors
    assert set(fit["scope_ns"]) == {"widedeep.lookup", "widedeep.towers",
                                    "widedeep.table_grad",
                                    "widedeep.optimizer"}
    claimed = sum(fit["scope_ns"].values())
    assert 0.99 * fit["program_ns"] < claimed <= fit["program_ns"]
    per_step = {k: v / 2 / 1e6 for k, v in fit["scope_ns"].items()}
    assert per_step == pytest.approx({
        "widedeep.lookup": 29.4, "widedeep.towers": 1.72,
        "widedeep.table_grad": 34.5, "widedeep.optimizer": 24.0}, rel=0.03)
    # the tables' draw is another program: its operations carry no scope
    # and are not the fused program's
    reduced = tr.reduce(raw)
    assert max(reduced["calls"][0]["module_ns"],
               key=reduced["calls"][0]["module_ns"].get).startswith("jit_run")
    assert any(m.startswith("jit__init_tables")
               for m in reduced["calls"][0]["module_ns"])
