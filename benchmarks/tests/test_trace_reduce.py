"""The trace reducer on a small recorded trace: busy union, idle share,
self times, custom-call time, gap attribution, and the readers on it."""

import os

import pytest

from harness import trace_reduce as tr
from metrics import (device_idle_pct, host_only_s, kernel_roofline_pct,
                     step_mfu_pct, step_ms)

HERE = os.path.dirname(os.path.abspath(__file__))
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce(tr.load_json(os.path.join(HERE, "data",
                                               "trace_small.json")))


def test_union_clip_and_gaps():
    merged = tr.union([(5, 9), (1, 3), (2, 4), (9, 10)])
    assert merged == [[1, 4], [5, 10]]
    assert tr.total(tr.clip(merged, 2, 6)) == 3
    assert tr.gaps(merged, 0, 12) == [[0, 1], [4, 5], [10, 12]]


def test_busy_union_and_idle_share(reduced):
    assert reduced["window_ns"] == 20100
    assert reduced["busy_ns"] == 100 + 4000 + 4500
    assert [c["busy_ns"] for c in reduced["calls"]] == [4100, 4500]
    ctx = {"trace": reduced}
    assert device_idle_pct.read(ctx) == pytest.approx(
        100 * (1 - 8600 / 20100))
    assert host_only_s.read(ctx) == pytest.approx(
        ((10000 - 4100) + (10000 - 4500)) / 2 / 1e9)


def test_self_times_and_breakdown(reduced):
    ops = dict(reduced["device_ops"])
    assert ops["while.1"] == pytest.approx((1200 + 2000) / 1e9)
    assert ops["custom-call.3"] == pytest.approx(3500 / 1e9)
    assert sum(ops.values()) == pytest.approx(reduced["busy_ns"] / 1e9)


def test_gaps_go_to_the_annotation_that_covers_them(reduced):
    idle = dict(reduced["idle_gaps"])
    assert idle["between_fits"] == pytest.approx(100 / 1e9)
    assert idle["fit.call"] == pytest.approx((11500 - 100) / 1e9)
    assert "outside_annotations" not in idle


def test_step_and_kernel_readers(reduced):
    config = {"steps_per_pass": 2, "counts": "kmeans_hibench",
              "rows": 1000, "k": 8, "dim": 4}
    ctx = {"trace": reduced, "config": config, "peaks": PEAKS,
           "calls": [(0.0, 1.0, 1), (1.0, 2.0, 1)]}
    # the fused program is the module with most device time in the call
    assert step_ms.read(ctx) == pytest.approx(
        1e3 * (4000 / 2 + 4500 / 2) / 2 / 1e9)
    least = max(4.0 * 1000 * 8 * 4 / 197e12, 4.0 * 1000 * 4 / 819e9)
    assert step_mfu_pct.read(ctx) == pytest.approx(
        100 * least / ((4000 / 2 + 4500 / 2) / 2 / 1e9))
    kernel_least = max(4.0 * 1000 * 8 * 4 / 197e12,
                       4.0 * (1000 * 4 + 2 * 8 * 4 + 8) / 819e9) * 2
    assert kernel_roofline_pct.read(ctx) == pytest.approx(
        100 * (kernel_least / 2000e-9 + kernel_least / 1500e-9) / 2)


def test_a_reader_with_nothing_to_read_returns_nothing():
    assert step_ms.read({"trace": None}) is None
    assert kernel_roofline_pct.read({"trace": None, "peaks": PEAKS}) is None
    calls = [{"ops": [["fusion.2", 10.0, {}]], "module_ns": {"m": 10.0},
              "start_ns": 0, "end_ns": 20, "busy_ns": 10}]
    ctx = {"trace": {"calls": calls}, "peaks": PEAKS,
           "config": {"steps_per_pass": 1, "counts": "kmeans_hibench",
                      "rows": 8, "k": 2, "dim": 2},
           "calls": [(0.0, 1.0, 1)]}
    assert kernel_roofline_pct.read(ctx) is None


@pytest.mark.parametrize("cell, kernels", [
    ("lr_criteo.fit", {"ell_margin_fused.6 custom-call",
                       "ell_scatter_apply_fused.6 custom-call"}),
    ("kmeans_hibench.fit", {"kmeans_update_stats.5 custom-call"}),
])
def test_recorded_chip_trace(cell, kernels):
    """An excerpt of each cell's first traced ``fit.call`` on the chip
    (PR 26): the plane and line rules find the device, the annotation and
    the Pallas calls."""
    raw = tr.load_json(os.path.join(HERE, "data", f"trace_{cell}.json"))
    assert [p["name"] for p in tr.device_planes(raw)] == ["/device:TPU:0"]
    reduced = tr.reduce(raw)
    call = reduced["calls"][0]
    assert 0 < call["busy_ns"] < call["end_ns"] - call["start_ns"]
    assert reduced["busy_ns"] <= reduced["window_ns"]
    spent = {}
    for name, ns, stats in call["ops"]:
        if kernel_roofline_pct.is_kernel(name, stats):
            spent[name] = spent.get(name, 0.0) + ns
    assert kernels <= set(spent)
    # the program's other custom-calls are 1 ns markers: they add nothing
    others = sum(ns for name, ns in spent.items() if name not in kernels)
    assert others < 1e-6 * sum(spent.values())
    assert max(call["module_ns"], key=call["module_ns"].get).startswith(
        "jit_")
