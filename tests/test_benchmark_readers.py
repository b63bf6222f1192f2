"""The benchmark's per-layer readers on recorded chip traces.

``benchmarks/run.py`` calls every reader of ``BENCHMARK.json``'s
``per_layer`` in every cell's traced run.  A CPU rehearsal's trace has no
device plane, so there each reader returns before it reads anything; on
the chip it reads the cell's own configuration.  These tests hand the
readers the chip traces recorded under ``benchmarks/tests/data`` with the
configuration of each listed cell: no reader may raise, the linear cell's
readers find nothing in another estimator's trace, and the ELL rooflines
read the Pallas calls by the names the program gives them.
"""

import os
import sys

import pytest

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
_LINEAR = ("fit_arrange_permute_s", "fit_arrange_ell_layout_s",
           "lr_margin_ms", "lr_apply_ms", "lr_unscoped_ms",
           "ell_margin_roofline_pct", "ell_scatter_roofline_pct",
           "ell_overflow_slots", "ell_heavy_indices")
_KERNELS = {"ell_margin_fused": "ell_margin",
            "ell_scatter_apply_fused": "ell_scatter_apply"}


@pytest.fixture(scope="module")
def bench():
    if _BENCH not in sys.path:
        sys.path.insert(0, _BENCH)
    from harness import files, program_scopes, trace_reduce

    return files, program_scopes, trace_reduce


def _cells():
    import json

    with open(os.path.join(os.path.dirname(_BENCH), "BENCHMARK.json")) as f:
        return sorted(w["name"] for w in json.load(f)["workloads"])


def _ctx(bench, monkeypatch, recorded, cell, rename=None):
    """What ``run.py`` hands the readers after a traced window of ``cell``
    whose trace is the recorded one, operations renamed by ``rename``."""
    files, program_scopes, trace_reduce = bench
    raw = trace_reduce.load_json(
        os.path.join(_BENCH, "tests", "data", f"trace_{recorded}.json"))
    for plane in raw["planes"]:
        for line in plane["lines"]:
            for event in line["events"]:
                stem, _, rest = event[0].partition(".")
                if rename and stem in rename:
                    event[0] = f"{rename[stem]}.{rest}"
    fits = tuple(program_scopes.fits(raw))
    monkeypatch.setattr(program_scopes, "fits_of_cell", lambda name: fits)
    trace = trace_reduce.reduce(raw)
    # the recorded excerpts keep the operations of a window's first call
    trace["calls"] = [call for call in trace["calls"] if call["ops"]]
    workload, config = files.cell(cell)
    return {"cell": cell, "config": config, "workload": workload,
            "rows": 1, "calls": [(0.0, 1.0, 1)] * len(trace["calls"]),
            "window_start": 0.0, "setup_s": 1.0, "compile_requests": 0,
            "compile_cache_hits": 0, "memory_peak_bytes": 1,
            "peaks": files.peaks("TPU v5 lite"), "trace": trace}


@pytest.mark.parametrize("cell", _cells())
@pytest.mark.parametrize("recorded", ["kmeans_hibench.fit",
                                      "lr_criteo.fit"])
def test_every_reader_takes_a_chip_trace_in_every_cell(
        bench, monkeypatch, recorded, cell):
    """Each reader gives a number or ``None`` on a chip trace, whatever
    the cell's configuration holds; the linear cell's readers give
    ``None`` on a KMeans fit's trace."""
    files = bench[0]
    ctx = _ctx(bench, monkeypatch, recorded, cell)
    read = {m["name"]: files.module("metrics", m["name"]).read(ctx)
            for m in files.manifest()["per_layer"]}
    if recorded == "kmeans_hibench.fit":
        assert {name: read[name] for name in _LINEAR} == dict.fromkeys(
            _LINEAR)


@pytest.mark.parametrize("metric", ["ell_margin_roofline_pct",
                                    "ell_scatter_roofline_pct"])
def test_ell_rooflines_read_the_kernels_by_their_names(bench, monkeypatch,
                                                       metric):
    """The linear cell's recorded trace (its Pallas calls named after
    their kernel functions, as the parent's program leaves them) gives
    ``None``; under the names the program gives them now, a share of the
    roofline above 0 and at most 100%."""
    files = bench[0]
    reader = files.module("metrics", metric)
    assert reader.read(_ctx(bench, monkeypatch, "lr_criteo.fit",
                            "lr_criteo.fit")) is None
    share = reader.read(_ctx(bench, monkeypatch, "lr_criteo.fit",
                             "lr_criteo.fit", rename=_KERNELS))
    assert 0.0 < share <= 100.0
