"""The reader ``als_index_native`` on small hand-made traces: the mean
over the window's fits of the note ``native`` on ``fit.gather.index``,
and ``None`` where the span carries no such note, as a program from
before the native index leaves it."""

import pytest

from harness import program_scopes as sc
from metrics import als_index_native

CELL = "als_netflix.fit"


def _trace(notes):
    """Two fits inside the benchmark's marks; fit ``k`` notes
    ``notes[k]`` on its span ``fit.gather.index``."""
    events = []
    for k, note in enumerate(notes):
        at = 1000 + 10000 * k
        events += [["fit.call", at, 9000, {}],
                   ["fit", at + 100, 8000, {"op": "ALS", "fit": k + 1}],
                   ["fit.gather", at + 100, 300, {"fit": k + 1}],
                   ["fit.gather.index", at + 150, 200,
                    {"fit": k + 1, **note}]]
    return {"planes": [{"name": "/host:CPU",
                        "lines": [{"name": "python3", "events": events}]}]}


@pytest.fixture
def ctx_of(monkeypatch):
    def ctx(notes):
        fits = tuple(sc.fits(_trace(notes)))
        assert len(fits) == len(notes)
        monkeypatch.setattr(sc, "fits_of_cell", lambda cell: fits)
        return {"cell": CELL}
    return ctx


@pytest.mark.parametrize("notes,want", [
    ([{"native": 1}, {"native": 1}], 1.0),
    ([{"native": 1}, {"native": 0}], 0.5),
    ([{"native": 0}, {"native": 0}], 0.0),
])
def test_the_reader_takes_the_mean_over_the_fits(ctx_of, notes, want):
    assert als_index_native.read(ctx_of(notes)) == pytest.approx(want)


def test_a_span_without_the_note_reads_as_nothing(ctx_of):
    assert als_index_native.read(ctx_of([{}, {}])) is None
    assert als_index_native.read(ctx_of([{"native": 1}, {}])) is None


def test_the_manifest_lists_the_reader_for_the_cell_only():
    from harness import files

    (entry,) = [m for m in files.manifest()["per_layer"]
                if m["name"] == "als_index_native"]
    assert entry == {"name": "als_index_native", "unit": "count",
                     "better": "higher", "source": "program_counter",
                     "layer": "host ingest", "moves": "train_rows_per_s",
                     "workloads": [CELL]}
