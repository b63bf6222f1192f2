"""``correct`` of ``gbt_airline.fit`` has been shown to fail, here at the
cell's rehearsal size (the readings at its own size, on the chip, are
``read_limits.py``'s: PERF.md section 2): the control (the plain
reference with its histograms' addends rounded to bfloat16, put in the
program's place) and each of the reference's three faults read over a
limit, the reference itself and a sound timed path do not, and a timed
path broken underneath the benchmark reports ``correct`` false."""

import argparse

import numpy as np
import pytest

import run as bench
from harness import files
from runners import fit_forest

CELL = "gbt_airline.fit"
SEED = 2147483659


@pytest.fixture(scope="module")
def cell():
    _, config = files.cell(CELL, rehearsal=True)
    reference = files.module("references", config["reference"])
    return config, reference, files.generate(config, SEED)


def over_limits(config, numbers):
    return [n for n, limit in config["limits"].items()
            if not numbers[n] <= limit]


def test_the_reference_itself_is_within_every_limit(cell):
    config, reference, data = cell
    numbers = reference.compare(config, data,
                                reference.reference(config, data, SEED), SEED)
    assert set(config["limits"]) <= set(numbers)
    assert {numbers[n] for n in config["limits"]} == {0.0}


@pytest.mark.parametrize("kind", ["control", "half_rows", "start",
                                  "tied_splits"])
def test_control_and_faults_are_over_a_limit(cell, kind):
    config, reference, data = cell
    stand_in = (reference.control(config, data, SEED) if kind == "control"
                else reference.fault(config, data, SEED, kind))
    assert over_limits(config, reference.compare(config, data, stand_in,
                                                 SEED)), kind


def test_a_worse_split_at_a_tied_node_is_a_mismatch(cell):
    """Nodes whose best and next best split tie (the next bin of an empty
    one, where a column of few values repeats its quantile edges) are
    checked like any other: the reference's forest with each tied node's
    split moved to the node's worst fails ``split_mismatch`` alone, at
    least once a moved node; the same forest unmoved reads 0."""
    config, reference, data = cell
    moved = reference.fault(config, data, SEED, "tied_splits")
    assert moved["tied"] > 0
    numbers = reference.compare(config, data, moved, SEED)
    assert numbers["split_mismatch"] > config["limits"]["split_mismatch"]
    assert numbers["split_shortfall"] > reference.DECIDED


def run_cell():
    args = argparse.Namespace(workload=CELL, seed=SEED, seconds=0.5, trace=0)
    return bench.run_cell(args, rehearsal=True, say=lambda text: None)


def test_sound_timed_path_is_correct():
    result = run_cell()
    assert result["correct"] is True, result["compared"]
    assert list(result)[-1] == "compared"
    assert set(result["compared"]) == {"margin_err", "logloss_gap",
                                       "split_mismatch",
                                       "first_tree_mismatch"}


@pytest.mark.parametrize("fault", ["half_rows", "start"])
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    """Every second row left out of the fit; the start margins returned
    (a forest that adds nothing: the fit's trees lost)."""
    sound_call = fit_forest.Session.call
    sound_answer = fit_forest.Session.answer

    def broken_answer(self, model):
        answer = sound_answer(self, model)
        if fault == "start":
            answer["feature"] = np.full_like(answer["feature"], -1)
            answer["value"] = np.zeros_like(answer["value"])
        return answer

    def broken_call(self):
        if fault == "half_rows" and not getattr(self, "_halved", False):
            from flink_ml_tpu import Table

            self.table = Table({name: self.table[name][::2]
                                for name in self.table.column_names})
            self._halved = True
        return sound_call(self)

    monkeypatch.setattr(fit_forest.Session, "answer", broken_answer)
    monkeypatch.setattr(fit_forest.Session, "call", broken_call)
    result = run_cell()
    assert result["correct"] is False, result["compared"]
