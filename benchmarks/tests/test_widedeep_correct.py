"""``correct`` of ``widedeep_criteo.fit`` has been shown to fail, at a
size a test run can hold (on the chip at the cell's own sizes: PERF.md
section 2, ``read_limits.py``): the control (the plain reference with its
state rounded to bfloat16 after every step, put in the program's place)
and each of the reference's three faults read over a limit, the reference
itself and a sound timed path do not, and a timed path broken underneath
the benchmark reports ``correct`` false."""

import argparse

import numpy as np
import pytest

import run as bench
from harness import files
from runners import fit as fit_runner

CELL = "widedeep_criteo.fit"
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
    params, _, losses = reference.run(config, data, SEED)
    numbers = reference.compare(config, data,
                                reference.as_answer(params, losses), SEED)
    assert set(config["limits"]) <= set(numbers)
    assert set(numbers.values()) == {0.0}


@pytest.mark.parametrize("kind", ["control", "unchanged", "half_batch",
                                  "altered"])
def test_control_and_faults_are_over_a_limit(cell, kind):
    config, reference, data = cell
    stand_in = (reference.control(config, data, SEED) if kind == "control"
                else reference.fault(config, data, SEED, kind))
    assert over_limits(config, reference.compare(config, data, stand_in,
                                                 SEED)), kind


def run_cell():
    args = argparse.Namespace(workload=CELL, seed=SEED, seconds=0.5, trace=0)
    return bench.run_cell(args, rehearsal=True, say=lambda text: None)


def test_sound_timed_path_is_correct():
    result = run_cell()
    assert result["correct"] is True, result["compared"]
    assert list(result)[-1] == "compared"
    assert set(result["compared"]) == {"table_err", "tower_err"}


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    """The state returned unchanged (what a fit at a learning rate of
    1e-30 returns is its own start); half of the rows left out; one row
    of the embedding table that no row of the data touches altered in
    its last bits where it is produced."""
    sound_call = fit_runner.Session.call
    sound_answer = fit_runner.Session.answer
    sound_estimator = fit_runner.Session.estimator

    def broken_estimator(self):
        est = sound_estimator(self)
        return est.set_learning_rate(1e-30) if fault == "unchanged" else est

    def broken_call(self):
        if fault == "half_batch" and not getattr(self, "_halved", False):
            from flink_ml_tpu import Table

            self.table = Table({name: self.table[name][::2]
                                for name in self.table.column_names})
            self._halved = True
        return sound_call(self)

    def broken_answer(self, model):
        answer = sound_answer(self, model)
        if fault == "altered":
            emb = np.array(answer["emb"])
            ids = self.table["catFeatures"] + np.concatenate(
                [[0], np.cumsum(self.config["vocab_sizes"])[:-1]])
            idle = np.setdiff1d(np.arange(len(emb)), ids)
            emb[idle[len(idle) // 2]] *= np.float32(1.0 + 1e-6)
            answer["emb"] = emb
        return answer

    monkeypatch.setattr(fit_runner.Session, "estimator", broken_estimator)
    monkeypatch.setattr(fit_runner.Session, "call", broken_call)
    monkeypatch.setattr(fit_runner.Session, "answer", broken_answer)
    result = run_cell()
    assert result["correct"] is False, result["compared"]
