"""``correct`` of ``als_netflix.fit`` has been shown to fail, at a size a
test run can hold (on the chip at the cell's own sizes: PERF.md section 2,
``read_limits.py``): the control (the plain reference with its
contractions at one bf16 pass, put in the program's place) and each of the
reference's three faults read over a limit, the reference itself and a
sound timed path do not, and a timed path broken underneath the benchmark
reports ``correct`` false."""

import argparse

import numpy as np
import pytest

import run as bench
from harness import files
from runners import fit as fit_runner

CELL = "als_netflix.fit"
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
                                reference.run(config, data, SEED), SEED)
    assert set(config["limits"]) <= set(numbers)
    assert {numbers[n] for n in config["limits"]} == {0.0}


@pytest.mark.parametrize("kind", ["control", "unchanged", "half_ratings",
                                  "plain_lambda"])
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
    assert set(result["compared"]) == {"factor_err", "rmse_gap"}


@pytest.mark.parametrize("fault", ["unchanged", "half_ratings",
                                   "plain_lambda"])
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    """The start returned as the answer (a fit whose updates were lost);
    every second rating left out; the weighting of lambda by a group's
    count taken out of the program's own solve."""
    sound_call = fit_runner.Session.call
    sound_answer = fit_runner.Session.answer

    def broken_answer(self, model):
        answer = sound_answer(self, model)
        if fault == "unchanged":
            rng = np.random.default_rng(self.seed)
            for name in ("userFactors", "itemFactors"):
                answer[name] = (rng.normal(size=answer[name].shape) / np.sqrt(
                    answer[name].shape[1])).astype(np.float32)
        return answer

    def broken_call(self):
        if fault == "half_ratings" and not getattr(self, "_halved", False):
            from flink_ml_tpu import Table

            self.table = Table({name: self.table[name][::2]
                                for name in self.table.column_names})
            self._halved = True
        return sound_call(self)

    monkeypatch.setattr(fit_runner.Session, "answer", broken_answer)
    monkeypatch.setattr(fit_runner.Session, "call", broken_call)
    if fault == "plain_lambda":
        from flink_ml_tpu.models.recommendation import als

        sound = als._regularized
        monkeypatch.setattr(
            als, "_regularized", lambda A, cnt, *rest: sound(
                A, np.ones(cnt.shape, np.float32), *rest))
    result = run_cell()
    assert result["correct"] is False, result["compared"]
