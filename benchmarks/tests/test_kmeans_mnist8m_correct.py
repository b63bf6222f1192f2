"""``correct`` of ``kmeans_mnist8m.fit`` has been shown to fail, at a size a
test run can hold (on the chip at the cell's own sizes: PERF.md section 2,
``read_limits.py``): the control (the plain reference with the operands of
both contractions rounded to fp8, put in the program's place) and each of
the reference's three faults read over a limit, the reference itself and a
sound timed path do not, and a timed path broken underneath the benchmark
reports ``correct`` false.  One centroid of the answer scaled by 1.1 is
NOT seen: on the chip the worst centroid of a sound run lies further from
the reference's than that (the configuration's ``limits_notes``), so that
number has no limit, and the test below holds the blind spot in view."""

import argparse

import numpy as np
import pytest

import run as bench
from harness import files
from runners import fit as fit_runner

CELL = "kmeans_mnist8m.fit"
SEED = 2147483659
# sizes for the control and the faults: enough centroids, rounds and shapes
# for the lower precision to show (the rehearsal's 64 centroids over 5
# rounds leave the fp8 control's median at 0.033)
SIZES = {"rows": 32768, "k": 256, "max_iter": 20,
         "generator_params": {"prototypes": 400},
         "reference_params": {"k": 256, "iterations": 20}}


@pytest.fixture(scope="module")
def cell():
    _, config = files.cell(CELL, rehearsal=True)
    config = files.overlaid(config, SIZES)
    reference = files.module("references", config["reference"])
    return config, reference, files.generate(config, SEED)


def over_limits(config, numbers):
    return [n for n, limit in config["limits"].items()
            if not numbers[n] <= limit]


def test_the_reference_itself_is_within_every_limit(cell):
    config, reference, data = cell
    # the stated precision of the rehearsal: control(dtype) is the
    # reference's own fit in that precision
    own = reference.control(config, data, SEED,
                            config["reference_params"]["operand_dtype"])
    numbers = reference.compare(config, data, own, SEED)
    assert set(config["limits"]) <= set(numbers)
    assert {numbers[n] for n in config["limits"]} == {0.0}


@pytest.mark.parametrize("kind", ["control", "unchanged", "half_batch"])
def test_control_and_faults_are_over_a_limit(cell, kind):
    config, reference, data = cell
    stand_in = (reference.control(config, data, SEED) if kind == "control"
                else reference.fault(config, data, SEED, kind))
    assert over_limits(config, reference.compare(config, data, stand_in,
                                                 SEED)), kind


def test_one_altered_centroid_is_what_correct_cannot_see(cell):
    """The fault ``altered`` moves the worst centroid's gap alone, by a
    tenth; the median and the objective, which are held, do not see it."""
    config, reference, data = cell
    numbers = reference.compare(
        config, data, reference.fault(config, data, SEED, "altered"), SEED)
    assert not over_limits(config, numbers)
    assert 0.05 < numbers["centroid_gap_worst"] < 0.2
    assert "centroid_gap_worst" not in config["limits"]


def test_the_generator_has_the_sources_shape(cell):
    config, _, data = cell
    points = data["features"]
    assert points.dtype == np.float32 and points.flags.c_contiguous
    assert points.shape == (config["rows"], 784)
    assert np.array_equal(points, np.rint(points))
    assert points.min() == 0.0 and points.max() <= 255.0
    inked = points > 0
    assert 0.15 < inked.mean() < 0.24
    assert 130.0 < points[inked].mean() < 170.0
    again = files.generate(config, SEED)["features"]
    assert np.array_equal(points, again)
    assert not np.array_equal(points,
                              files.generate(config, SEED + 1)["features"])


def run_cell():
    args = argparse.Namespace(workload=CELL, seed=SEED, seconds=0.5, trace=0)
    return bench.run_cell(args, rehearsal=True, say=lambda text: None)


def test_sound_timed_path_is_correct():
    result = run_cell()
    assert result["correct"] is True, result["compared"]
    assert list(result)[-1] == "compared"
    _, config = files.cell(CELL, rehearsal=True)
    assert set(result["compared"]) == set(config["limits"])


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    """The start returned as the answer (a fit whose updates were lost);
    every second row left out."""
    sound_call = fit_runner.Session.call
    sound_answer = fit_runner.Session.answer

    def broken_answer(self, model):
        answer = sound_answer(self, model)
        if fault == "unchanged":
            points = self.table["features"]
            start = np.random.default_rng(self.seed).permutation(
                len(points))[:answer["centroids"].shape[0]]
            answer["centroids"] = points[start]
        return answer

    def broken_call(self):
        if fault == "half_batch" and not getattr(self, "_halved", False):
            from flink_ml_tpu import Table

            # every second row gone from the table: the start's row
            # numbers then name other rows than the reference's do
            self.table = Table({"features": np.ascontiguousarray(
                self.table["features"][::2])})
            self._halved = True
        return sound_call(self)

    monkeypatch.setattr(fit_runner.Session, "answer", broken_answer)
    monkeypatch.setattr(fit_runner.Session, "call", broken_call)
    result = run_cell()
    assert result["correct"] is False, result["compared"]
