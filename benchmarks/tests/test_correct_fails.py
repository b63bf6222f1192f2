"""``correct`` has been shown to fail.

1. The control (the reference in the nearest lower precision, put in the
   program's place) fails a limit, at a size a test run can hold.  On the
   chip at the cells' own sizes: PERF.md section 2 (``read_limits.py``).
2. A run driven past the look for a chip, with the timed path broken
   underneath, reports ``correct`` false, once for each fault a cell can
   have: the state returned unchanged; half of the batch left out, the mean
   taken over the rest; an answer altered where it is produced.  (One chip:
   no exchange between chips to leave out.)
"""

import argparse

import numpy as np
import pytest

import run as bench
from harness import files
from runners import fit as fit_runner

CELLS = ["lr_criteo.fit", "kmeans_hibench.fit"]
# sizes for the control: enough steps for the lower precision to show
CONTROL_SIZES = {
    "lr_criteo.fit": {"rows": 1 << 16, "global_batch_size": 256,
                      "reference_params": {"batch": 256, "epochs": 2}},
    "kmeans_hibench.fit": {},
}


def rehearsal_config(cell, extra=None):
    _, config = files.cell(cell, rehearsal=True)
    return files.overlaid(config, extra or {})


def over_limits(config, numbers):
    return [n for n, limit in config["limits"].items()
            if not numbers[n] <= limit]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_and_reference_passes(cell):
    config = rehearsal_config(cell, CONTROL_SIZES[cell])
    reference = files.module("references", config["reference"])
    data = files.generate(config, 7)
    control = reference.control(config, data, 7)
    assert over_limits(config, reference.compare(config, data, control, 7))
    for kind in reference.FAULTS:
        broken = reference.fault(config, data, 7, kind)
        assert over_limits(
            config, reference.compare(config, data, broken, 7)), kind


def unchanged(session, answer):
    """The step returns its state unchanged: the fit's start comes back."""
    if "coefficients" in answer:
        answer["coefficients"] = np.zeros_like(answer["coefficients"])
        answer["intercept"] = np.zeros_like(answer["intercept"])
    else:
        points = session.table["features"]
        k = answer["centroids"].shape[0]
        start = np.random.default_rng(session.seed).permutation(
            len(points))[:k]
        answer["centroids"] = points[start]
    return answer


def altered(session, answer):
    """The answer altered where it is produced: the largest weight by a
    twentieth; one centroid, the last, by a tenth."""
    if "coefficients" in answer:
        values = np.array(answer["coefficients"])
        flat = values.reshape(-1)
        flat[int(np.argmax(np.abs(flat)))] *= 1.05
        answer["coefficients"] = values
    else:
        values = np.array(answer["centroids"])
        values[-1] *= 1.1
        answer["centroids"] = values
    return answer


def half_table(session):
    """Half of the rows left out, the means taken over the rest."""
    from flink_ml_tpu import Table

    session.table = Table({name: session.table[name][::2]
                           for name in session.table.column_names})


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    sound_call = fit_runner.Session.call
    sound_answer = fit_runner.Session.answer

    def broken_call(self):
        if fault == "half_batch" and not getattr(self, "_halved", False):
            half_table(self)
            self._halved = True
        return sound_call(self)

    def broken_answer(self, model):
        answer = sound_answer(self, model)
        if fault == "unchanged":
            return unchanged(self, answer)
        if fault == "altered":
            return altered(self, answer)
        return answer

    monkeypatch.setattr(fit_runner.Session, "call", broken_call)
    monkeypatch.setattr(fit_runner.Session, "answer", broken_answer)
    args = argparse.Namespace(workload=cell, seed=2147483659, seconds=0.5,
                              trace=0)
    result = bench.run_cell(args, rehearsal=True, say=lambda text: None)
    assert result["correct"] is False, result["compared"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_timed_path_is_correct(cell):
    args = argparse.Namespace(workload=cell, seed=2147483659, seconds=0.5,
                              trace=0)
    result = bench.run_cell(args, rehearsal=True, say=lambda text: None)
    assert result["correct"] is True, result["compared"]
    assert list(result)[-1] == "compared"
