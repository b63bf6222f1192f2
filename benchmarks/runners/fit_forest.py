"""The ``fit`` entry for an estimator whose model is a forest of trees
(``GBTClassifier``, ``GBTRegressor``): whole ``Estimator.fit(table)``
calls on one resident host table, as ``runners/fit.py`` makes them, with
two differences.

- The answer is the forest's columns whole: its first model-data table
  holds a row a tree, and the configuration's ``answer.model_columns``
  are taken as they are (a column of one value a tree, the bin edges,
  the base score and the learning rate, as its first row).
- On the chip ``check_plan``'s registry half also runs before the first
  call: a program whose registry picks another histogram for the cell
  (an XLA one, whose (rows x features) temporaries cannot fit beside the
  table) stops right after the data instead of spending minutes and
  tens of GB of host memory on a fit that cannot end.
"""

from __future__ import annotations

from types import SimpleNamespace

import numpy as np

from runners import fit

#: model-data columns that hold one value for the whole forest
_PER_FOREST = ("binEdges", "baseScore", "learningRate")


class Session(fit.Session):
    def answer(self, model) -> dict:
        data = model.get_model_data()[0]
        return {col: np.asarray(data[col][0] if col in _PER_FOREST
                                else data[col])
                for col in self.config["answer"]["model_columns"]}


def prepare(config: dict, data: dict, seed: int, devices: list) -> Session:
    session = Session(config, data, seed, devices)
    if devices[0].platform == "tpu":
        # a stand-in model that holds the planned value: only the
        # registry's pick is checked here
        spec = config["expect_plan"]
        session.check_plan(
            SimpleNamespace(**{spec["model_attr"]: spec["equals"]}))
    return session
