"""The ``fit`` entry: whole ``Estimator.fit(table)`` calls on one resident
host table, fresh weights every call, as a user who refits.

The configuration's file names the estimator class, its setters (each a
key of the file), the table's columns, where the passes of a fit are
counted, the columns of the model that make the answer, and the plan the
fit must take on the chip.
"""

from __future__ import annotations

import importlib

import numpy as np


class Session:
    def __init__(self, config: dict, data: dict, seed: int, devices: list):
        from flink_ml_tpu import Table
        from flink_ml_tpu.parallel.mesh import device_mesh

        self.config = config
        self.seed = int(seed)
        self.rows = int(config["rows"])
        self.mesh = device_mesh(devices=devices)
        self.table = Table(data)
        spec = config["estimator"]
        self._cls = getattr(importlib.import_module(spec["module"]),
                            spec["class"])

    def estimator(self):
        spec = self.config["estimator"]
        est = self._cls()
        for setter, key in spec["setters"].items():
            getattr(est, setter)(self.config[key])
        if spec.get("seed_setter"):
            getattr(est, spec["seed_setter"])(self.seed)
        return est

    def call(self):
        """One ``fit()``.  It returns host arrays, so it ends in a fetch
        of the parameters: the device has finished when it returns."""
        from flink_ml_tpu.parallel.mesh import use_mesh

        with use_mesh(self.mesh):
            return self.estimator().fit(self.table)

    def passes(self, model) -> int:
        spec = self.config["passes"]
        if "model_len" in spec:
            return len(getattr(model, spec["model_len"]))
        return int(self.config[spec["key"]])

    def answer(self, model) -> dict:
        (data,) = model.get_model_data()
        out = {col: np.asarray(data[col][0])
               for col in self.config["answer"]["model_columns"]}
        for attr in self.config["answer"].get("model_attrs", []):
            out[attr] = np.asarray(getattr(model, attr), np.float64)
        return out

    def check_plan(self, model) -> None:
        """On the chip the fit has to take the path the cell is about."""
        spec = self.config.get("expect_plan", {})
        if "model_attr" in spec:
            got = getattr(model, spec["model_attr"])
            if got != spec["equals"]:
                raise SystemExit(f"the fit planned {got!r}, the cell "
                                 f"measures {spec['equals']!r}")
        if "registry_op" in spec:
            from flink_ml_tpu.kernels.registry import lookup

            sig = tuple(self.config.get(s, s) if isinstance(s, str) else s
                        for s in spec["sig"])
            got = lookup(spec["registry_op"], sig=sig).backend
            if got != spec["backend"]:
                raise SystemExit(
                    f"{spec['registry_op']} resolves to {got!r} at {sig}, "
                    f"the cell measures {spec['backend']!r}")


def prepare(config: dict, data: dict, seed: int, devices: list) -> Session:
    return Session(config, data, seed, devices)
