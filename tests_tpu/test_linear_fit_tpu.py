"""``LogisticRegression.fit`` on mixed Criteo-shaped columns, on the chip:
the ELL plan through the one-device ingest (columns put flat and laid out
by the device in the epoch order, the layout read through that order and
put in rounds) against the host's arrays and the float64 reference."""

import importlib.util
import json
import os

import numpy as np
import pytest

pytestmark = pytest.mark.tpu

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")


def _bench_module(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"_bench_{name}", os.path.join(_BENCH, kind, f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_ell_fit_on_the_device_ingest_matches_host_arrays_and_reference(
        tpu, monkeypatch):
    """``lr_criteo``'s table at 16 steps of batch 32,768 (524,288 rows x
    13 dense + 26 Zipf-skewed hashed fields, 2^20 weights, 2 epochs):
    the fit plans ``ell``, trains on arrays equal bit for bit to the host
    path's (``prepare_epoch_tensor`` and ``ell_layout`` of the permuted
    categorical tensor), and lands within the configuration's limits of
    the plain float64 SGD."""
    from flink_ml_tpu import Table
    from flink_ml_tpu.models.classification import LogisticRegression
    from flink_ml_tpu.models.common import sgd as S
    from flink_ml_tpu.ops.ell_scatter import ell_layout
    from flink_ml_tpu.parallel.mesh import device_mesh, use_mesh

    with open(os.path.join(_BENCH, "configs", "lr_criteo.json")) as f:
        config = json.load(f)
    batch = int(config["global_batch_size"])
    rows = 16 * batch
    params = {k: v for k, v in config.items() if not isinstance(v, dict)}
    params.update(config["generator_params"], rows=rows)
    data = _bench_module("generators", "criteo_zipf").generate(
        params, 2147483611)

    seen = []
    driver = S._run_minibatch_epochs

    def spy(update, arrays, *args, **kwargs):
        seen.append([np.asarray(a) for a in arrays])
        return driver(update, arrays, *args, **kwargs)

    monkeypatch.setattr(S, "_run_minibatch_epochs", spy)
    est = (LogisticRegression().set_num_features(config["num_features"])
           .set_global_batch_size(batch).set_max_iter(config["max_iter"])
           .set_tol(config["tol"]))
    with use_mesh(device_mesh(devices=[tpu])):
        model = est.fit(Table(data))
    assert model.planned_impl == "ell"

    steps, _, perm = S.plan_epoch_layout(rows, batch, 1, est.get_seed())
    assert steps == 16

    def epoch(a):
        return S.prepare_epoch_tensor(a, perm, steps, batch)

    lay = ell_layout(epoch(data["features_indices"]),
                     config["num_features"], device=False)
    assert lay.need_ovf.max() > 0 and lay.need_heavy.max() > 0
    want = [epoch(data["features_dense"]), lay.src, lay.pos, lay.mask,
            lay.ovf_idx, lay.ovf_src, lay.heavy_idx, lay.heavy_cnt,
            epoch(data["label"].astype(np.float32)),
            epoch(np.ones(rows, np.float32))]
    (got,) = seen
    assert len(got) == len(want)
    for i, (a, b) in enumerate(zip(got, want)):
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), i

    config["reference_params"]["batch"] = batch
    (model_data,) = model.get_model_data()
    compared = _bench_module("references", "logistic_sgd").compare(
        config, data, {"coefficients": model_data["coefficients"][0],
                       "intercept": model_data["intercept"][0],
                       "loss_log": np.asarray(model.loss_log)}, 0)
    for name, limit in config["limits"].items():
        assert compared[name] <= limit, (name, compared)
