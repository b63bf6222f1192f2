"""Gradient-boosted trees: trainer, classifier, regressor."""

import numpy as np
import pytest

from flink_ml_tpu import Table
from flink_ml_tpu.models.classification import GBTClassifier, GBTClassifierModel
from flink_ml_tpu.models.regression import GBTRegressor, GBTRegressorModel


def _xor_table(n=800, seed=0):
    """Nonlinear target a linear model cannot fit."""
    rng = np.random.default_rng(seed)
    X = rng.uniform(-1, 1, size=(n, 2))
    y = ((X[:, 0] > 0) ^ (X[:, 1] > 0)).astype(np.int64)
    return Table({"features": X, "label": y}), X, y


def _friedman(n=2000, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.uniform(size=(n, 5))
    y = (10 * np.sin(np.pi * X[:, 0] * X[:, 1]) + 20 * (X[:, 2] - 0.5) ** 2
         + 10 * X[:, 3] + 5 * X[:, 4])
    return Table({"features": X, "label": y}), X, y


def test_classifier_learns_xor():
    table, X, y = _xor_table()
    model = (GBTClassifier().set_max_iter(30).set_max_depth(3)
             .set_learning_rate(0.3).fit(table))
    out = model.transform(table)[0]
    pred = np.asarray(out["prediction"])
    assert (pred == y).mean() > 0.97
    probs = np.asarray(out["rawPrediction"])
    assert ((probs > 0.5) == (pred == 1)).all()
    assert probs.min() >= 0 and probs.max() <= 1


def test_classifier_label_values_preserved():
    table, X, y = _xor_table(n=400)
    relabeled = Table({"features": X, "label": np.where(y == 1, "yes", "no")})
    model = GBTClassifier().set_max_iter(20).set_max_depth(3).fit(relabeled)
    pred = np.asarray(model.transform(relabeled)[0]["prediction"])
    assert set(np.unique(pred)) <= {"yes", "no"}
    assert (pred == np.where(y == 1, "yes", "no")).mean() > 0.9


def test_classifier_routes_three_labels_to_softmax_path():
    table = Table({"features": np.random.default_rng(0).normal(size=(30, 2)),
                   "label": np.asarray([0, 1, 2] * 10)})
    model = GBTClassifier().set_max_iter(2).fit(table)
    assert model._soft is not None and model._soft.n_classes == 3


def test_regressor_beats_linear_on_friedman():
    table, X, y = _friedman()
    model = (GBTRegressor().set_max_iter(40).set_max_depth(4)
             .set_learning_rate(0.2).fit(table))
    pred = np.asarray(model.transform(table)[0]["prediction"])
    rmse = np.sqrt(np.mean((pred - y) ** 2))
    # linear least squares on the same data
    A = np.concatenate([X, np.ones((len(X), 1))], axis=1)
    lin = A @ np.linalg.lstsq(A, y, rcond=None)[0]
    lin_rmse = np.sqrt(np.mean((lin - y) ** 2))
    assert rmse < 0.5 * lin_rmse, (rmse, lin_rmse)


def test_regressor_monotone_improvement_with_trees():
    table, X, y = _friedman(n=500, seed=1)

    def rmse(trees):
        m = (GBTRegressor().set_max_iter(trees).set_max_depth(3)
             .set_learning_rate(0.3).fit(table))
        p = np.asarray(m.transform(table)[0]["prediction"])
        return np.sqrt(np.mean((p - y) ** 2))

    assert rmse(30) < rmse(5) < rmse(1)


def test_constant_labels_yield_constant_prediction():
    X = np.random.default_rng(0).normal(size=(50, 3))
    table = Table({"features": X, "label": np.full(50, 7.0)})
    model = GBTRegressor().set_max_iter(5).fit(table)
    pred = np.asarray(model.transform(table)[0]["prediction"])
    np.testing.assert_allclose(pred, 7.0, atol=1e-3)


def test_save_load_round_trip(tmp_path):
    table, X, y = _xor_table(n=300)
    model = GBTClassifier().set_max_iter(10).set_max_depth(3).fit(table)
    p1 = np.asarray(model.transform(table)[0]["prediction"])
    model.save(str(tmp_path / "c"))
    re = GBTClassifierModel.load(str(tmp_path / "c"))
    p2 = np.asarray(re.transform(table)[0]["prediction"])
    np.testing.assert_array_equal(p1, p2)

    rtable, _, ry = _friedman(n=300)
    rmodel = GBTRegressor().set_max_iter(8).fit(rtable)
    r1 = np.asarray(rmodel.transform(rtable)[0]["prediction"])
    rmodel.save(str(tmp_path / "r"))
    rre = GBTRegressorModel.load(str(tmp_path / "r"))
    np.testing.assert_allclose(
        np.asarray(rre.transform(rtable)[0]["prediction"]), r1)


def test_model_data_round_trip():
    table, X, y = _xor_table(n=200)
    model = GBTClassifier().set_max_iter(5).set_max_depth(2).fit(table)
    rebuilt = GBTClassifierModel().set_model_data(*model.get_model_data())
    rebuilt.copy_params_from(model)
    np.testing.assert_array_equal(
        np.asarray(rebuilt.transform(table)[0]["prediction"]),
        np.asarray(model.transform(table)[0]["prediction"]))


def test_unseen_data_generalizes():
    table, X, y = _xor_table(n=1000, seed=2)
    model = (GBTClassifier().set_max_iter(30).set_max_depth(3)
             .set_learning_rate(0.3).fit(table))
    _, X2, y2 = _xor_table(n=500, seed=99)
    pred = np.asarray(model.transform(Table({"features": X2}))[0]["prediction"])
    assert (pred == y2).mean() > 0.95


def test_empty_fit_rejected():
    with pytest.raises(ValueError):
        GBTRegressor().fit(Table({"features": np.zeros((0, 2)),
                                  "label": np.zeros(0)}))


# ------------------------------------------------------------- multiclass


def test_gbt_multiclass_three_rings(rng):
    """3 well-separated blobs; softmax GBT must classify near-perfectly."""
    from flink_ml_tpu.models.classification import GBTClassifier

    n = 120
    centers = np.asarray([[0.0, 0.0], [6.0, 0.0], [0.0, 6.0]])
    X = np.concatenate([rng.normal(size=(n, 2)) * 0.5 + c for c in centers])
    y = np.repeat(["alpha", "beta", "gamma"], n)
    t = Table({"features": X, "label": y})
    model = (GBTClassifier().set_max_iter(10).set_max_depth(3)
             .set_learning_rate(0.3).fit(t))
    out = model.transform(t)[0]
    pred = np.asarray(out["prediction"])
    assert (pred == y).mean() > 0.98
    probs = np.asarray(out["rawPrediction"])
    assert probs.shape == (3 * n, 3)
    np.testing.assert_allclose(probs.sum(axis=1), 1.0, atol=1e-9)


def test_gbt_multiclass_save_load_and_model_data(tmp_path, rng):
    from flink_ml_tpu.models.classification import (
        GBTClassifier,
        GBTClassifierModel,
    )

    X = rng.normal(size=(90, 3))
    y = (X[:, 0] > 0).astype(int) + (X[:, 1] > 0).astype(int)  # 3 classes
    t = Table({"features": X, "label": y})
    model = GBTClassifier().set_max_iter(4).set_max_depth(3).fit(t)
    pred = np.asarray(model.transform(t)[0]["prediction"])

    model.save(str(tmp_path / "m"))
    re = GBTClassifierModel.load(str(tmp_path / "m"))
    np.testing.assert_array_equal(
        np.asarray(re.transform(t)[0]["prediction"]), pred)

    # model-data round trip through Tables
    fresh = GBTClassifierModel().set_model_data(*model.get_model_data())
    fresh.copy_params_from(model)
    np.testing.assert_array_equal(
        np.asarray(fresh.transform(t)[0]["prediction"]), pred)


def test_gbt_binary_still_binary(rng):
    """2-label input keeps the logistic path (scalar margins)."""
    from flink_ml_tpu.models.classification import GBTClassifier

    X = rng.normal(size=(80, 2))
    y = (X[:, 0] > 0).astype(int)
    model = (GBTClassifier().set_max_iter(5)
             .fit(Table({"features": X, "label": y})))
    assert model._soft is None
    probs = np.asarray(model.transform(
        Table({"features": X, "label": y}))[0]["rawPrediction"])
    assert probs.ndim == 1


def test_set_model_data_replaces_representation(rng):
    """Installing binary model data on a model that held a multiclass forest
    (or vice versa) fully replaces it — no stale forest answers."""
    from flink_ml_tpu.models.classification import GBTClassifier

    X = rng.normal(size=(90, 2))
    t3 = Table({"features": X,
                "label": (X[:, 0] > 0).astype(int) + (X[:, 1] > 0).astype(int)})
    t2 = Table({"features": X, "label": (X[:, 0] > 0).astype(int)})
    m3 = GBTClassifier().set_max_iter(3).fit(t3)
    m2 = GBTClassifier().set_max_iter(3).fit(t2)

    m3.set_model_data(*m2.get_model_data())
    assert m3._soft is None
    pred = np.asarray(m3.transform(t2)[0]["prediction"])
    np.testing.assert_array_equal(pred,
                                  np.asarray(m2.transform(t2)[0]["prediction"]))

    m2.set_model_data(*GBTClassifier().set_max_iter(3).fit(t3)
                      .get_model_data())
    assert m2._soft is not None and m2._forest is None
    assert set(np.asarray(m2.transform(t3)[0]["prediction"])) <= {0, 1, 2}


class TestOutOfCore:
    """train_forest_outofcore == train_forest on the same rows (VERDICT r2
    task 9): identical tree STRUCTURE (exact int match on features and
    thresholds), allclose values/predictions."""

    def _data(self, n=3000, d=6):
        rng = np.random.default_rng(11)
        X = rng.normal(size=(n, d))
        y = ((X[:, 0] + 0.5 * X[:, 1] ** 2 + 0.1 * rng.normal(size=n))
             > 0.4).astype(np.float64)
        return X, y

    def test_matches_incore_forest(self, tmp_path):
        from flink_ml_tpu.models.common.gbt import (
            GBTConfig, predict_forest, train_forest,
            train_forest_outofcore)

        X, y = self._data()
        cfg = GBTConfig(num_trees=5, max_depth=3, max_bins=32)

        def grad_hess(yv, pred):
            p = 1.0 / (1.0 + np.exp(-pred))
            return p - yv, np.maximum(p * (1.0 - p), 1e-12)

        incore, _ = train_forest(X, y, "logistic", 0.0, cfg)

        def make_reader(batch=700):
            def gen():
                for s in range(0, len(X), batch):
                    yield {"features": X[s:s + batch],
                           "label": y[s:s + batch]}
            return gen()

        ooc = train_forest_outofcore(
            make_reader, grad_hess, 0.0, cfg,
            work_dir=str(tmp_path / "w"), sample_rows=len(X))

        np.testing.assert_array_equal(ooc.feature, incore.feature)
        np.testing.assert_array_equal(ooc.threshold, incore.threshold)
        np.testing.assert_allclose(ooc.value, incore.value,
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(predict_forest(X, ooc),
                                   predict_forest(X, incore),
                                   rtol=1e-4, atol=1e-5)

    def test_estimator_fit_outofcore(self, tmp_path):
        from flink_ml_tpu.data.table import Table
        from flink_ml_tpu.models.classification.gbtclassifier import (
            GBTClassifier)

        X, y = self._data(n=2000)
        t = Table({"features": X, "label": y})

        def make_reader():
            def gen():
                for s in range(0, len(X), 500):
                    yield {"features": X[s:s + 500], "label": y[s:s + 500]}
            return gen()

        est = (GBTClassifier().set_max_iter(5).set_max_depth(3)
               .set_max_bins(32))
        m_ooc = est.fit_outofcore(make_reader,
                                  work_dir=str(tmp_path / "w2"))
        m_in = est.fit(t)
        pred_ooc = np.asarray(
            m_ooc.transform(t)[0][est.get_prediction_col()]).ravel()
        pred_in = np.asarray(
            m_in.transform(t)[0][est.get_prediction_col()]).ravel()
        np.testing.assert_array_equal(pred_ooc, pred_in)
        acc = (pred_ooc == y).mean()
        assert acc > 0.9, acc

    def test_streaming_rejects_arbitrary_labels(self, tmp_path):
        from flink_ml_tpu.models.classification.gbtclassifier import (
            GBTClassifier)

        X, _ = self._data(n=100)
        y = np.where(X[:, 0] > 0, 3.0, 7.0)

        def make_reader():
            return iter([{"features": X, "label": y}])

        with pytest.raises(ValueError, match="0/1 labels"):
            GBTClassifier().fit_outofcore(make_reader,
                                          work_dir=str(tmp_path / "w3"))

    def test_device_binning_matches_host(self):
        import jax.numpy as jnp

        from flink_ml_tpu.models.common.gbt import (
            apply_bins, apply_bins_device, bin_features)

        rng = np.random.default_rng(3)
        X = rng.normal(size=(500, 4))
        X[:, 2] = np.round(X[:, 2])          # ties on edges
        _, edges = bin_features(X, 16)
        host = apply_bins(X.astype(np.float32), edges)
        dev = np.asarray(apply_bins_device(
            jnp.asarray(X, jnp.float32), jnp.asarray(edges, jnp.float32)))
        np.testing.assert_array_equal(host, dev)


def test_device_binning_nan_matches_host():
    import jax.numpy as jnp

    from flink_ml_tpu.models.common.gbt import (
        apply_bins, apply_bins_device, quantile_edges)

    rng = np.random.default_rng(4)
    X = rng.normal(size=(200, 3))
    edges = quantile_edges(X, 8)
    X[5, 0] = np.nan
    X[17, 2] = np.nan
    host = apply_bins(X, edges)
    dev = np.asarray(apply_bins_device(
        jnp.asarray(X, jnp.float32), jnp.asarray(edges, jnp.float32)))
    np.testing.assert_array_equal(host, dev)


def test_outofcore_workdir_reusable_and_cleaned(tmp_path):
    from flink_ml_tpu.models.common.gbt import (
        GBTConfig, train_forest_outofcore)

    rng = np.random.default_rng(5)
    X = rng.normal(size=(300, 3))
    y = (X[:, 0] > 0).astype(np.float64)

    def grad_hess(yv, pred):
        p = 1.0 / (1.0 + np.exp(-pred))
        return p - yv, np.maximum(p * (1.0 - p), 1e-12)

    def make_reader():
        return iter([{"features": X, "label": y}])

    wd = str(tmp_path / "work")
    cfg = GBTConfig(num_trees=2, max_depth=2, max_bins=8)
    for _ in range(2):   # same work_dir twice must not collide
        train_forest_outofcore(make_reader, grad_hess, 0.0, cfg,
                               work_dir=wd)
    import os
    assert os.listdir(wd) == []   # run dirs removed on return


def test_mxu_histograms_match_segsum():
    """The MXU one-hot contraction (the Pallas kernel, interpreted here)
    must equal the segment_sum form to float32 summation order —
    including dead rows (-1) and empty nodes — and grow the same forest
    end-to-end."""
    import jax.numpy as jnp

    from flink_ml_tpu.models.common import gbt

    rng = np.random.default_rng(21)
    n, d, bins, n_nodes = 512, 5, 16, 4
    cols = tuple(jnp.asarray(rng.integers(0, bins, size=n), jnp.int32)
                 for _ in range(d))
    ids = jnp.asarray(
        np.where(rng.random(n) < 0.2, -1,
                 rng.integers(0, n_nodes, size=n)), jnp.int32)
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    h = jnp.asarray(rng.random(n) + 0.1, jnp.float32)
    gs, hs = gbt._level_histograms_segsum(cols, ids, g, h, n_nodes, d,
                                          bins)
    gm, hm = gbt._level_histograms_pallas(cols, ids, g, h, n_nodes, d,
                                          bins)
    np.testing.assert_allclose(np.asarray(gm), np.asarray(gs),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(hm), np.asarray(hs),
                               rtol=1e-5, atol=1e-5)

    # end-to-end: the two impls grow the same forest
    X = rng.normal(size=(1024, 4))
    y = (X[:, 0] + X[:, 1] > 0).astype(np.float64)

    cfg = gbt.GBTConfig(num_trees=3, max_depth=3)
    old = gbt.HIST_IMPL
    try:
        gbt.HIST_IMPL = "segsum"
        f1, impl1 = gbt.train_forest(X, y, "logistic", 0.0, cfg)
        gbt.HIST_IMPL = "pallas"
        f2, impl2 = gbt.train_forest(X, y, "logistic", 0.0, cfg)
    finally:
        gbt.HIST_IMPL = old
    assert (impl1, impl2) == ("segsum", "pallas")
    np.testing.assert_array_equal(f1.feature, f2.feature)
    np.testing.assert_array_equal(f1.threshold, f2.threshold)
    np.testing.assert_allclose(gbt.predict_forest(X, f1),
                               gbt.predict_forest(X, f2),
                               rtol=1e-5, atol=1e-5)
    # unknown impl names fail loudly, never silently fall back
    try:
        gbt.HIST_IMPL = "typo"
        with pytest.raises(KeyError):
            gbt._level_histograms(cols, ids, g, h, n_nodes, d, bins)
    finally:
        gbt.HIST_IMPL = old


# ------------------------------------------------- the fused binary fit

def _reference():
    """``benchmarks/references/gbt_hist.py``: the plain reference the
    benchmark's ``gbt_airline.fit`` compares with (it imports nothing of
    the program)."""
    import importlib
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    return importlib.import_module("benchmarks.references.gbt_hist")


def _airline_like(n=6000, d=5, seed=3):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    X[:, 1] = np.round(X[:, 1] * 2)            # a few distinct values
    logit = 1.5 * X[:, 0] - X[:, 1] * (X[:, 2] > 0) + 0.5 * X[:, 3] ** 2 - 0.4
    y = (rng.random(n) < 1 / (1 + np.exp(-logit))).astype(np.float32)
    return X, y


_REF_PARAMS = {"depth": 4, "bins": 16, "trees": 6, "lr": 0.3, "lam": 1.0,
               "mcw": 1e-3, "sample": 1 << 18, "block": 2048,
               "precision": "highest"}


def _fused_and_reference(hist_impl):
    from flink_ml_tpu.models.common import gbt

    X, y = _airline_like()
    p = _REF_PARAMS
    ref = _reference().boost(X, y, p)
    cfg = gbt.GBTConfig(num_trees=p["trees"], max_depth=p["depth"],
                        max_bins=p["bins"], learning_rate=p["lr"],
                        reg_lambda=p["lam"], min_child_weight=p["mcw"])
    old = gbt.HIST_IMPL
    try:
        gbt.HIST_IMPL = hist_impl
        forest, impl = gbt.train_forest(X, y, "logistic", ref["baseScore"],
                                        cfg)
    finally:
        gbt.HIST_IMPL = old
    assert impl == hist_impl
    return X, y, forest, ref


@pytest.mark.parametrize("hist_impl", ["segsum", "pallas"])
def test_fused_fit_matches_the_plain_reference(hist_impl):
    """Every tree's split features and thresholds exactly as the plain
    reference's (continuous features: no two splits tie in exact
    arithmetic), leaves and margins within 1e-5: both sum the same float32
    values in other orders (the reference's blocks of rows, the program's
    histograms), a relative 1e-7 a sum here."""
    X, y, forest, ref = _fused_and_reference(hist_impl)
    np.testing.assert_array_equal(forest.feature, ref["feature"])
    np.testing.assert_array_equal(
        np.where(forest.feature >= 0, forest.threshold, 0),
        np.where(ref["feature"] >= 0, ref["threshold"], 0))
    np.testing.assert_allclose(forest.value, ref["value"], atol=1e-5)
    np.testing.assert_array_equal(forest.bin_edges, ref["binEdges"])
    from flink_ml_tpu.models.common.gbt import predict_forest

    m_ref = _reference().margins(
        ref, _reference().bin_table(X, ref["binEdges"], len(X)),
        _REF_PARAMS["depth"])
    np.testing.assert_allclose(predict_forest(X, forest), np.asarray(m_ref),
                               atol=1e-5)


def _airline_limits():
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "gbt_airline.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("kind", ["sound", "control", "half_rows", "start",
                                  "tied_splits"])
def test_reference_comparison_fails_the_control_and_the_faults(kind):
    """``gbt_airline.fit``'s comparison at a small size: the fused fit
    passes every limit; the reference with bfloat16-rounded addends (what
    a one-hot contraction at the MXU's default precision sums), every
    second row left out, the start margins returned, and the reference's
    forest with its tied nodes' splits moved to their worst each read over
    one at least."""
    from flink_ml_tpu.models.classification import GBTClassifier

    ref_mod = _reference()
    config = _airline_limits()
    config["reference_params"].update(trees=6, block_rows=2048)
    data = {"features": _airline_like(n=8000, d=13, seed=9)[0]}
    data["label"] = _airline_like(n=8000, d=13, seed=9)[1]
    if kind == "sound":
        model = (GBTClassifier().set_max_iter(6).set_max_depth(5)
                 .set_max_bins(32).set_reg_lambda(config["reg_lambda"])
                 .fit(Table(data)))
        (t,) = model.get_model_data()[:1]
        answer = {c: np.asarray(t[c]) for c in ("feature", "threshold",
                                                "value")}
        answer.update(binEdges=np.asarray(t["binEdges"][0]),
                      baseScore=float(t["baseScore"][0]),
                      learningRate=float(t["learningRate"][0]))
    elif kind == "control":
        answer = ref_mod.control(config, data, 0)
    else:
        answer = ref_mod.fault(config, data, 0, kind)
    numbers = ref_mod.compare(config, data, answer, 0)
    over = [n for n, limit in config["limits"].items()
            if not numbers[n] <= limit]
    assert (not over) if kind == "sound" else over, (kind, numbers)


def test_histogram_kernel_matches_segsum_ragged_and_wide():
    """The Pallas histogram (interpreted) against segment_sum where the
    rows are no multiple of the kernel's block and the (feature, bin)
    one-hot takes two matmuls a block (64 bins x 13 features)."""
    import jax.numpy as jnp

    from flink_ml_tpu.models.common import gbt

    rng = np.random.default_rng(8)
    n, d, bins, n_nodes = 5000, 13, 64, 8
    cols = tuple(jnp.asarray(rng.integers(0, bins, size=n), jnp.int32)
                 for _ in range(d))
    ids = jnp.asarray(np.where(rng.random(n) < 0.1, -1,
                               rng.integers(0, n_nodes, size=n)), jnp.int32)
    g = jnp.asarray(rng.normal(size=n) * 3, jnp.float32)
    h = jnp.asarray(rng.random(n), jnp.float32)
    want = gbt._level_histograms_segsum(cols, ids, g, h, n_nodes, d, bins)
    got = gbt._level_histograms_pallas(cols, ids, g, h, n_nodes, d, bins)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=2e-5)


def test_exact_bf16_parts_sum_to_the_float32_value():
    """Three bfloat16 values carry a float32 value exactly, where its last
    part is a normal float (the value above about 1e-31)."""
    import jax.numpy as jnp

    from flink_ml_tpu.ops.gbt_hist_pallas import _exact_bf16_parts

    rng = np.random.default_rng(2)
    x = np.concatenate([rng.normal(size=4096) * 10.0 ** rng.integers(
        -20, 30, 4096), [0.0, -0.0, 1e-12, 3.0e38]]).astype(np.float32)
    parts = _exact_bf16_parts(jnp.asarray(x))
    for part in parts:
        p = np.asarray(part)
        assert np.array_equal(p, np.asarray(jnp.asarray(p).astype(
            jnp.bfloat16).astype(jnp.float32)))
    total = (np.asarray(parts[0], np.float64) + np.asarray(parts[1])
             + np.asarray(parts[2]))
    np.testing.assert_array_equal(total.astype(np.float32), x)


def test_native_binning_is_the_numpy_rule():
    """``bin_columns`` (``native/gbt_bin.cpp`` where it builds) gives
    ``np.searchsorted(edges, x, side="left")`` of every float32 value
    against float64 edges — values on, just under and just over an edge,
    infinities, NaN — laid out feature-major with zero padding."""
    from flink_ml_tpu.models.common import gbt

    rng = np.random.default_rng(6)
    X = rng.normal(size=(20_000, 4)).astype(np.float32)
    X[:, 2] = np.round(X[:, 2])
    edges = gbt.quantile_edges(X, 32)
    # the float32 values on either side of an edge between two of them,
    # and one on an edge
    below = np.float32(edges[0, 5])
    if np.float64(below) > edges[0, 5]:
        below = np.nextafter(below, np.float32(-np.inf))
    X[7, 0], X[8, 0] = below, np.nextafter(below, np.float32(np.inf))
    X[9, 0] = edges[0, 6] = np.float32(edges[0, 6])
    X[:3, 1] = (np.inf, -np.inf, np.nan)
    got = gbt.bin_columns(X, edges, 20_480)
    want = gbt.apply_bins(X, edges).T
    assert got.shape == (4, 20_480) and got.dtype == np.int32
    np.testing.assert_array_equal(got[:, :20_000], want)
    assert not got[:, 20_000:].any()
    assert got[1, 2] == 31                          # NaN: the last bin


def test_edges_come_from_a_strided_sample_of_a_large_table(monkeypatch):
    from flink_ml_tpu.models.common import gbt

    monkeypatch.setattr(gbt, "EDGE_SAMPLE_ROWS", 100)
    X = np.arange(1000, dtype=np.float32)[:, None]
    np.testing.assert_array_equal(gbt.edge_sample(X)[:, 0],
                                  np.arange(0, 1000, 10))
    _, edges = gbt.bin_features(X, 4)
    np.testing.assert_array_equal(
        edges, gbt.quantile_edges(X[::10], 4))


def test_fit_notes_its_plan_and_spans_and_reuses_its_program(
        fit_noting_reuse):
    """The binary fit is one fused program: a second fit of the same
    shapes in the process enqueues the kept executable; the model names
    the histogram backend; the root span holds the phases the benchmark
    reads."""
    from flink_ml_tpu.models.classification import GBTClassifier
    from flink_ml_tpu.obs.trace import tracer

    X, y = _airline_like(n=3000)
    table = Table({"features": X, "label": y})

    def est():
        return GBTClassifier().set_max_iter(3).set_max_depth(3)

    first, reused_first = fit_noting_reuse(est(), table)
    second, reused_second = fit_noting_reuse(est(), table)
    assert (reused_first, reused_second) == (0, 1)
    assert first.hist_impl == "segsum"
    np.testing.assert_array_equal(second._forest.value, first._forest.value)
    tracer.enable()
    try:
        est().fit(table)
        names = {s.name for s in tracer.find()}
        (arrange,) = [s for s in tracer.find("fit.arrange")]
    finally:
        tracer.disable()
        tracer.clear()
    assert {"fit", "fit.gather", "fit.arrange", "fit.arrange.bin",
            "fit.upload", "iterate.dispatch", "fit.fetch"} <= names
    assert (arrange.ids["hist_impl"], arrange.ids["trees"]) == ("segsum", 3)


def test_regressor_fit_is_the_fused_squared_loss():
    from flink_ml_tpu.models.common import gbt

    table, X, y = _friedman(n=600, seed=4)
    model = (GBTRegressor().set_max_iter(5).set_max_depth(3)
             .set_learning_rate(0.3).fit(table))
    forest, _ = gbt.train_forest(
        X.astype(np.float32), y.astype(np.float32), "squared",
        float(np.mean(y.astype(np.float32), dtype=np.float64)),
        gbt.GBTConfig(num_trees=5, max_depth=3, learning_rate=0.3))
    np.testing.assert_array_equal(model._forest.feature, forest.feature)
    np.testing.assert_allclose(model._forest.value, forest.value, atol=1e-6)
