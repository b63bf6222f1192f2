"""Model selection (`api/model_selection.py`): ParamGridBuilder grids,
CrossValidator fold mechanics + best-candidate selection + full-table
refit, TrainValidationSplit, metric direction, error probes."""

import numpy as np
import pytest

from flink_ml_tpu import Table
from flink_ml_tpu.api.model_selection import (
    CrossValidator,
    CrossValidatorModel,
    ParamGridBuilder,
    TrainValidationSplit,
)
from flink_ml_tpu.models.classification import LogisticRegression
from flink_ml_tpu.models.evaluation.binary_evaluator import (
    BinaryClassificationEvaluator,
)


def _data(n=400, d=4, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d))
    y = (X[:, 0] - 0.5 * X[:, 1] > 0).astype(np.float64)
    return Table({"features": X, "label": y})


def _lr():
    return (LogisticRegression().set_max_iter(15).set_learning_rate(0.5)
            .set_global_batch_size(128))


def _auc_eval():
    return (BinaryClassificationEvaluator()
            .set_raw_prediction_col("rawPrediction")
            .set_metrics("areaUnderROC"))


class TestParamGridBuilder:
    def test_cartesian_product(self):
        grid = (ParamGridBuilder()
                .add_grid(LogisticRegression.REG, [0.0, 0.1])
                .add_grid(LogisticRegression.MAX_ITER, [5, 10, 20])
                .build())
        assert len(grid) == 6
        regs = {g[LogisticRegression.REG] for g in grid}
        assert regs == {0.0, 0.1}

    def test_empty_builder_is_single_default(self):
        assert ParamGridBuilder().build() == [{}]

    def test_rejects_non_param(self):
        with pytest.raises(TypeError):
            ParamGridBuilder().add_grid("reg", [1])
        with pytest.raises(ValueError):
            ParamGridBuilder().add_grid(LogisticRegression.REG, [])


class TestCrossValidator:
    def test_selects_sane_candidate_and_refits(self):
        t = _data()
        # candidate 0 is crippled (1 iteration, tiny lr); candidate 1 real
        grid = [
            {LogisticRegression.MAX_ITER: 1,
             LogisticRegression.LEARNING_RATE: 1e-4},
            {LogisticRegression.MAX_ITER: 20,
             LogisticRegression.LEARNING_RATE: 0.5},
        ]
        cv = (CrossValidator(_lr(), _auc_eval(), grid)
              .set_num_folds(3).set_seed(7))
        model = cv.fit(t)
        assert isinstance(model, CrossValidatorModel)
        assert model.best_index == 1
        assert len(model.avg_metrics) == 2
        assert model.avg_metrics[1] > model.avg_metrics[0]
        # refit-on-all-rows model predicts well
        pred = np.asarray(model.transform(t)[0]["prediction"]).ravel()
        assert (pred == np.asarray(t["label"])).mean() > 0.9

    def test_fold_partition_is_exact(self):
        t = _data(n=103)
        cv = CrossValidator(_lr(), _auc_eval()).set_num_folds(4).set_seed(1)
        splits = cv._splits(t)
        assert len(splits) == 4
        val_rows = sum(v.num_rows for _, v in splits)
        assert val_rows == 103                       # folds cover all rows
        for train, val in splits:
            assert train.num_rows + val.num_rows == 103
        # validation folds are disjoint (feature rows unique per fold)
        seen = np.concatenate(
            [np.asarray(v["features"])[:, 0] for _, v in splits])
        assert len(np.unique(seen)) == 103

    def test_minimizing_metric_direction(self):
        # with largerIsBetter=false the crippled candidate "wins"
        t = _data()
        grid = [
            {LogisticRegression.MAX_ITER: 1,
             LogisticRegression.LEARNING_RATE: 1e-4},
            {LogisticRegression.MAX_ITER: 20,
             LogisticRegression.LEARNING_RATE: 0.5},
        ]
        cv = (CrossValidator(_lr(), _auc_eval(), grid)
              .set_num_folds(2).set_larger_is_better(False))
        assert cv.fit(t).best_index == 0

    def test_too_few_rows_rejected(self):
        cv = CrossValidator(_lr(), _auc_eval()).set_num_folds(5)
        with pytest.raises(ValueError, match="folds"):
            cv.fit(_data(n=3))

    def test_missing_pieces_rejected(self):
        with pytest.raises(ValueError, match="set_estimator"):
            CrossValidator().fit(_data())

    def test_model_save_delegates_to_best(self, tmp_path):
        from flink_ml_tpu.models.classification import (
            LogisticRegressionModel)

        t = _data()
        model = CrossValidator(_lr(), _auc_eval()).set_num_folds(2).fit(t)
        path = str(tmp_path / "best")
        model.save(path)
        loaded = LogisticRegressionModel.load(path)
        np.testing.assert_array_equal(
            np.asarray(loaded.transform(t)[0]["prediction"]),
            np.asarray(model.transform(t)[0]["prediction"]))


class TestTrainValidationSplit:
    def test_single_split_selection(self):
        t = _data()
        grid = [
            {LogisticRegression.MAX_ITER: 1,
             LogisticRegression.LEARNING_RATE: 1e-4},
            {LogisticRegression.MAX_ITER: 20,
             LogisticRegression.LEARNING_RATE: 0.5},
        ]
        tvs = (TrainValidationSplit(_lr(), _auc_eval(), grid)
               .set_train_ratio(0.7).set_seed(3))
        model = tvs.fit(t)
        assert model.best_index == 1
        (train, val), = tvs._splits(t)
        assert train.num_rows == 280 and val.num_rows == 120

    def test_degenerate_ratio_rejected(self):
        tvs = (TrainValidationSplit(_lr(), _auc_eval())
               .set_train_ratio(0.001))
        with pytest.raises(ValueError, match="empty split"):
            tvs.fit(_data(n=10))

def test_root_exports_and_bool_param():
    import flink_ml_tpu as fm

    assert fm.CrossValidator is CrossValidator
    assert fm.ParamGridBuilder is ParamGridBuilder
    cv = CrossValidator().set(CrossValidator.LARGER_IS_BETTER, False)
    assert cv.get(CrossValidator.LARGER_IS_BETTER) is False


def test_add_grid_repeated_param_replaces():
    grid = (ParamGridBuilder()
            .add_grid(LogisticRegression.REG, [0.0, 1.0])
            .add_grid(LogisticRegression.REG, [2.0, 3.0])
            .build())
    assert [g[LogisticRegression.REG] for g in grid] == [2.0, 3.0]


def test_cv_over_pipeline_clones_children():
    from flink_ml_tpu import Pipeline
    from flink_ml_tpu.models.feature.scalers import StandardScaler

    t = _data()
    grid = (ParamGridBuilder()
            .add_grid(LogisticRegression.MAX_ITER, [1, 20])
            .build())
    pipe = Pipeline([StandardScaler().set_output_col("features"),
                     _lr()])
    cv = (CrossValidator(pipe, _auc_eval(), grid)
          .set_num_folds(2).set_seed(2))
    model = cv.fit(t)
    assert model.best_params[LogisticRegression.MAX_ITER] == 20
    pred = np.asarray(model.transform(t)[0]["prediction"]).ravel()
    assert (pred == np.asarray(t["label"])).mean() > 0.9
    # the original pipeline's children are untouched by candidate fits
    assert pipe.stages[1].get_max_iter() == 15


def test_cv_pipeline_unknown_grid_param_rejected():
    from flink_ml_tpu import Pipeline
    from flink_ml_tpu.models.clustering.kmeans import KMeansParams

    pipe = Pipeline([_lr()])
    cv = CrossValidator(pipe, _auc_eval(),
                        [{KMeansParams.K: 4}]).set_num_folds(2)
    with pytest.raises(ValueError, match="matches no pipeline stage"):
        cv.fit(_data())


def test_cv_pipeline_nested_and_shared_mixin_binding():
    from flink_ml_tpu import Pipeline
    from flink_ml_tpu.models.feature.scalers import StandardScaler

    t = _data()
    # nested pipeline; maxIter (HasMaxIter mixin) binds into the inner LR
    inner = Pipeline([_lr()])
    pipe = Pipeline([StandardScaler().set_output_col("features"), inner])
    grid = (ParamGridBuilder()
            .add_grid(LogisticRegression.MAX_ITER, [1, 20]).build())
    model = (CrossValidator(pipe, _auc_eval(), grid)
             .set_num_folds(2).set_seed(4).fit(t))
    assert model.best_params[LogisticRegression.MAX_ITER] == 20


def test_cv_pipeline_tuple_key_pins_one_child():
    from flink_ml_tpu import Pipeline
    from flink_ml_tpu.models.feature.scalers import StandardScaler
    from flink_ml_tpu.params.shared import HasFeaturesCol

    # featuresCol is a SHARED mixin param: a bare key would hit both
    # children; the tuple key pins it to the LR child only
    t = _data().with_column("feat2", np.asarray(_data()["features"]))
    pipe = Pipeline([StandardScaler().set_output_col("scaled"), _lr()])
    grid = [{(1, HasFeaturesCol.FEATURES_COL): "scaled"}]
    model = (CrossValidator(pipe, _auc_eval(), grid)
             .set_num_folds(2).fit(t))
    # the scaler child still reads the raw column (params untouched)
    assert pipe.stages[0].get_features_col() == "features"
    pred = np.asarray(model.transform(t)[0]["prediction"]).ravel()
    assert (pred == np.asarray(t["label"])).mean() > 0.9


def test_cv_pipeline_reuses_transformer_children():
    from flink_ml_tpu import Pipeline
    from flink_ml_tpu.models.feature.scalers import StandardScaler

    t = _data()
    # a FITTED model child must pass through with its model data intact
    scaler_model = (StandardScaler().set_output_col("features").fit(t))
    pipe = Pipeline([scaler_model, _lr()])
    grid = (ParamGridBuilder()
            .add_grid(LogisticRegression.MAX_ITER, [1, 20]).build())
    model = (CrossValidator(pipe, _auc_eval(), grid)
             .set_num_folds(2).fit(t))
    assert model.best_params[LogisticRegression.MAX_ITER] == 20


def test_cv_pipeline_transformer_grid_param_does_not_mutate_original():
    """ADVICE r3: a grid key targeting a plain TRANSFORMER child must
    bind on a per-candidate clone — never on the caller's original stage
    (and candidates must not share one mutable transformer)."""
    from flink_ml_tpu import Pipeline
    from flink_ml_tpu.api.model_selection import _clone_with
    from flink_ml_tpu.models.feature.transforms import Normalizer

    t = _data()
    norm = Normalizer().set_p(2.0).set_output_col("features")
    pipe = Pipeline([norm, _lr()])
    grid = (ParamGridBuilder()
            .add_grid(Normalizer.P, [1.0, 3.0])
            .add_grid(LogisticRegression.MAX_ITER, [1, 20]).build())

    # direct clone surface: binding P must not touch the original
    c = _clone_with(pipe, {Normalizer.P: 1.0})
    assert c.stages[0].get_p() == 1.0
    assert norm.get_p() == 2.0
    assert c.stages[0] is not norm

    # nested pipeline: the same guarantee one level down
    outer = Pipeline([Pipeline([norm]), _lr()])
    c2 = _clone_with(outer, {Normalizer.P: 3.0})
    assert c2.stages[0].stages[0].get_p() == 3.0
    assert norm.get_p() == 2.0

    # full CV run leaves the original untouched too
    model = (CrossValidator(pipe, _auc_eval(), grid)
             .set_num_folds(2).set_seed(5).fit(t))
    assert norm.get_p() == 2.0
    assert pipe.stages[1].get_max_iter() == 15
    assert model.best_params[Normalizer.P] in (1.0, 3.0)


def test_cv_pipeline_fused_scoring_reuses_compiled_segments():
    """Pipeline candidates score through the fused chain (`api/chain.py`):
    fold metrics are identical to the stagewise path, and because the
    segment jit is plan-static with fold params as runtime device args,
    a whole repeat grid x fold sweep at the same shapes adds ZERO new
    XLA lowerings — fold models share one compiled program per
    (schema, bucket) instead of recompiling per fold."""
    from flink_ml_tpu.utils.backend import count_compiles

    from flink_ml_tpu import Pipeline
    from flink_ml_tpu.api import chain
    from flink_ml_tpu.models.feature.scalers import StandardScaler

    t = _data(n=400)
    grid = (ParamGridBuilder()
            .add_grid(LogisticRegression.MAX_ITER, [2, 8]).build())

    def _cv():
        pipe = Pipeline([StandardScaler().set_output_col("features"),
                         _lr()])
        return (CrossValidator(pipe, _auc_eval(), grid)
                .set_num_folds(4).set_seed(6))

    with chain.chain_disabled():
        ref = _cv().fit(t)
    fused = _cv().fit(t)
    assert fused.avg_metrics == ref.avg_metrics   # fold metrics unchanged
    assert fused.best_index == ref.best_index

    # scoring-side compile reuse: one fitted pipeline per fold (distinct
    # fitted arrays, identical stage types / columns / shapes), fold 1
    # warms the (schema, bucket) segment compiles, every later fold's
    # scoring transform must hit them — the fit-side `sgd` compiles stay
    # outside the counter (they are per-fit and predate the chain)
    folds = []
    for train, val in _cv()._splits(t):
        pipe = Pipeline([StandardScaler().set_output_col("features"),
                         _lr().set_max_iter(2)])
        folds.append((pipe.fit(train), val))
    m0, v0 = folds[0]
    m0.transform(v0)                        # warm fold
    with count_compiles() as count:
        preds = [m.transform(v)[0] for m, v in folds]
    assert count() == 0, (
        f"{count()} new XLA lowerings across fold scoring — fold "
        "models are not sharing the plan-static segment compiles")
    for (m, v), pred in zip(folds, preds):
        with chain.chain_disabled():
            (sw,) = m.transform(v)
        for c in sw.column_names:
            assert np.array_equal(np.asarray(sw[c]), np.asarray(pred[c]))
