"""Operator chaining (`api/chain.py`): fused segment execution is
bit-exact with the stagewise path across every ported terminal family,
chain breaks land exactly at non-chainable stages (including the
zero-row edge), dispatch count drops to one per segment, steady state
adds zero XLA lowerings across warmed buckets, f64-vs-f32 inputs share
one compiled program, save->load round trips keep the fused path exact,
and one serving endpoint runs preprocess+score per micro-batch."""

import os

import numpy as np
import pytest

from flink_ml_tpu import PipelineModel, Table
from flink_ml_tpu.api import chain
from flink_ml_tpu.models.classification import GBTClassifier
from flink_ml_tpu.models.classification.logisticregression import (
    LogisticRegression,
)
from flink_ml_tpu.models.clustering.kmeans import KMeans
from flink_ml_tpu.models.feature.pca import PCA
from flink_ml_tpu.models.feature.randomsplitter import RandomSplitter
from flink_ml_tpu.models.feature.scalers import (
    MaxAbsScaler,
    MinMaxScaler,
    StandardScaler,
)
from flink_ml_tpu.models.feature.transforms import Binarizer, Normalizer
from flink_ml_tpu.models.recommendation.widedeep import WideDeep
from flink_ml_tpu.serving import serve_model


def _table(n=120, d=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d))
    y = (X[:, 0] > 0).astype(np.int64)
    return Table({"features": X, "label": y})


def _scaler_chain(table):
    """std -> minmax -> maxabs, each feeding the next column."""
    s1 = StandardScaler().set_output_col("std").fit(table)
    t1 = s1.transform(table)[0]
    s2 = (MinMaxScaler().set_features_col("std").set_output_col("mm")
          .fit(t1))
    t2 = s2.transform(t1)[0]
    s3 = (MaxAbsScaler().set_features_col("mm").set_output_col("ma")
          .fit(t2))
    return [s1, s2, s3], s3.transform(t2)[0]


def _assert_tables_equal(ref, out, cols=None):
    for name in (cols or ref.column_names):
        a, b = np.asarray(ref[name]), np.asarray(out[name])
        assert a.shape == b.shape, (name, a.shape, b.shape)
        assert np.array_equal(a, b), f"column {name!r} diverged"


def _ab(pm, *tables):
    """(stagewise, fused) outputs for the same inputs."""
    with chain.chain_disabled():
        ref = pm.transform(*tables)
    return ref, pm.transform(*tables)


# -- bit-exactness per terminal family ---------------------------------------

def test_fused_bitexact_linear_terminal():
    t = _table()
    stages, t3 = _scaler_chain(t)
    lr = (LogisticRegression().set_features_col("ma").set_max_iter(3)
          .fit(t3))
    pm = PipelineModel(stages + [lr])
    feats = t.drop("label")
    (ref,), (out,) = _ab(pm, feats)
    _assert_tables_equal(ref, out)
    plan = pm._chain_plan([feats])
    assert plan.describe() == [("segment", 4)]   # ONE fused program


def test_fused_bitexact_kmeans_terminal():
    t = _table(seed=3)
    stages, t3 = _scaler_chain(t)
    km = (KMeans().set_k(4).set_max_iter(3).set_features_col("ma")
          .fit(t3))
    pm = PipelineModel(stages + [km])
    feats = t.drop("label")
    (ref,), (out,) = _ab(pm, feats)
    _assert_tables_equal(ref, out)
    assert pm._chain_plan([feats]).describe() == [("segment", 4)]


def test_fused_bitexact_widedeep_terminal():
    rng = np.random.default_rng(6)
    n = 96
    dense = rng.normal(size=(n, 4)).astype(np.float32)
    cat = np.stack([rng.integers(0, 10, size=n),
                    rng.integers(0, 7, size=n)], axis=1).astype(np.int32)
    label = (cat[:, 0] > 4).astype(np.int64)
    t = Table({"denseFeatures": dense, "catFeatures": cat, "label": label})
    s1 = (StandardScaler().set_features_col("denseFeatures")
          .set_output_col("denseFeatures").fit(t))
    t1 = s1.transform(t)[0]
    s2 = (MaxAbsScaler().set_features_col("denseFeatures")
          .set_output_col("denseFeatures").fit(t1))
    t2 = s2.transform(t1)[0]
    s3 = (Normalizer().set_features_col("denseFeatures")
          .set_output_col("denseFeatures"))
    t3 = s3.transform(t2)[0]
    wd = WideDeep().set_vocab_sizes([10, 7]).set_max_iter(3).fit(t3)
    pm = PipelineModel([s1, s2, s3, wd])
    feats = t.drop("label")
    (ref,), (out,) = _ab(pm, feats)
    _assert_tables_equal(ref, out)
    assert pm._chain_plan([feats]).describe() == [("segment", 4)]

    # the categorical range check (WideDeep's host `pre`) still fires on
    # the fused path
    bad = Table({"denseFeatures": dense, "catFeatures": cat + 100})
    with pytest.raises(ValueError):
        pm.transform(bad)


def test_mixed_feature_chain_bitexact():
    """Longer chain through the elementwise transform kernels (Binarizer's
    f32 threshold surrogate included)."""
    t = _table(seed=9)
    s1 = StandardScaler().set_output_col("std").fit(t)
    t1 = s1.transform(t)[0]
    s2 = Binarizer().set_features_col("std").set_output_col("bin") \
        .set_threshold(0.25)
    t2 = s2.transform(t1)[0]
    s3 = Normalizer().set_features_col("std").set_output_col("norm")
    t3 = s3.transform(t2)[0]
    s4 = PCA().set_k(3).set_features_col("norm").set_output_col("pc") \
        .fit(t3)
    t4 = s4.transform(t3)[0]
    lr = (LogisticRegression().set_features_col("pc").set_max_iter(2)
          .fit(t4))
    pm = PipelineModel([s1, s2, s3, s4, lr])
    feats = t.drop("label")
    (ref,), (out,) = _ab(pm, feats)
    # Normalizer divides each row by its norm.  XLA:CPU (jax 0.9.0) rounds
    # that division differently inside the fused program than in the
    # stage's own (found: 2 ulp at most), so ``norm`` is held to 4 ulp,
    # and what is computed from its unit rows (the projection, the
    # margin's sigmoid) to 4 ulp of 1.0; every other column is bit-equal.
    exact = [c for c in ref.column_names
             if c not in ("norm", "pc", "rawPrediction")]
    _assert_tables_equal(ref, out, cols=exact)
    np.testing.assert_array_max_ulp(
        np.asarray(ref["norm"]), np.asarray(out["norm"]), maxulp=4)
    for name in ("pc", "rawPrediction"):
        np.testing.assert_allclose(
            np.asarray(out[name]), np.asarray(ref[name]), rtol=0,
            atol=4 * np.finfo(np.float32).eps)
    assert pm._chain_plan([feats]).describe() == [("segment", 5)]


def test_encoder_chain_wide_margins_bitexact():
    """Covers the encoder kernels (StringIndexer numeric vocab, OneHot,
    VectorAssembler) AND the context-stable margin contraction: an
    8-wide assembled features column feeds the LR terminal, the width
    regime where a plain matvec would accumulate differently inside the
    fused program than in the standalone predict entry point (see
    ``linear._stable_margins``)."""
    from flink_ml_tpu.models.feature.encoders import (
        OneHotEncoder,
        OneHotEncoderParams,
        StringIndexer,
        VectorAssembler,
    )

    rng = np.random.default_rng(1)
    n = 80
    cat = rng.integers(0, 5, size=n).astype(np.int64)
    x = rng.normal(size=(n, 3))
    # f32 column: the StringIndexer lookup is a vocabulary-EQUALITY
    # decision, so its kernel declines f64 input (exact_compare) — see
    # test_exact_compare_kernels_decline_f64
    val = rng.choice([1.5, 2.5, 7.0, 9.0], size=n).astype(np.float32)
    t = Table({"cat": cat, "x": x, "val": val,
               "label": (x[:, 0] > 0).astype(np.int64)})
    si = StringIndexer().set_input_cols("val").set_output_cols("vid").fit(t)
    t0 = si.transform(t)[0]
    oh = (OneHotEncoder().set_input_cols("cat").set_output_cols("hot")
          .set(OneHotEncoderParams.HANDLE_INVALID, "keep").fit(t0))
    t1 = oh.transform(t0)[0]
    va = (VectorAssembler().set_input_cols("hot", "x", "vid")
          .set_features_col("raw"))         # 4 + 3 + 1 = 8-wide
    t2 = va.transform(t1)[0]
    sc = (StandardScaler().set_features_col("raw")
          .set_output_col("features").fit(t2))
    t3 = sc.transform(t2)[0]
    lr = LogisticRegression().set_max_iter(2).fit(t3)
    pm = PipelineModel([si, oh, va, sc, lr])
    feats = t.drop("label")
    (ref,), (out,) = _ab(pm, feats)
    assert pm._chain_plan([feats]).describe() == [("segment", 5)]
    # derived columns value-equal; dtypes follow the chain's documented
    # f32 normalization (the stagewise assembler path is host-f64)
    for name in ("vid", "hot", "features", "prediction", "rawPrediction"):
        a = np.asarray(ref[name])
        b = np.asarray(out[name])
        assert a.shape == b.shape
        assert np.array_equal(a.astype(b.dtype), b), name


def test_widedeep_wide_dense_bitexact():
    """dense width >= 8 exercises the wide tower's context-stable
    contraction (``forward_from_rows``) under fusion."""
    rng = np.random.default_rng(6)
    n = 128
    dense = rng.normal(size=(n, 8)).astype(np.float32)
    cat = np.stack([rng.integers(0, 10, size=n),
                    rng.integers(0, 7, size=n)], axis=1).astype(np.int32)
    t = Table({"denseFeatures": dense, "catFeatures": cat,
               "label": (cat[:, 0] > 4).astype(np.int64)})
    s1 = (StandardScaler().set_features_col("denseFeatures")
          .set_output_col("denseFeatures").fit(t))
    t1 = s1.transform(t)[0]
    s2 = (MaxAbsScaler().set_features_col("denseFeatures")
          .set_output_col("denseFeatures").fit(t1))
    t2 = s2.transform(t1)[0]
    wd = WideDeep().set_vocab_sizes([10, 7]).set_max_iter(2).fit(t2)
    pm = PipelineModel([s1, s2, wd])
    feats = t.drop("label")
    (ref,), (out,) = _ab(pm, feats)
    _assert_tables_equal(ref, out)


def test_gbt_breaks_chain_and_matches():
    """GBT scores host-f64 margins across trees -> deliberately NOT
    chainable; it falls back stagewise after the fused scaler segment."""
    t = _table(seed=4)
    stages, t3 = _scaler_chain(t)
    gbt = (GBTClassifier().set_max_iter(3).set_features_col("ma")
           .fit(t3))
    pm = PipelineModel(stages + [gbt])
    feats = t.drop("label")
    (ref,), (out,) = _ab(pm, feats)
    _assert_tables_equal(ref, out)
    assert pm._chain_plan([feats]).describe() == \
        [("segment", 3), ("stage", 1)]


# -- chain-break correctness --------------------------------------------------

def test_chain_break_at_splitter_bitexact():
    """scaler -> randomsplitter -> scaler -> model: segment boundaries
    land exactly at the non-chainable stage, the split fans the flow into
    two tables, and every output matches the stagewise path bit-exactly."""
    t = _table(seed=5)
    s1 = StandardScaler().set_output_col("std").fit(t)
    t1 = s1.transform(t)[0]
    s2 = (MinMaxScaler().set_features_col("std").set_output_col("mm")
          .fit(t1))
    t2 = s2.transform(t1)[0]
    lr = LogisticRegression().set_features_col("mm").set_max_iter(2) \
        .fit(t2)
    splitter = RandomSplitter().set_weights(1.0, 1.0).set_seed(7)
    pm = PipelineModel([s1, splitter, s2, lr])
    feats = t.drop("label")
    ref, out = _ab(pm, feats)
    assert len(ref) == len(out) == 2            # the split fans out
    for r, o in zip(ref, out):
        _assert_tables_equal(r, o)
    plan = pm._chain_plan([feats])
    assert plan.describe() == \
        [("segment", 1), ("stage", 1), ("segment", 2)]


def test_zero_row_table_fused():
    t = _table()
    s1 = StandardScaler().set_output_col("std").fit(t)
    t1 = s1.transform(t)[0]
    s2 = (MinMaxScaler().set_features_col("std").set_output_col("mm")
          .fit(t1))
    t2 = s2.transform(t1)[0]
    lr = LogisticRegression().set_features_col("mm").set_max_iter(2) \
        .fit(t2)
    for stages in ([s1, s2, lr],
                   [s1, RandomSplitter().set_weights(1.0, 1.0), s2, lr]):
        pm = PipelineModel(stages)
        empty = t.drop("label").take(0)
        ref, out = _ab(pm, empty)
        assert len(ref) == len(out)
        for r, o in zip(ref, out):
            assert o.num_rows == 0
            _assert_tables_equal(r, o)


def test_single_chainable_stage_stays_stagewise():
    """A plan of singleton segments is the stagewise path with extra
    bookkeeping — not worthwhile, so no plan is kept."""
    t = _table()
    s1 = StandardScaler().set_output_col("std").fit(t)
    pm = PipelineModel([s1])
    assert pm._chain_plan([t.drop("label")]) is None


def test_unsafe_int_values_fall_back_stagewise():
    """Integer batch values beyond the f32-exact range (+-2^24) cannot run
    in an f32 segment without silently diverging from the host-f64
    compare — the segment detects them per call and runs its stages
    stagewise, so the fused path still matches exactly."""
    rng = np.random.default_rng(3)
    n = 64
    big = (1 << 24) + rng.integers(0, 3, size=n).astype(np.int64)
    t = Table({"features": rng.normal(size=(n, 4)), "big": big})
    s1 = StandardScaler().set_output_col("std").fit(t)
    bz = (Binarizer().set_features_col("big").set_output_col("bin")
          .set_threshold((1 << 24) + 0.5))
    pm = PipelineModel([s1, bz])
    (ref,), (out,) = _ab(pm, t)
    _assert_tables_equal(ref, out)
    assert np.asarray(out["bin"]).any()          # the compare really fires
    # safe batches through the same plan keep the fused path
    small = Table({"features": np.asarray(t["features"]),
                   "big": big - (1 << 24)})
    (ref2,), (out2,) = _ab(pm, small)
    _assert_tables_equal(ref2, out2)

    # standalone rerouted transforms fall back to their host-f64 path too
    mm = (MinMaxScaler().set_features_col("big").set_output_col("mm")
          .fit(t))
    got = np.asarray(mm.transform(t)[0]["mm"])
    X = big.astype(np.float64).reshape(-1, 1)
    span = np.maximum(X.max() - X.min(), 1e-12)
    assert np.array_equal(got, (X - X.min()) / span)


def test_fused_onehot_negative_id_raises():
    """The stagewise keep path raises on NEGATIVE ids (only too-large
    ids zero out) — the fused segment's pre hook must raise identically,
    not silently emit a zero row."""
    from flink_ml_tpu.models.feature.encoders import (
        OneHotEncoder,
        OneHotEncoderParams,
        VectorAssembler,
    )

    rng = np.random.default_rng(9)
    n = 40
    t = Table({"cat": rng.integers(0, 4, size=n).astype(np.int64),
               "x": rng.normal(size=(n, 3))})
    oh = (OneHotEncoder().set_input_cols("cat").set_output_cols("hot")
          .set(OneHotEncoderParams.HANDLE_INVALID, "keep").fit(t))
    va = VectorAssembler().set_input_cols("hot", "x").set_features_col("f")
    pm = PipelineModel([oh, va])
    pm.transform(t)                       # warms + caches the fused plan
    assert pm._chain_plan([t]).describe() == [("segment", 2)]
    bad = Table({"cat": np.array([1, -1, 2], np.int64),
                 "x": np.zeros((3, 3))})
    with pytest.raises(ValueError, match="out of range"):
        pm.transform(bad)
    with chain.chain_disabled(), \
            pytest.raises(ValueError, match="out of range"):
        pm.transform(bad)


def test_exact_compare_kernels_decline_f64():
    """Kernels whose OUTPUT is an exact comparison decision (bucket
    index, vocabulary equality, placeholder fill) must not chain on f64
    columns: segment-entry f32 rounding could carry a value across the
    boundary the host-f64 compare respects.  They decline — stagewise
    fallback at full precision — while f32 columns keep the kernel."""
    from flink_ml_tpu.models.feature.encoders import StringIndexer
    from flink_ml_tpu.models.feature.transforms import Imputer
    from flink_ml_tpu.models.feature.vector_ops import (
        KBinsDiscretizer,
        KBinsDiscretizerModel,
        VectorIndexer,
    )

    rng = np.random.default_rng(17)
    n = 64
    Xd = rng.normal(size=(n, 2))                     # f64
    t64 = Table({"features": Xd})
    t32 = Table({"features": Xd.astype(np.float32)})
    cats = Table({"features": rng.integers(0, 3, size=(n, 2))
                  .astype(np.float64)})
    for stage in (
            KBinsDiscretizer().set_num_bins(4).fit(t64),
            VectorIndexer().set_handle_invalid("keep").fit(cats),
            Imputer().set_missing_value(0.1).fit(t64),
    ):
        assert stage.transform_kernel(t64.schema()) is None
        assert stage.transform_kernel(t32.schema()) is not None
    si = StringIndexer().set_input_cols("v").set_output_cols("vid").fit(
        Table({"v": np.array([1.0, 2.0, 1.0], np.float32)}))
    assert si.transform_kernel({"v": ((), np.dtype(np.float64))}) is None
    assert si.transform_kernel({"v": ((), np.dtype(np.float32))}) is not None

    # the divergence declining prevents: an f64 value just below a
    # non-f32-exact learned edge rounds ONTO the edge at f32 entry, so a
    # fused compare would bump it into the next bucket
    kb = KBinsDiscretizerModel().set_model_data(
        Table({"edges": np.array([[0.0, 0.3, 1.0]]),
               "n_edges": np.array([3])}))
    near = Table({"features": np.array(
        [[np.nextafter(0.3, 0.0)], [0.3], [0.75]])})
    assert np.array_equal(
        np.asarray(kb.transform(near)[0]["output"]).ravel(), [0.0, 1.0, 1.0])
    s1 = (StandardScaler().set_features_col("output")
          .set_output_col("std").fit(kb.transform(near)[0]))
    s2 = (MaxAbsScaler().set_features_col("std").set_output_col("ma")
          .fit(s1.transform(kb.transform(near)[0])[0]))
    pm = PipelineModel([kb, s1, s2])
    (ref,), (out,) = _ab(pm, near)
    _assert_tables_equal(ref, out)
    assert pm._chain_plan([near]).describe() == \
        [("stage", 1), ("segment", 2)]               # kb fell out of the chain


def test_kbins_nan_bins_last_fused():
    """NaN sorts AFTER every edge in the host searchsorted (last bin);
    the fused kernel's >=-count sees NaN compare false everywhere (bin 0)
    and must route it to the last bin explicitly."""
    from flink_ml_tpu.models.feature.vector_ops import KBinsDiscretizerModel

    kb = KBinsDiscretizerModel().set_model_data(
        Table({"edges": np.array([[0.0, 0.3, 1.0]]),
               "n_edges": np.array([3])}))
    t = Table({"features": np.array([[0.1], [np.nan], [0.8]], np.float32)})
    host = np.asarray(kb.transform(t)[0]["output"])
    fused = chain.apply_kernel(kb.transform_kernel(t.schema()), t)["output"]
    assert np.array_equal(host.astype(np.float32), np.asarray(fused))
    assert np.array_equal(np.asarray(fused).ravel(), [0.0, 1.0, 1.0])


def test_imputer_f64_placeholder_fills_exactly():
    """A non-f32-exact placeholder present EXACTLY in f64 data must fill
    via the host path — the kernel declines f64 instead of rounding the
    placeholder past the compare and passing the value through."""
    from flink_ml_tpu.models.feature.transforms import Imputer

    t = Table({"features": np.array([[0.1], [1.0], [3.0]])})
    im = Imputer().set_missing_value(0.1).set_output_col("out").fit(t)
    got = np.asarray(im.transform(t)[0]["out"]).ravel()
    assert np.array_equal(got, [2.0, 1.0, 3.0])      # 0.1 -> mean(1, 3)


def test_pre_cols_conflict_splits_segments():
    """A stage whose host ``pre`` validates a column produced mid-segment
    (OneHot on StringIndexer's ids) closes the running segment and opens
    a fresh one — fused across a segment boundary, not demoted to
    per-stage host dispatch."""
    from flink_ml_tpu.models.feature.encoders import (
        OneHotEncoder,
        OneHotEncoderParams,
        StringIndexer,
        VectorAssembler,
    )

    rng = np.random.default_rng(21)
    n = 64
    t = Table({"val": rng.choice([1.5, 2.5, 7.0], size=n)
               .astype(np.float32),
               "x": rng.normal(size=(n, 3))})
    si = StringIndexer().set_input_cols("val").set_output_cols("vid").fit(t)
    t0 = si.transform(t)[0]
    oh = (OneHotEncoder().set_input_cols("vid").set_output_cols("hot")
          .set(OneHotEncoderParams.HANDLE_INVALID, "keep").fit(t0))
    va = (VectorAssembler().set_input_cols("hot", "x")
          .set_features_col("f"))
    pm = PipelineModel([si, oh, va])
    (ref,), (out,) = _ab(pm, t)
    assert pm._chain_plan([t]).describe() == \
        [("segment", 1), ("segment", 2)]
    for name in ("vid", "hot", "f"):                 # value-equal (f32 posture)
        a, b = np.asarray(ref[name]), np.asarray(out[name])
        assert a.shape == b.shape
        assert np.array_equal(a.astype(b.dtype), b), name


def test_param_mutation_rebuilds_plan():
    """Mutating a stage param after the first fused transform must not
    serve the stale kernels the old value was baked into."""
    t = _table(seed=14)
    s1 = StandardScaler().set_output_col("std").fit(t)
    bz = (Binarizer().set_features_col("std").set_output_col("bin")
          .set_threshold(0.0))
    pm = PipelineModel([s1, bz])
    feats = t.drop("label")
    pm.transform(feats)                          # plan built at thr=0.0
    bz.set_threshold(0.75)
    (ref,), (out,) = _ab(pm, feats)
    _assert_tables_equal(ref, out)
    assert not np.array_equal(np.asarray(out["bin"]),
                              (np.asarray(out["std"]) > 0.0))


# -- dispatch accounting ------------------------------------------------------

def test_fused_dispatch_count_is_one_per_segment():
    t = _table(seed=8)
    stages, t3 = _scaler_chain(t)
    lr = (LogisticRegression().set_features_col("ma").set_max_iter(2)
          .fit(t3))
    pm = PipelineModel(stages + [lr])
    feats = t.drop("label")
    pm.transform(feats)                          # plan build + warm
    d0 = chain.dispatch_count()
    pm.transform(feats)
    assert chain.dispatch_count() - d0 == 1      # 4 stages, ONE dispatch


# -- zero recompiles ----------------------------------------------------------

def test_zero_recompile_steady_state_warmed_buckets():
    from flink_ml_tpu.utils.backend import count_compiles

    t = _table(n=128, d=8, seed=2)
    stages, t3 = _scaler_chain(t)
    lr = (LogisticRegression().set_features_col("ma").set_max_iter(2)
          .fit(t3))
    pm = PipelineModel(stages + [lr])
    feats = t.drop("label")
    for n in (8, 16, 32, 64, 128):               # warm the bucket ladder
        pm.transform(feats.take(n))
    with count_compiles() as count:
        for n in (1, 3, 8, 9, 16, 23, 33, 64, 100, 128):
            pm.transform(feats.take(n))
    assert count() == 0, (
        f"{count()} new XLA lowerings in steady state — bucket padding "
        "or plan caching regressed")


def test_dtype_hygiene_f64_f32_share_one_compile():
    """numpy float64 input columns must NOT retrace: segment entry
    normalizes to f32 on host, so f64 and f32 views of the same data hit
    one compiled program (and produce identical derived columns)."""
    from flink_ml_tpu.utils.backend import count_compiles

    t = _table(n=64, d=8, seed=11)               # f64 features
    stages, t3 = _scaler_chain(t)
    lr = (LogisticRegression().set_features_col("ma").set_max_iter(2)
          .fit(t3))
    pm = PipelineModel(stages + [lr])
    f64 = t.drop("label")
    f32 = Table({"features": np.asarray(t["features"], np.float32)})
    pm.transform(f64)                            # warm once, f64 entry
    with count_compiles() as count:
        (a,) = pm.transform(f64)
        (b,) = pm.transform(f32)
    assert count() == 0, (
        f"{count()} new lowerings — f64 input retraced the segment")
    # derived columns identical (the untouched passthrough input keeps
    # its caller dtype by design)
    _assert_tables_equal(
        a, b, cols=[c for c in a.column_names if c != "features"])


# -- persistence --------------------------------------------------------------

def test_persist_round_trip_fused_bitexact(tmp_path):
    t = _table(seed=12)
    stages, t3 = _scaler_chain(t)
    lr = (LogisticRegression().set_features_col("ma").set_max_iter(3)
          .fit(t3))
    pm = PipelineModel(stages + [lr])
    feats = t.drop("label")
    with chain.chain_disabled():                 # pre-save stagewise oracle
        (ref,) = pm.transform(feats)
    path = os.path.join(str(tmp_path), "pipeline")
    pm.save(path)
    loaded = PipelineModel.load(path)
    (out,) = loaded.transform(feats)             # fused path post-load
    _assert_tables_equal(ref, out)
    assert loaded._chain_plan([feats]).describe() == [("segment", 4)]


# -- serving ------------------------------------------------------------------

def test_pipeline_servable_honors_min_bucket():
    """The servable's fused plan must pad with the servable's OWN bucket
    floor: warm_up tiles buckets from min_bucket, so a plan padding to a
    different ladder would compile on the serving path after ready."""
    from flink_ml_tpu.utils.backend import count_compiles

    from flink_ml_tpu.serving.executor import make_servable

    t = _table(n=128, seed=19)
    stages, t3 = _scaler_chain(t)
    lr = (LogisticRegression().set_features_col("ma").set_max_iter(2)
          .fit(t3))
    pm = PipelineModel(stages + [lr])
    feats = t.drop("label")
    servable = make_servable(pm, feats.take(2), min_bucket=64,
                             max_batch_rows=128)
    servable.warm_up()
    with count_compiles() as count:
        for n in (3, 40, 100):
            servable.predict(feats.take(n))
    assert count() == 0, (
        f"{count()} new lowerings post-warm-up — the fused plan pads a "
        "different bucket ladder than warm_up compiled")


def test_pipeline_serving_single_dispatch_chain():
    """One endpoint serves preprocess+score: fused per-micro-batch output
    is bit-exact with the offline stagewise transform."""
    t = _table(n=128, seed=13)
    stages, t3 = _scaler_chain(t)
    lr = (LogisticRegression().set_features_col("ma").set_max_iter(3)
          .fit(t3))
    pm = PipelineModel(stages + [lr])
    feats = t.drop("label")
    with chain.chain_disabled():
        (ref,) = pm.transform(feats)
    endpoint = serve_model(pm, feats.take(2), max_batch_rows=64,
                           max_wait_ms=0.5)
    try:
        start = 0
        for size in (1, 6, 14, 32):
            got = endpoint.predict(feats.slice(start, start + size))
            _assert_tables_equal(ref.slice(start, start + size), got)
            start += size
    finally:
        endpoint.close()
