"""Sparse/hashed feature path through the linear family (the Criteo shape:
hashed high-dim features scored against a dense weight, VERDICT r1 task 2).

Oracle strategy: on a small dimension the sparse trainers must agree with
the dense trainers run on the densified matrix — same seed, same batching,
same update — to float tolerance.  The high-dim tests then check the 2^20
path is expressible and learns.
"""

import numpy as np
import pytest

from flink_ml_tpu import Table
from flink_ml_tpu.linalg import SparseVector, stack_sparse_vectors
from flink_ml_tpu.models.classification import (
    LogisticRegression,
    LogisticRegressionModel,
    OnlineLogisticRegression,
)
from flink_ml_tpu.models.common.sgd import (
    SGDConfig,
    sgd_fit,
    sgd_fit_sparse,
)
from flink_ml_tpu.models.common.losses import LOSSES
from flink_ml_tpu.models.feature import FeatureHasher


def _sparse_problem(rng, n=256, d=32, nnz=4):
    """Random fixed-nnz rows + separable labels; returns both forms."""
    idx = np.stack([rng.choice(d, size=nnz, replace=False)
                    for _ in range(n)]).astype(np.int32)
    vals = rng.normal(size=(n, nnz)).astype(np.float32)
    dense = np.zeros((n, d), np.float32)
    np.add.at(dense, (np.arange(n)[:, None], idx), vals)
    w_true = rng.normal(size=(d,))
    y = (dense @ w_true > 0).astype(np.float64)
    return idx, vals, dense, y


def test_stack_sparse_vectors_pads_and_derives_dim():
    vecs = [SparseVector(10, [1, 3], [1.0, 2.0]),
            SparseVector(10, [7], [5.0])]
    idx, vals, dim = stack_sparse_vectors(vecs)
    assert dim == 10
    assert idx.shape == (2, 2) and vals.shape == (2, 2)
    np.testing.assert_array_equal(idx[1], [7, 0])
    np.testing.assert_array_equal(vals[1], [5.0, 0.0])
    with pytest.raises(ValueError, match="nnz"):
        stack_sparse_vectors(vecs, nnz=1)


def test_sgd_fit_sparse_matches_dense_oracle(rng):
    idx, vals, dense, y = _sparse_problem(rng)
    cfg = SGDConfig(learning_rate=0.5, max_epochs=8, global_batch_size=64,
                    tol=0, seed=3)
    dense_state, dense_log = sgd_fit(LOSSES["logistic"], dense, y, None, cfg)
    sparse_state, sparse_log = sgd_fit_sparse(
        LOSSES["logistic"], idx, vals, y, None, dense.shape[1], cfg)
    np.testing.assert_allclose(sparse_state.coefficients,
                               dense_state.coefficients, atol=1e-5)
    np.testing.assert_allclose(sparse_state.intercept, dense_state.intercept,
                               atol=1e-5)
    np.testing.assert_allclose(sparse_log, dense_log, atol=1e-5)


def test_sgd_fit_sparse_regularized_matches_dense(rng):
    idx, vals, dense, y = _sparse_problem(rng)
    cfg = SGDConfig(learning_rate=0.3, max_epochs=5, global_batch_size=64,
                    reg=0.05, elastic_net=0.4, tol=0, seed=1)
    dense_state, _ = sgd_fit(LOSSES["logistic"], dense, y, None, cfg)
    sparse_state, _ = sgd_fit_sparse(
        LOSSES["logistic"], idx, vals, y, None, dense.shape[1], cfg)
    np.testing.assert_allclose(sparse_state.coefficients,
                               dense_state.coefficients, atol=1e-5)


def test_lr_fit_on_sparse_vector_column(rng):
    idx, vals, dense, y = _sparse_problem(rng, n=128, d=16, nnz=3)
    vecs = np.empty((128,), object)
    for i in range(128):
        vecs[i] = SparseVector(16, idx[i], vals[i])
    sparse_t = Table({"features": vecs, "label": y})
    dense_t = Table({"features": dense.astype(np.float64), "label": y})

    lr = lambda: (LogisticRegression().set_max_iter(6).set_learning_rate(0.5)
                  .set_tol(0))
    m_sparse = lr().fit(sparse_t)
    m_dense = lr().fit(dense_t)
    np.testing.assert_allclose(m_sparse._state.coefficients,
                               m_dense._state.coefficients, atol=1e-5)
    # inference accepts the sparse column too
    p_sparse = np.asarray(m_sparse.transform(sparse_t)[0]["prediction"])
    p_dense = np.asarray(m_dense.transform(dense_t)[0]["prediction"])
    np.testing.assert_array_equal(p_sparse, p_dense)


def test_lr_fit_on_hashed_pair_columns_2e20(rng):
    """The Criteo-shaped config: 2^20 hashed dims, fixed actives per row."""
    d = 1 << 20
    n, nnz = 512, 8
    idx = rng.integers(0, d, size=(n, nnz)).astype(np.int32)
    vals = np.ones((n, nnz), np.float32)
    # label depends on whether the row's first hashed slot is even
    y = (idx[:, 0] % 2 == 0).astype(np.float64)
    # make it learnable: even rows get a dedicated marker slot
    idx[y == 1, 0] = 2
    idx[y == 0, 0] = 3
    t = Table({"features_indices": idx, "features_values": vals, "label": y})

    lr = (LogisticRegression().set_max_iter(10).set_learning_rate(1.0)
          .set_tol(0).set_num_features(d).set_global_batch_size(128))
    model = lr.fit(t)
    assert model._state.coefficients.shape == (d,)
    pred = np.asarray(model.transform(t)[0]["prediction"])
    assert (pred == y).mean() > 0.95
    assert model._loss_log[-1] < model._loss_log[0]


def test_lr_requires_num_features_for_pair_columns(rng):
    t = Table({"features_indices": np.zeros((4, 2), np.int32),
               "features_values": np.ones((4, 2), np.float32),
               "label": np.asarray([0.0, 1.0, 0.0, 1.0])})
    with pytest.raises(ValueError, match="numFeatures"):
        LogisticRegression().fit(t)


def test_online_lr_sparse_matches_dense_ftrl(rng):
    idx, vals, dense, y = _sparse_problem(rng, n=200, d=24, nnz=5)
    sparse_t = Table({"features_indices": idx, "features_values": vals,
                      "label": y})
    dense_t = Table({"features": dense.astype(np.float64), "label": y})

    def online():
        return (OnlineLogisticRegression().set_global_batch_size(50)
                .set_alpha(0.5).set_beta(1.0))

    m_sparse = online().set_num_features(24).fit(sparse_t)
    m_dense = online().fit(dense_t)
    np.testing.assert_allclose(m_sparse._state.coefficients,
                               m_dense._state.coefficients, atol=1e-5)
    assert m_sparse.model_version == m_dense.model_version == 4


def test_online_lr_sparse_high_dim(rng):
    d = 1 << 20
    n, nnz = 300, 6
    idx = rng.integers(4, d, size=(n, nnz)).astype(np.int32)
    y = rng.integers(0, 2, size=n).astype(np.float64)
    idx[:, 0] = np.where(y == 1, 1, 2)  # marker slots
    vals = np.ones((n, nnz), np.float32)
    t = Table({"features_indices": idx, "features_values": vals, "label": y})
    model = (OnlineLogisticRegression().set_num_features(d)
             .set_global_batch_size(100).set_alpha(1.0).fit(t))
    w = model._state.coefficients
    assert w.shape == (d,)
    assert w[1] > 0 > w[2]  # marker weights separated
    pred = np.asarray(model.transform(t)[0]["prediction"])
    assert (pred == y).mean() > 0.95


def test_feature_hasher_sparse_output_matches_dense(rng):
    n = 64
    t = Table({
        "age": rng.normal(size=n),
        "city": rng.choice(["sf", "nyc", "la"], size=n),
        "device": rng.choice(["ios", "android"], size=n),
    })
    fh = (FeatureHasher().set_input_cols("age", "city", "device")
          .set_num_features(128).set_output_col("f"))
    dense = np.asarray(fh.transform(t)[0]["f"])
    sp = fh.set_sparse_output(True).transform(t)[0]
    idx = np.asarray(sp["f_indices"])
    vals = np.asarray(sp["f_values"])
    assert idx.shape == (n, 3) and vals.shape == (n, 3)
    rebuilt = np.zeros((n, 128))
    np.add.at(rebuilt, (np.arange(n)[:, None], idx), vals)
    np.testing.assert_allclose(rebuilt, dense, atol=1e-6)


def test_hasher_to_lr_pipeline_sparse(rng):
    """FeatureHasher(sparse) -> LogisticRegression end-to-end, the Criteo
    ingest composition."""
    n = 256
    city = rng.choice(["sf", "nyc", "la", "chi"], size=n)
    y = (city == "sf").astype(np.float64)
    t = Table({"city": city, "label": y})
    hashed = (FeatureHasher().set_input_cols("city").set_num_features(1 << 16)
              .set_output_col("features").set_sparse_output(True)
              .transform(t)[0])
    model = (LogisticRegression().set_num_features(1 << 16).set_max_iter(20)
             .set_learning_rate(2.0).set_tol(0).fit(hashed))
    pred = np.asarray(model.transform(hashed)[0]["prediction"])
    assert (pred == y).mean() > 0.98


def test_model_save_load_high_dim_roundtrip(tmp_path, rng):
    d = 1 << 18
    idx = rng.integers(0, d, size=(64, 4)).astype(np.int32)
    vals = np.ones((64, 4), np.float32)
    y = rng.integers(0, 2, size=64).astype(np.float64)
    t = Table({"features_indices": idx, "features_values": vals, "label": y})
    model = (LogisticRegression().set_num_features(d).set_max_iter(2)
             .fit(t))
    model.save(str(tmp_path / "m"))
    re = LogisticRegressionModel.load(str(tmp_path / "m"))
    np.testing.assert_allclose(re._state.coefficients,
                               model._state.coefficients)
    p1 = np.asarray(model.transform(t)[0]["prediction"])
    p2 = np.asarray(re.transform(t)[0]["prediction"])
    np.testing.assert_array_equal(p1, p2)


def test_out_of_range_indices_rejected(rng):
    from flink_ml_tpu.models.common.linear import check_sparse_indices

    with pytest.raises(ValueError, match="out of range"):
        check_sparse_indices(np.asarray([[0, 100]]), 100)
    check_sparse_indices(np.asarray([[0, 99]]), 100)  # in range: fine

    # through the estimator: hasher at 2^10 vs model at 2^8
    idx = rng.integers(0, 1 << 10, size=(32, 3)).astype(np.int32)
    idx[0, 0] = (1 << 10) - 1
    t = Table({"features_indices": idx,
               "features_values": np.ones((32, 3), np.float32),
               "label": rng.integers(0, 2, size=32).astype(np.float64)})
    with pytest.raises(ValueError, match="hash-space"):
        LogisticRegression().set_num_features(1 << 8).set_max_iter(1).fit(t)


def test_midtrain_checkpoint_resume_through_estimator(tmp_path, rng):
    """fit_outofcore exposes the full checkpoint surface (every-N-steps +
    resume) so an interrupted Criteo pass restarts without dropping to the
    sgd layer."""
    from flink_ml_tpu.data.datacache import DataCacheReader, DataCacheWriter
    from flink_ml_tpu.iteration.checkpoint import CheckpointConfig

    cache = str(tmp_path / "cache")
    w = DataCacheWriter(cache, segment_rows=256)
    X = rng.normal(size=(1024, 8)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    w.append({"features": X, "label": y})
    w.finish()

    est = (LogisticRegression().set_learning_rate(0.5).set_max_iter(3)
           .set_tol(0.0))
    ck = CheckpointConfig(str(tmp_path / "ck"))
    m1 = est.fit_outofcore(lambda: DataCacheReader(cache, batch_rows=128),
                           num_features=8, checkpoint=ck,
                           checkpoint_every_steps=2)
    # resume of a COMPLETED run returns the checkpointed answer unchanged
    m2 = est.fit_outofcore(lambda: DataCacheReader(cache, batch_rows=128),
                           num_features=8, checkpoint=ck,
                           checkpoint_every_steps=2, resume=True)
    assert np.all(np.isfinite(m2._state.coefficients))


# ---------------------------------------------------------------------------
# Blocked (128-lane) gather/scatter path + mixed dense/categorical trainer
# ---------------------------------------------------------------------------

def test_blocked_gather_scatter_bitwise_equals_elementwise(rng):
    """d % 128 == 0 switches to the row-blocked path; the arithmetic must
    be exactly the elementwise gather/scatter."""
    import jax.numpy as jnp

    from flink_ml_tpu.models.common import sgd as sgd_mod

    d = 512
    w = jnp.asarray(rng.normal(size=d), jnp.float32)
    idx = jnp.asarray(rng.integers(0, d, size=(64, 7)), jnp.int32)
    upd = jnp.asarray(rng.normal(size=64 * 7), jnp.float32)

    assert sgd_mod._use_blocked(d)
    np.testing.assert_array_equal(
        np.asarray(sgd_mod._blocked_gather(w, idx)), np.asarray(w[idx]))
    np.testing.assert_array_equal(
        np.asarray(sgd_mod._blocked_scatter_add(w, idx, upd)),
        np.asarray(w.at[idx.reshape(-1)].add(upd)))
    assert not sgd_mod._use_blocked(500)


def test_sgd_fit_sparse_blocked_dim_matches_dense_oracle(rng):
    """Same oracle as above but at d=256 so the blocked path is the one
    exercised."""
    idx, vals, dense, y = _sparse_problem(rng, n=192, d=256, nnz=5)
    cfg = SGDConfig(learning_rate=0.5, max_epochs=6, global_batch_size=64,
                    tol=0, seed=2)
    dense_state, dense_log = sgd_fit(LOSSES["logistic"], dense, y, None, cfg)
    sparse_state, sparse_log = sgd_fit_sparse(
        LOSSES["logistic"], idx, vals, y, None, 256, cfg)
    np.testing.assert_allclose(sparse_state.coefficients,
                               dense_state.coefficients, atol=1e-5)
    np.testing.assert_allclose(sparse_log, dense_log, atol=1e-5)


def _mixed_problem(rng, n=256, n_dense=5, n_cat=3, d=256):
    dense = rng.normal(size=(n, n_dense)).astype(np.float32)
    cat = rng.integers(n_dense, d, size=(n, n_cat)).astype(np.int32)
    w_true = rng.normal(size=(d,))
    margin = dense @ w_true[:n_dense] + w_true[cat].sum(axis=1)
    y = (margin > 0).astype(np.float64)
    return dense, cat, y


def test_sgd_fit_mixed_matches_sparse_encoding(rng):
    """The mixed trainer must agree with sgd_fit_sparse on the equivalent
    (indices, values) encoding: dense slot j -> (j, x_j), cat -> (idx, 1)."""
    from flink_ml_tpu.models.common.sgd import sgd_fit_mixed

    n, n_dense, n_cat, d = 256, 5, 3, 256
    dense, cat, y = _mixed_problem(rng, n, n_dense, n_cat, d)
    idx = np.concatenate(
        [np.broadcast_to(np.arange(n_dense, dtype=np.int32), (n, n_dense)),
         cat], axis=1)
    vals = np.concatenate(
        [dense, np.ones((n, n_cat), np.float32)], axis=1)

    cfg = SGDConfig(learning_rate=0.4, max_epochs=6, global_batch_size=64,
                    tol=0, seed=5)
    sparse_state, sparse_log = sgd_fit_sparse(
        LOSSES["logistic"], idx, vals, y, None, d, cfg)
    mixed_state, mixed_log = sgd_fit_mixed(
        LOSSES["logistic"], dense, cat, y, None, d, cfg)
    np.testing.assert_allclose(mixed_state.coefficients,
                               sparse_state.coefficients, atol=1e-5)
    np.testing.assert_allclose(mixed_state.intercept, sparse_state.intercept,
                               atol=1e-5)
    np.testing.assert_allclose(mixed_log, sparse_log, atol=1e-5)
    # and it learned the problem
    assert mixed_log[-1] < mixed_log[0] * 0.7


def test_sgd_fit_mixed_regularized_matches_sparse(rng):
    from flink_ml_tpu.models.common.sgd import sgd_fit_mixed

    n, n_dense, n_cat, d = 192, 4, 2, 128
    dense, cat, y = _mixed_problem(rng, n, n_dense, n_cat, d)
    idx = np.concatenate(
        [np.broadcast_to(np.arange(n_dense, dtype=np.int32), (n, n_dense)),
         cat], axis=1)
    vals = np.concatenate(
        [dense, np.ones((n, n_cat), np.float32)], axis=1)

    cfg = SGDConfig(learning_rate=0.3, max_epochs=5, global_batch_size=64,
                    reg=0.05, elastic_net=0.3, tol=0, seed=7)
    sparse_state, _ = sgd_fit_sparse(
        LOSSES["logistic"], idx, vals, y, None, d, cfg)
    mixed_state, _ = sgd_fit_mixed(
        LOSSES["logistic"], dense, cat, y, None, d, cfg)
    np.testing.assert_allclose(mixed_state.coefficients,
                               sparse_state.coefficients, atol=1e-5)


def test_sgd_fit_mixed_rejects_bad_shapes(rng):
    from flink_ml_tpu.models.common.sgd import sgd_fit_mixed

    dense = rng.normal(size=(16, 8)).astype(np.float32)
    cat = rng.integers(0, 4, size=(16, 2)).astype(np.int32)
    with pytest.raises(ValueError, match="exceeds"):
        sgd_fit_mixed(LOSSES["logistic"], dense, cat,
                      np.zeros(16), None, 4, SGDConfig())


def test_lr_fit_on_mixed_columns_matches_pair_columns(rng):
    """The estimator surface: {col}_dense + {col}_indices dispatches to the
    mixed trainer and must agree with the equivalent pair-column fit."""
    n, n_dense, n_cat, d = 256, 4, 3, 256
    dense, cat, y = _mixed_problem(rng, n, n_dense, n_cat, d)
    idx = np.concatenate(
        [np.broadcast_to(np.arange(n_dense, dtype=np.int32), (n, n_dense)),
         cat], axis=1)
    vals = np.concatenate([dense, np.ones((n, n_cat), np.float32)], axis=1)

    def make_lr():
        return (LogisticRegression().set_num_features(d).set_max_iter(6)
                .set_learning_rate(0.4).set_tol(0).set_seed(5)
                .set_global_batch_size(64))

    mixed_t = Table({"features_dense": dense, "features_indices": cat,
                     "label": y})
    pair_t = Table({"features_indices": idx, "features_values": vals,
                    "label": y})
    m_mixed = make_lr().fit(mixed_t)
    m_pair = make_lr().fit(pair_t)
    np.testing.assert_allclose(m_mixed._state.coefficients,
                               m_pair._state.coefficients, atol=1e-5)

    # transform on mixed columns scores through the mixed margins
    # (better than chance after 6 epochs; exactness is the assert above)
    out = m_mixed.transform(mixed_t)[0]
    pred = np.asarray(out["prediction"])
    assert np.mean(pred == y) > 0.65

    # out-of-range categorical at transform time is rejected
    bad = Table({"features_dense": dense[:1],
                 "features_indices": np.full((1, n_cat), d, np.int32)})
    with pytest.raises(ValueError, match="out of range"):
        m_mixed.transform(bad)


def test_lr_mixed_requires_num_features(rng):
    dense, cat, y = _mixed_problem(rng, 64, 3, 2, 128)
    t = Table({"features_dense": dense, "features_indices": cat, "label": y})
    with pytest.raises(ValueError, match="numFeatures"):
        LogisticRegression().set_max_iter(2).fit(t)


def test_outofcore_mixed_matches_manual_updates(rng):
    """sgd_fit_outofcore with dense_key+indices_key must reproduce a manual
    _mixed_update loop over the SAME batch order — true parity, not just
    'loss went down' (a swapped dense/cat wiring would fail this)."""
    import jax
    import jax.numpy as jnp

    from flink_ml_tpu.models.common.sgd import (
        SGDConfig, _mixed_update, sgd_fit_outofcore)

    n, n_dense, n_cat, d = 256, 4, 3, 256
    dense, cat, y = _mixed_problem(rng, n, n_dense, n_cat, d)
    batch = 64
    cfg = SGDConfig(learning_rate=0.4, max_epochs=3, tol=0, seed=0,
                    global_batch_size=batch)

    def make_reader():
        def gen():
            for s in range(0, n, batch):
                yield {"features_dense": dense[s:s + batch],
                       "features_indices": cat[s:s + batch],
                       "label": y[s:s + batch]}
        return gen()

    ooc_state, ooc_log = sgd_fit_outofcore(
        LOSSES["logistic"], make_reader, num_features=d, config=cfg,
        indices_key="features_indices", dense_key="features_dense")
    assert ooc_log[-1] < ooc_log[0]

    # manual twin: identical update, identical batch order
    update = jax.jit(_mixed_update(LOSSES["logistic"], cfg))
    params = {"w": jnp.zeros((d,), jnp.float32),
              "b": jnp.zeros((), jnp.float32)}
    manual_log = []
    for _ in range(cfg.max_epochs):
        losses = []
        for s in range(0, n, batch):
            params, value = update(
                params, jnp.asarray(dense[s:s + batch]),
                jnp.asarray(cat[s:s + batch]),
                jnp.asarray(y[s:s + batch], jnp.float32),
                jnp.ones((batch,), jnp.float32))
            losses.append(float(value))
        manual_log.append(float(np.mean(losses)))

    np.testing.assert_allclose(ooc_state.coefficients,
                               np.asarray(params["w"], np.float64),
                               atol=1e-6)
    np.testing.assert_allclose(ooc_log, manual_log, atol=1e-5)


def test_resolve_features_rejects_ambiguous_schema(rng):
    from flink_ml_tpu.models.common.linear import resolve_features

    t = Table({"features_dense": np.zeros((4, 2), np.float32),
               "features_indices": np.zeros((4, 3), np.int32),
               "features_values": np.ones((4, 3), np.float32)})
    with pytest.raises(ValueError, match="ambiguous"):
        resolve_features(t, "features")


def test_online_lr_accepts_mixed_columns(rng):
    """The mixed convention re-encodes into FTRL's (indices, values) form
    instead of crashing."""
    n, nd, nc, d = 256, 3, 2, 128
    dense, cat, y = _mixed_problem(rng, n, nd, nc, d)
    t = Table({"features_dense": dense, "features_indices": cat, "label": y})
    model = (OnlineLogisticRegression().set_num_features(d)
             .set_global_batch_size(64).fit(t))
    out = model.transform(Table({"features_dense": dense,
                                 "features_indices": cat}))[0]
    assert np.isfinite(np.asarray(out["rawPrediction"])).all()


import jax as _jax


class TestShardedMixedWeight:
    """dp x model mesh: the weight shards over 'model' (VERDICT r2 task 7).
    The sharded fit must reproduce the single-device oracle allclose —
    a wrong psum/axis placement still converges, so only exact
    equivalence catches it (the WideDeep oracle stance)."""

    def _data(self, d):
        rng = np.random.default_rng(5)
        n, nd, nc = 256, 3, 5
        dense = rng.normal(size=(n, nd)).astype(np.float32)
        cat = rng.integers(0, d, size=(n, nc)).astype(np.int32)
        y = rng.integers(0, 2, size=n).astype(np.float64)
        cat[:, 0] = np.where(y == 1, 40, 41)
        return dense, cat, y

    @pytest.mark.parametrize("axes", [{"data": 2, "model": 4},
                                      {"data": 1, "model": 8},
                                      {"data": 8, "model": 1}])
    def test_matches_single_device_oracle(self, axes):
        from flink_ml_tpu.models.common.losses import logistic_loss
        from flink_ml_tpu.models.common.sgd import SGDConfig, sgd_fit_mixed
        from flink_ml_tpu.parallel.mesh import device_mesh

        d = 1 << 10
        dense, cat, y = self._data(d)
        for cfg in (SGDConfig(learning_rate=0.4, global_batch_size=64,
                              max_epochs=4, tol=0),
                    SGDConfig(learning_rate=0.4, global_batch_size=64,
                              max_epochs=4, tol=0, reg=0.02,
                              elastic_net=0.25)):
            oracle, oracle_log = sgd_fit_mixed(
                logistic_loss, dense, cat, y, None, d, cfg,
                mesh=device_mesh({"data": 1},
                                 devices=_jax.devices()[:1]))
            got, got_log = sgd_fit_mixed(
                logistic_loss, dense, cat, y, None, d, cfg,
                mesh=device_mesh(axes))
            np.testing.assert_allclose(got.coefficients,
                                       oracle.coefficients,
                                       rtol=1e-4, atol=1e-5)
            np.testing.assert_allclose(got.intercept, oracle.intercept,
                                       rtol=1e-4, atol=1e-6)
            np.testing.assert_allclose(got_log, oracle_log,
                                       rtol=1e-5, atol=1e-6)

    def test_rejects_indivisible_hash_space(self):
        from flink_ml_tpu.models.common.losses import logistic_loss
        from flink_ml_tpu.models.common.sgd import SGDConfig, sgd_fit_mixed
        from flink_ml_tpu.parallel.mesh import device_mesh

        dense, cat, y = self._data(1001)
        with pytest.raises(ValueError, match="divide the model axis"):
            sgd_fit_mixed(logistic_loss, dense, cat, y, None, 1001,
                          SGDConfig(max_epochs=1),
                          mesh=device_mesh({"data": 1, "model": 8}))


def test_auto_batch_sizing_plans_ell_at_bench_scale(rng, monkeypatch):
    """The DEFAULT product path must plan the ELL kernels at the Criteo
    shape (1M rows, 2^20 hashed dims): a fixed batch of 32 would mean 32k
    steps of layout (~400 GB) and a silent XLA fallback; auto sizing must
    pick a batch whose layout stack fits the budget so plan_mixed_impl
    says "ell" on one TPU chip."""
    import jax

    from flink_ml_tpu.models.common import sgd as S
    from flink_ml_tpu.parallel.mesh import device_mesh

    n, d = 1_000_000, 1 << 20
    cfg = S.SGDConfig()  # defaults: auto batch
    batch = S.resolve_global_batch_size(cfg, n, d)
    steps = -(-n // batch)
    assert steps * d * 12 <= S._ELL_LAYOUT_BUDGET_BYTES
    assert batch <= S._AUTO_BATCH_CAP

    # the planner itself would say "ell" for that layout on 1 TPU device
    mesh = device_mesh({"data": 1}, devices=jax.devices()[:1])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert S.plan_mixed_impl(d, mesh, steps) == "ell"
    # ... and the r2 default would NOT have (the weak-#2 divergence)
    assert S.plan_mixed_impl(d, mesh, -(-n // 32)) == "xla"

    # explicit user choices always pass through untouched
    assert S.resolve_global_batch_size(
        S.SGDConfig(global_batch_size=17), n, d) == 17
    # dense fits keep the classic default
    assert S.resolve_global_batch_size(cfg, n) == S.DEFAULT_GLOBAL_BATCH


def test_planned_impl_surfaces_on_product_models(rng):
    """The estimator surface must expose which impl fit planned (the
    benchmark's ``lr_criteo`` checks it as its ``expect_plan``)."""
    d = 1 << 10
    X = rng.normal(size=(64, 6)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float64)
    idx = rng.integers(6, d, size=(64, 3)).astype(np.int32)
    t = Table({"features_dense": X, "features_indices": idx, "label": y})
    model = (LogisticRegression().set_num_features(d).set_max_iter(2)
             .set_tol(0).fit(t))
    # CPU backend: the planner always says "xla" for the mixed layout
    assert model.planned_impl == "xla"

    dense_model = (LogisticRegression().set_max_iter(2).set_tol(0)
                   .fit(Table({"features": X, "label": y})))
    assert dense_model.planned_impl == "dense"
    # loaded models don't carry a planned impl
    assert model.loss_log  # sanity: fit actually trained
