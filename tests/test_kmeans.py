"""KMeans tests — mirror of ``KMeansTest.java`` (259 LoC): param defaults,
fit+transform on the 6-point/2-cluster fixture with exact cluster membership
(BASELINE.md anchor), save/load round-trip, pipeline integration."""

import numpy as np
import pytest

from flink_ml_tpu import Pipeline, Table
from flink_ml_tpu.models.clustering.kmeans import (
    KMeans,
    KMeansModel,
    select_random_centroids,
)
from flink_ml_tpu.utils import persist

# The exact fixture from KMeansTest.java:58-66
DATA = np.array([
    [0.0, 0.0],
    [0.0, 0.3],
    [0.3, 0.0],
    [9.0, 0.0],
    [9.0, 0.6],
    [9.6, 0.0],
], dtype=np.float64)


def _table():
    return Table({"features": DATA})


def _clusters(table, pred_col="prediction"):
    """Group feature rows by predicted cluster -> set of frozensets."""
    groups = {}
    for row, c in zip(table["features"], table[pred_col]):
        groups.setdefault(int(c), set()).add(tuple(row.tolist()))
    return set(frozenset(v) for v in groups.values())

EXPECTED = {
    frozenset({(0.0, 0.0), (0.0, 0.3), (0.3, 0.0)}),
    frozenset({(9.0, 0.0), (9.0, 0.6), (9.6, 0.0)}),
}


def test_param_defaults():
    # KMeansTest.testParam analog
    kmeans = KMeans()
    assert kmeans.get_k() == 2
    assert kmeans.get_max_iter() == 20
    assert kmeans.get_features_col() == "features"
    assert kmeans.get_prediction_col() == "prediction"
    assert kmeans.get_distance_measure() == "euclidean"

    kmeans.set_k(9).set_max_iter(3).set_features_col("f")
    assert kmeans.get_k() == 9 and kmeans.get_max_iter() == 3

    with pytest.raises(Exception):
        KMeans().set_k(1)  # gtEq(2)


def test_fit_and_transform_exact_clusters():
    # KMeansTest.testFitAndPredict analog: exact cluster membership
    model = KMeans().set_max_iter(10).set_seed(3).fit(_table())
    out = model.transform(_table())[0]
    assert out.column_names == ["features", "prediction"]
    assert _clusters(out) == EXPECTED


def test_different_seeds_converge_same_clusters():
    for seed in range(5):
        model = KMeans().set_seed(seed).set_max_iter(20).fit(_table())
        assert _clusters(model.transform(_table())[0]) == EXPECTED


def test_prediction_col_rename():
    model = KMeans().set_prediction_col("cluster").fit(_table())
    out = model.transform(_table())[0]
    assert "cluster" in out.column_names
    assert _clusters(out, "cluster") == EXPECTED


def test_model_data_round_trip():
    model = KMeans().set_max_iter(5).fit(_table())
    (data,) = model.get_model_data()
    centroids = data["centroids"][0]
    assert centroids.shape == (2, 2)
    fresh = KMeansModel().set_model_data(Table({"centroids": centroids[None]}))
    assert _clusters(fresh.transform(_table())[0]) == EXPECTED


def test_save_load_estimator_and_model(tmp_path):
    # KMeansTest.testSaveLoad analog
    est_path, model_path = str(tmp_path / "est"), str(tmp_path / "model")
    kmeans = KMeans().set_k(2).set_max_iter(7).set_seed(1)
    kmeans.save(est_path)
    loaded_est = KMeans.load(est_path)
    assert loaded_est.get_max_iter() == 7

    model = loaded_est.fit(_table())
    model.save(model_path)
    loaded_model = KMeansModel.load(model_path)
    assert _clusters(loaded_model.transform(_table())[0]) == EXPECTED
    # reflective load too
    assert isinstance(persist.load_stage(model_path), KMeansModel)


def test_in_pipeline(tmp_path):
    pipeline = Pipeline([KMeans().set_max_iter(10)])
    pmodel = pipeline.fit(_table())
    assert _clusters(pmodel.transform(_table())[0]) == EXPECTED
    path = str(tmp_path / "pm")
    pmodel.save(path)
    from flink_ml_tpu import PipelineModel
    assert _clusters(PipelineModel.load(path).transform(_table())[0]) == EXPECTED


def test_select_random_centroids_semantics():
    pts = np.arange(20, dtype=np.float64).reshape(10, 2)
    c1 = select_random_centroids(pts, 3, seed=5)
    c2 = select_random_centroids(pts, 3, seed=5)
    np.testing.assert_array_equal(c1, c2)  # deterministic under seed
    assert len({tuple(r) for r in c1}) == 3  # distinct points
    with pytest.raises(ValueError):
        select_random_centroids(pts[:2], 3, seed=0)


_START_CASES = [
    (3, 1, 1), (3, 4096, 4096), (3, 1000, 1), (3, 1000, 1000),
    (5, 7, 3), (5, 8, 3), (5, 9, 3),                   # mask edges 2^3
    (11, 65535, 64), (11, 65536, 64), (11, 65537, 64),  # mask edges 2^16
    ((1 << 31) + 12345, 4097, 10),                      # seed above 2^31
    ((1 << 40) + 7, 50_000, 4096),
    (29, 2_025_000, 4096),                              # kmeans_mnist8m's
]


@pytest.mark.parametrize("seed,n,k", _START_CASES)
def test_random_start_is_numpys_permutation_prefix(seed, n, k):
    """The native pass gives ``default_rng(seed).permutation(n)[:k]``
    index for index and leaves the generator in NumPy's state; the start
    is those rows."""
    from flink_ml_tpu.models.clustering import kmeans as km

    lib = km._native_start()
    assert lib is not None
    native, numpy_ = np.random.default_rng(seed), np.random.default_rng(seed)
    want = numpy_.permutation(n)[:k]
    np.testing.assert_array_equal(km._draw_prefix(lib, native, n, k), want)
    assert native.bit_generator.state == numpy_.bit_generator.state
    points = np.arange(2 * n, dtype=np.float64).reshape(n, 2)
    np.testing.assert_array_equal(select_random_centroids(points, k, seed),
                                  points[want])


@pytest.mark.parametrize("loader", ["none", "declines"])
@pytest.mark.parametrize("seed,n,k", [(3, 1000, 10), (5, 9, 3),
                                      ((1 << 31) + 12345, 4097, 10)])
def test_random_start_without_the_native_pass_is_numpys(
        monkeypatch, loader, seed, n, k):
    """No library, or a pass that declines (as it does past the 32-bit
    limit or without its buffer): NumPy draws, with the same answer, and
    says so."""
    from types import SimpleNamespace

    from flink_ml_tpu.models.clustering import kmeans as km

    want = np.random.default_rng(seed).permutation(n)[:k]
    declining = SimpleNamespace(perm_prefix=lambda *args: 1)
    monkeypatch.setattr(km, "_native_start",
                        lambda: None if loader == "none" else declining)
    idx, native = km.random_start(n, k, seed)
    np.testing.assert_array_equal(idx, want)
    assert native is False


def test_native_start_declines_over_32_bits_before_drawing():
    """n - 1 past 2^32 - 1 (NumPy draws 64 bits there): the pass returns
    an error before its first draw, the generator untouched."""
    from flink_ml_tpu.models.clustering import kmeans as km

    rng = np.random.default_rng(1)
    before = rng.bit_generator.state
    assert km._draw_prefix(km._native_start(), rng, (1 << 32) + 1, 3) is None
    assert rng.bit_generator.state == before


def test_native_start_self_check_refuses_a_wrong_pass(monkeypatch):
    """The loader's one self-check a process: a pass that does not give
    this NumPy's permutation is not used."""
    from flink_ml_tpu.models.clustering import kmeans as km

    monkeypatch.setattr(km, "_draw_prefix",
                        lambda lib, rng, n, k: np.arange(k))
    km._native_start.cache_clear()
    try:
        assert km._native_start() is None
    finally:
        km._native_start.cache_clear()


def _fit_and_its_init_span(est, table):
    """The ``native`` note of the fit's span ``fit.arrange.init``, and the
    model's centroids."""
    from flink_ml_tpu.obs.trace import tracer

    tracer.enable()
    try:
        model = est.fit(table)
        (span,) = tracer.find("fit.arrange.init")
    finally:
        tracer.disable()
        tracer.clear()
    return (span.ids["native"],
            np.asarray(model.get_model_data()[0]["centroids"][0]))


def _start_table():
    return Table({"features": np.random.default_rng(4).normal(size=(300, 3))})


@pytest.mark.parametrize("init_mode,native", [("random", 1),
                                              ("k-means++", 0)])
def test_fit_notes_whether_the_start_was_native(init_mode, native):
    """``fit.arrange.init`` notes ``native``: 1 where the native pass drew
    the start, 0 where it did not."""
    est = KMeans().set_k(4).set_max_iter(2).set_seed(9)
    note, _ = _fit_and_its_init_span(est.set_init_mode(init_mode),
                                     _start_table())
    assert note == native


def test_fit_without_the_library_gives_the_same_model(monkeypatch):
    """No library: NumPy draws the start, the span says ``native`` 0 and
    the centroids are the native fit's to the bit."""
    from flink_ml_tpu.models.clustering import kmeans as km

    est = KMeans().set_k(4).set_max_iter(2).set_seed(9)
    native_note, native = _fit_and_its_init_span(est, _start_table())
    monkeypatch.setattr(km, "_native_start", lambda: None)
    numpy_note, numpy_ = _fit_and_its_init_span(est, _start_table())
    assert (native_note, numpy_note) == (1, 0)
    np.testing.assert_array_equal(native, numpy_)


def test_transform_without_model_data_errors():
    with pytest.raises(RuntimeError):
        KMeansModel().transform(_table())


def test_unpadded_vs_padded_identical():
    # 6 rows on an 8-device mesh forces padding; result must equal a
    # single-device (no padding needed) run via masking.
    m1 = KMeans().set_seed(0).set_max_iter(10).fit(_table())
    big = Table({"features": np.tile(DATA, (4, 1))})  # 24 rows: divisible by 8
    m2 = KMeans().set_seed(0).set_max_iter(10).fit(big)
    assert _clusters(m1.transform(_table())[0]) == EXPECTED
    assert _clusters(m2.transform(_table())[0]) == EXPECTED


def test_manhattan_distance_measure():
    model = (KMeans().set_distance_measure("manhattan").set_max_iter(10)
             .fit(_table()))
    assert _clusters(model.transform(_table())[0]) == EXPECTED


def test_pallas_epoch_step_matches_xla_step():
    # The fused-kernel body (interpret mode) must reproduce the XLA body on
    # zero-padded data.
    import jax.numpy as jnp

    from flink_ml_tpu.distance import DistanceMeasure
    from flink_ml_tpu.models.clustering.kmeans import (
        kmeans_epoch_step,
        kmeans_epoch_step_pallas,
    )

    rng = np.random.default_rng(3)
    pts = rng.normal(size=(256 - 11, 4)).astype(np.float32)
    padded = np.concatenate(
        [pts, np.zeros((11, 4), np.float32)]).astype(np.float32)
    mask = np.concatenate([np.ones(len(pts)), np.zeros(11)]).astype(np.float32)
    cents = pts[:5].copy()
    data = (jnp.asarray(padded), jnp.asarray(mask))

    xla_body = kmeans_epoch_step(DistanceMeasure.get_instance("euclidean"), 5)
    expected = np.asarray(xla_body(jnp.asarray(cents), 0, data).feedback)
    body = kmeans_epoch_step_pallas(5, block_n=128, interpret=True)
    got = np.asarray(body(jnp.asarray(cents), 0, data).feedback)
    np.testing.assert_allclose(got, expected, atol=1e-4)


def test_pallas_epoch_step_sharded_matches(cpu_mesh_8):
    import jax
    import jax.numpy as jnp

    from jax.sharding import PartitionSpec as P

    from flink_ml_tpu.models.clustering.kmeans import (
        _pad_points,
        kmeans_epoch_step_pallas,
    )
    from flink_ml_tpu.parallel.mesh import put_sharded, replicate

    rng = np.random.default_rng(4)
    pts = rng.normal(size=(1000, 4)).astype(np.float32)
    points, mask = (put_sharded(a, cpu_mesh_8, P("data"))
                    for a in _pad_points(pts, cpu_mesh_8, row_multiple=128,
                                         fill="zero"))
    assert points.shape[0] == 1024
    cents = replicate(pts[:5].copy(), cpu_mesh_8)

    single = kmeans_epoch_step_pallas(5, block_n=128, interpret=True)
    sharded = kmeans_epoch_step_pallas(5, cpu_mesh_8, block_n=128,
                                       interpret=True)
    expected = np.asarray(single(jnp.asarray(pts[:5].copy()), 0,
                                 (jnp.asarray(np.asarray(points)),
                                  jnp.asarray(np.asarray(mask)))).feedback)
    got = np.asarray(
        jax.jit(lambda c, d: sharded(c, 0, d).feedback)(cents, (points, mask)))
    np.testing.assert_allclose(got, expected, atol=1e-4)


def test_plan_fit_impl_gates():
    import jax

    from flink_ml_tpu.distance import DistanceMeasure
    from flink_ml_tpu.models.clustering import kmeans as km
    from flink_ml_tpu.parallel.mesh import default_mesh

    mesh = default_mesh()
    euclid = DistanceMeasure.get_instance("euclidean")
    cosine = DistanceMeasure.get_instance("cosine")
    if jax.default_backend() == "tpu":  # pragma: no cover - CPU suite
        assert km._plan_fit_impl(1 << 20, 64, 256, euclid, mesh)[0] == "pallas"
    # CPU backend always plans XLA
    else:
        assert km._plan_fit_impl(1 << 20, 64, 256, euclid, mesh)[0] == "xla"
    # small n / non-euclidean never plan pallas regardless of backend
    assert km._plan_fit_impl(100, 64, 256, euclid, mesh)[0] == "xla"
    assert km._plan_fit_impl(1 << 20, 64, 256, cosine, mesh)[0] == "xla"


@pytest.mark.parametrize("n,d,k,data_devs,block_n", [
    (20_000_000, 20, 10, 1, 32768),   # HiBench: the largest the tile admits
    (1 << 20, 64, 256, 1, 8192),      # chip_smoke.py's fit
    (1 << 20, 20, 10, 8, 16384),      # a shard of 2^17 rows: eight blocks
    (65536, 20, 10, 1, 8192),         # the smallest fit the kernel takes
    (70_000, 8, 4, 1, 8192),          # 8750 rows a block at most
])
def test_plan_leaves_a_shard_eight_blocks(monkeypatch, n, d, k, data_devs,
                                          block_n):
    """On a TPU the plan takes the largest block the VMEM model admits,
    halved until a shard holds eight of them: its fill rows stay under an
    eighth of it."""
    import jax

    from flink_ml_tpu.distance import DistanceMeasure
    from flink_ml_tpu.models.clustering import kmeans as km
    from flink_ml_tpu.parallel.mesh import device_mesh

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = device_mesh({"data": data_devs},
                       devices=jax.devices()[:data_devs])
    plan = km._fit_plan(n, d, k, DistanceMeasure.get_instance("euclidean"),
                        mesh)
    assert (plan.impl, plan.block_n, plan.row_multiple, plan.fill) == (
        "pallas", block_n, block_n, "zero")


def test_plan_tiles_over_k_where_k_does_not_fit_vmem(monkeypatch):
    """``kmeans_mnist8m``'s shapes: the plan is the kernel tiled over k at
    the tiles ``stats_tiles`` gives, noted on the span with the share of
    the MXU's operand area that is padding (784 on 896 lanes); HiBench's
    shapes plan what they planned before, block included."""
    import jax

    from flink_ml_tpu.distance import DistanceMeasure
    from flink_ml_tpu.models.clustering import kmeans as km
    from flink_ml_tpu.parallel.mesh import device_mesh

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = device_mesh({"data": 1}, devices=jax.devices()[:1])
    euclid = DistanceMeasure.get_instance("euclidean")
    plan = km._fit_plan(2_025_000, 784, 4096, euclid, mesh)
    assert (plan.impl, plan.block_n, plan.k_tile, plan.row_multiple,
            plan.fill) == ("pallas", 512, 512, 512, "zero")
    assert plan.notes() == {"stats_plan": "k_tiled", "block_n": 512,
                            "k_tile": 512,
                            "mxu_padded_share": 1.0 - 784 / 896,
                            "sums": "by_row"}
    hibench = km._fit_plan(20_000_000, 20, 10, euclid, mesh)
    assert hibench == km.FitPlan("pallas", 32768, 32768, "zero", 10, 20)
    assert hibench.notes()["stats_plan"] == "feature_major"
    assert hibench.notes()["sums"] == "onehot"
    # a small k too wide for the feature-major tile sums by row as well
    wide = km._fit_plan(2_025_000, 1536, 512, euclid, mesh)
    assert (wide.k_tile, wide.notes()["sums"]) == (512, "by_row")


def test_fit_through_the_ktiled_body_matches_plain_lloyd(monkeypatch):
    """``KMeans.fit`` under a plan that takes the kernel tiled over k (the
    interpreter stands in for the chip; the test steers the plan, no
    option of the program does) against Lloyd's algorithm written out in
    the kernel's stated arithmetic: operands of the scores rounded to
    bfloat16, the first index on a tie, an empty cluster keeps its
    centroid.  On whole grey levels the sums are exact on both sides."""
    import jax.numpy as jnp

    from flink_ml_tpu.models.clustering import kmeans as km

    rng = np.random.default_rng(11)
    protos = rng.integers(0, 256, size=(12, 48)).astype(np.float32)
    pts = np.clip(protos[rng.integers(0, 12, size=1000)]
                  + rng.integers(-9, 10, size=(1000, 48)), 0, 255
                  ).astype(np.float32)
    k, seed, rounds = 20, 5, 4

    def bf16(a):
        return np.asarray(jnp.asarray(a, jnp.float32).astype(jnp.bfloat16)
                          .astype(jnp.float32), np.float64)

    cents = km.select_random_centroids(pts, k, seed)
    for _ in range(rounds):
        scores = (cents.astype(np.float64) ** 2).sum(1)[None] - 2.0 * (
            bf16(pts) @ bf16(cents).T)
        assign = scores.argmin(1)
        counts = np.bincount(assign, minlength=k)
        sums = np.zeros((k, pts.shape[1]))
        np.add.at(sums, assign, pts)
        cents = np.where(counts[:, None] > 0,
                         sums / np.maximum(counts, 1)[:, None],
                         cents).astype(np.float32)

    step = km.kmeans_epoch_step_pallas
    monkeypatch.setattr(
        km, "_fit_plan", lambda n, d, k, measure, mesh, **how:
        km.FitPlan("pallas", 128, 128, "zero", k, d, k_tile=8))
    monkeypatch.setattr(
        km, "kmeans_epoch_step_pallas",
        lambda *a, **kw: step(*a, **kw, interpret=True))
    model = (KMeans().set_k(k).set_seed(seed).set_max_iter(rounds)
             .fit(Table({"features": pts})))
    got = np.asarray(model.get_model_data()[0]["centroids"][0])
    np.testing.assert_allclose(got, cents, rtol=1e-6)


# ---------------------------------------------------------------------------
# out-of-core fit (replay-per-epoch, the ReplayOperator analog at scale)
# ---------------------------------------------------------------------------

def _ooc_batches(pts, batch):
    def gen():
        for s in range(0, len(pts), batch):
            yield {"features": pts[s:s + batch]}
    return gen


def test_kmeans_outofcore_matches_incore_math():
    """Per-batch accumulation must reproduce the full-batch Lloyd's update
    exactly (same init): streaming is a layout change, not a math change."""
    import jax.numpy as jnp

    from flink_ml_tpu.distance import DistanceMeasure
    from flink_ml_tpu.models.clustering.kmeans import (
        kmeans_epoch_step,
        kmeans_fit_outofcore,
        select_random_centroids,
    )

    rng = np.random.default_rng(0)
    pts = rng.normal(size=(257, 5)).astype(np.float32)  # odd row count
    k, iters, batch = 4, 6, 64

    got = kmeans_fit_outofcore(_ooc_batches(pts, batch), k,
                               max_iter=iters, seed=3)

    body = kmeans_epoch_step(DistanceMeasure.get_instance("euclidean"), k)
    c = jnp.asarray(select_random_centroids(pts[:batch], k, 3))
    mask = jnp.ones((len(pts),), jnp.float32)
    for _ in range(iters):
        c = body(c, 0, (jnp.asarray(pts), mask)).feedback
    np.testing.assert_allclose(got, np.asarray(c), atol=1e-5)


def test_kmeans_outofcore_estimator_clusters(tmp_path):
    from flink_ml_tpu.data.datacache import DataCacheReader, DataCacheWriter

    rng = np.random.default_rng(1)
    centers = np.asarray([[6.0, 6.0], [-6.0, -6.0]], np.float32)
    pts = np.concatenate([c + rng.normal(scale=0.3, size=(150, 2))
                          for c in centers]).astype(np.float32)
    pts = pts[rng.permutation(len(pts))]

    cache = str(tmp_path / "cache")
    writer = DataCacheWriter(cache, segment_rows=128)
    for s in range(0, len(pts), 64):
        writer.append({"features": pts[s:s + 64]})
    writer.finish()

    model = (KMeans().set_k(2).set_max_iter(10)
             .fit_outofcore(lambda: DataCacheReader(cache, batch_rows=64)))
    got = np.sort(np.asarray(model.get_model_data()[0]["centroids"][0]),
                  axis=0)
    np.testing.assert_allclose(got, np.sort(centers, axis=0), atol=0.2)

    pred = np.asarray(
        model.transform(Table({"features": pts}))[0]["prediction"])
    assert len(np.unique(pred)) == 2


def test_kmeans_outofcore_empty_reader_raises():
    from flink_ml_tpu.models.clustering.kmeans import kmeans_fit_outofcore

    with pytest.raises(ValueError, match="empty"):
        kmeans_fit_outofcore(lambda: iter(()), 2, max_iter=2)


class TestKMeansPlusPlus:
    def test_seeding_picks_distinct_dataset_points(self):
        from flink_ml_tpu.models.clustering.kmeans import (
            select_kmeanspp_centroids)

        rng = np.random.default_rng(0)
        pts = rng.normal(size=(500, 3)).astype(np.float32)
        init = select_kmeanspp_centroids(pts, 8, seed=1)
        assert init.shape == (8, 3)
        # every chosen centroid IS a dataset point, all distinct
        matches = (np.abs(pts[None, :, :] - init[:, None, :])
                   .sum(-1) < 1e-7).any(axis=1)
        assert matches.all()
        assert len(np.unique(init.round(5), axis=0)) == 8
        # deterministic per seed
        np.testing.assert_array_equal(
            init, select_kmeanspp_centroids(pts, 8, seed=1))

    def test_covers_separated_clusters(self):
        from flink_ml_tpu.models.clustering.kmeans import (
            select_kmeanspp_centroids)

        rng = np.random.default_rng(2)
        centers = np.array([[0.0, 0.0], [50.0, 0.0], [0.0, 50.0]])
        pts = np.concatenate([c + 0.5 * rng.normal(size=(200, 2))
                              for c in centers]).astype(np.float32)
        init = select_kmeanspp_centroids(pts, 3, seed=0)
        # one seed per cluster: nearest true center of each pick is unique
        owner = np.argmin(((init[:, None, :] - centers[None])**2).sum(-1),
                          axis=1)
        assert set(owner) == {0, 1, 2}

    def test_estimator_init_mode_param(self):
        rng = np.random.default_rng(3)
        centers = np.array([[0.0, 0.0], [30.0, 0.0]])
        pts = np.concatenate([c + rng.normal(size=(100, 2))
                              for c in centers])
        t = Table({"features": pts})
        model = (KMeans().set_k(2).set_max_iter(10)
                 .set_init_mode("k-means++").fit(t))
        assign = np.asarray(model.transform(t)[0]["prediction"])
        assert len(set(assign[:100])) == 1 and len(set(assign[100:])) == 1
        assert assign[0] != assign[100]
        with pytest.raises(Exception):
            KMeans().set_init_mode("banana")


def test_first_index_rule_matches_argmin_under_real_ties():
    """The stats kernel must reproduce numpy first-index argmin EXACTLY
    on discrete data with real ties.  This is the reference's Lloyd's
    semantics (KMeans.java:238-315 assigns each point to exactly one
    centroid)."""
    import jax.numpy as jnp

    from flink_ml_tpu.ops.kmeans_pallas import kmeans_update_stats

    rng = np.random.default_rng(0)
    pts = rng.integers(0, 3, size=(1024, 8)).astype(np.float32)
    cents = np.stack([
        pts[0], pts[1],
        pts[0] + np.eye(8, dtype=np.float32)[0],
        pts[0] - np.eye(8, dtype=np.float32)[0]])
    d2 = ((pts[:, None, :] - cents[None, :, :]) ** 2).sum(-1)
    assert int(((d2 == d2.min(1, keepdims=True)).sum(1) > 1).sum()) > 0

    sums, counts = kmeans_update_stats(
        jnp.asarray(pts), jnp.asarray(cents), block_n=1024, interpret=True)
    assign = d2.argmin(1)
    want_counts = np.bincount(assign, minlength=4).astype(np.float64)
    want_sums = np.zeros((4, 8))
    np.add.at(want_sums, assign, pts)
    np.testing.assert_allclose(np.asarray(counts, np.float64), want_counts,
                               atol=1e-3)
    np.testing.assert_allclose(np.asarray(sums, np.float64), want_sums,
                               rtol=1e-5, atol=1e-3)
    # total mass is exactly n: a tied point counts once
    assert float(np.asarray(counts).sum()) == len(pts)


def test_kmeans_outofcore_epoch_aware_shuffled_reader(tmp_path):
    """An epoch-aware ShuffledCacheReader factory (the sgd streaming
    protocol) drives out-of-core Lloyd's: each iteration receives its
    epoch number, the permuted stream carries the same row multiset, and
    the fit recovers the true generating centers (init draws from epoch
    0's first shuffled batch, so the whole run is deterministic in the
    pinned seeds)."""
    from flink_ml_tpu.data.datacache import (
        DataCacheReader,
        DataCacheWriter,
        ShuffledCacheReader,
    )
    from flink_ml_tpu.models.clustering.kmeans import kmeans_fit_outofcore

    rng = np.random.default_rng(4)
    centers = np.array([[0.0, 0.0], [8.0, 8.0], [-8.0, 8.0]], np.float32)
    pts = np.concatenate([
        centers[i] + rng.normal(scale=0.3, size=(200, 2)).astype(np.float32)
        for i in range(3)])
    rng.shuffle(pts)
    cache = str(tmp_path / "kmshuf")
    w = DataCacheWriter(cache, segment_rows=256)
    w.append({"features": pts})
    w.finish()

    # seed pinned to a converging random init (random Lloyd init can
    # collapse two centroids onto a midpoint regardless of the reader)
    got = kmeans_fit_outofcore(
        lambda epoch: ShuffledCacheReader(cache, batch_rows=128,
                                          seed=3, epoch=epoch),
        k=3, max_iter=8, seed=1)
    # every true center recovered within the cluster noise scale
    d = np.linalg.norm(got[:, None, :] - centers[None, :, :], axis=-1)
    assert d.min(axis=0).max() < 0.5


# -- workset (delta-iteration) fit, ISSUE 9 ----------------------------------

def _blob_table(n, d=16, k=5, seed=0, spread=8.0, noise=0.4):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(k, d)) * spread
    lab = rng.integers(0, k, n)
    X = centers[lab] + rng.normal(size=(n, d)) * noise
    return Table({"features": X.astype(np.float32)})


@pytest.mark.parametrize("n", [4096, 4003])   # exact multiple + padded tail
def test_workset_kmeans_bitexact_vs_bsp(n):
    """Acceptance: on the virtual 8-device mesh the bound-filtered fit's
    final centroids are BIT-identical to BSP with and without a padded
    tail, the while_loop exits strictly before maxIter, and the
    points scored per round decay below 20% of n before convergence."""
    k, max_iter = 5, 60
    table = _blob_table(n, k=k, seed=3)
    bsp = (KMeans().set_k(k).set_max_iter(max_iter).set_seed(7)
           .fit(table))
    est = (KMeans().set_k(k).set_max_iter(max_iter).set_seed(7)
           .set_workset(True))
    wk = est.fit(table)

    c_bsp = bsp.get_model_data()[0]["centroids"][0]
    c_wk = wk.get_model_data()[0]["centroids"][0]
    np.testing.assert_array_equal(c_bsp, c_wk)

    rep = est.last_workset_report
    assert rep["rounds"] < max_iter            # convergence-driven exit
    assert rep["rounds"] == len(rep["active_fraction"])
    assert rep["n_points"] == n
    # bound filter bites: some pre-convergence round scores < 20% of n
    scored = rep["points_scored"]
    assert scored[0] == n                      # round 0 = BSP full rescore
    assert scored[:-1].min() < 0.2 * n
    # the workset drains exactly at the exit round
    assert rep["active_fraction"][-1] == 0.0


def test_workset_kmeans_report_absent_on_bsp_fit():
    est = KMeans().set_k(2).set_max_iter(5)
    est.fit(_table())
    assert getattr(est, "last_workset_report", None) is None


def test_workset_param_default_off_and_roundtrips(tmp_path):
    est = KMeans().set_k(3).set_workset(True)
    assert KMeans().get_workset() is False
    est.save(str(tmp_path / "est"))
    assert KMeans.load(str(tmp_path / "est")).get_workset() is True


def test_saved_kmeans_with_the_retired_tie_param_loads_and_fits_as_without(
        tmp_path):
    """An estimator saved while KMeans had a param for its tie rule
    still loads: the unknown name is skipped, and the estimator fits
    exactly as one saved without it (every fit takes the first-index
    rule)."""
    import json
    import os

    path = str(tmp_path / "est")
    KMeans().set_k(2).set_max_iter(5).set_seed(3).save(path)
    plain = KMeans.load(path)
    meta_path = os.path.join(path, "metadata")
    with open(meta_path) as f:
        meta = json.load(f)
    retired = "tiePolicy"
    meta["paramMap"][retired] = "split"
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    old = KMeans.load(path)
    assert old.get_param(retired) is None
    assert old.params_to_json() == plain.params_to_json()

    def centroids(est):
        return np.asarray(est.fit(_table()).get_model_data()[0]
                          ["centroids"][0])

    assert centroids(old).tobytes() == centroids(plain).tobytes()


def test_workset_requires_euclidean():
    from flink_ml_tpu.distance import DistanceMeasure
    from flink_ml_tpu.models.clustering.kmeans import (
        kmeans_workset_epoch_step)

    with pytest.raises(ValueError, match="euclidean"):
        kmeans_workset_epoch_step(
            DistanceMeasure.get_instance("manhattan"), 3)


def test_fit_plan_workset_initializer_settles_padding():
    """Satellite: the shared FitPlan bound-state initializer — padding
    rows are born settled (never active, never scored), real rows start
    with the vacuous full-rescore bounds."""
    import jax.numpy as jnp

    from flink_ml_tpu.distance import DistanceMeasure
    from flink_ml_tpu.models.clustering.kmeans import _fit_plan
    from flink_ml_tpu.parallel.mesh import default_mesh

    euclid = DistanceMeasure.get_instance("euclidean")
    plan = _fit_plan(100, 4, 3, euclid, default_mesh(), workset=True)
    assert plan.impl == "xla" and plan.row_multiple == 1
    pad_mask = jnp.asarray([1.0, 1.0, 1.0, 0.0, 0.0])
    ws = plan.init_workset(pad_mask)
    np.testing.assert_array_equal(np.asarray(ws.mask), [1, 1, 1, 0, 0])
    assert np.all(np.isinf(np.asarray(ws.bounds["upper"])))
    assert np.all(np.asarray(ws.bounds["lower"]) == -np.inf)
    np.testing.assert_array_equal(np.asarray(ws.bounds["assign"]),
                                  np.zeros(5))


# -- ISSUE 28: the points reach the device from the table's own buffer ------

def _column_of(kind: str, n: int, d: int = 5):
    """A features column of each kind ``KMeans.fit`` tells apart."""
    from flink_ml_tpu.linalg import DenseVector

    rng = np.random.default_rng(28)
    base = (100.0 * rng.normal(size=(n, d))).astype(np.float32)
    if kind == "f32_c":
        return base
    if kind == "f32_1d":
        return np.ascontiguousarray(base[:, 0])
    if kind == "f64":
        return 100.0 * rng.normal(size=(n, d))     # rounds on the way down
    if kind == "f32_fortran":
        return np.asfortranarray(base)
    if kind == "i32":
        return (base * 1e5).astype(np.int32)       # beyond float32's 2^24
    if kind == "i64":
        return (base.astype(np.float64) * 1e14).astype(np.int64)
    assert kind == "object"
    col = np.empty((n,), object)
    for i, row in enumerate(base.astype(np.float64)):
        col[i] = DenseVector(row)
    return col


_COLUMN_KINDS = ["f32_c", "f32_1d", "f64", "f32_fortran", "i32", "object"]


@pytest.fixture
def small_relayout_pieces(monkeypatch):
    """``_rows_from_pieces`` at 64 rows a relayout step, so that a few
    hundred rows take the loop the chip takes at 20 M (its programs are
    kept per shapes, so they are dropped on the way in and out)."""
    from flink_ml_tpu.models.clustering import kmeans as km

    km._rows_from_pieces.cache_clear()
    monkeypatch.setattr(km, "_RELAYOUT_ROWS", 64)
    yield
    km._rows_from_pieces.cache_clear()


def _parent_fit(column, mesh, *, k, seed, max_iter, row_multiple, fill):
    """The centroids as the parent commit's ``_fit`` made them: its three
    host copies, kept here as the expectation, then the same program."""
    from jax.sharding import PartitionSpec as P

    from flink_ml_tpu.distance import DistanceMeasure
    from flink_ml_tpu.iteration import IterationConfig, iterate
    from flink_ml_tpu.linalg import stack_vectors
    from flink_ml_tpu.models.clustering.kmeans import kmeans_epoch_step
    from flink_ml_tpu.parallel.mesh import (
        fetch_replicated, local_axis_multiple, put_sharded, replicate)
    from flink_ml_tpu.utils.padding import pad_rows_with_mask

    host_points = stack_vectors(column)
    host_points = host_points.astype(np.float32)
    padded, mask = pad_rows_with_mask(
        host_points, local_axis_multiple(mesh, row_multiple=row_multiple),
        fill=fill)

    init = select_random_centroids(host_points, k, seed)
    result = iterate(
        kmeans_epoch_step(DistanceMeasure.get_instance("euclidean"), k),
        replicate(init, mesh),
        (put_sharded(padded, mesh, P("data")),
         put_sharded(mask, mesh, P("data"))),
        max_epochs=max_iter, config=IterationConfig(mode="fused"))
    return np.asarray(fetch_replicated(result.state))


def _fit_recording_puts(monkeypatch, column, mesh, *, row_multiple, fill):
    """``KMeans.fit`` on ``mesh`` under a plan that pads to
    ``row_multiple`` (off the chip the XLA plan asks for no multiple, so
    the test steers it); returns the centroids, the parent's, the host
    arrays the fit handed to ``put_sharded`` and the flat float32 host
    arrays it handed to ``jax.device_put``."""
    import jax

    from flink_ml_tpu.models.clustering import kmeans as km
    from flink_ml_tpu.parallel.mesh import use_mesh

    monkeypatch.setattr(
        km, "_fit_plan",
        lambda n, d, k, measure, mesh, **how:
        km.FitPlan("xla", None, row_multiple, fill, k, d))
    puts, handed = [], []
    real_put, real_device_put = km.put_sharded, jax.device_put
    monkeypatch.setattr(
        km, "put_sharded",
        lambda arr, *a: (puts.append(arr), real_put(arr, *a))[1])
    monkeypatch.setattr(
        jax, "device_put", lambda x, *a, **kw: (
            handed.append(x) if isinstance(x, np.ndarray) and x.ndim == 1
            and x.dtype == np.float32 and x.size else None,
            real_device_put(x, *a, **kw))[1])
    args = dict(k=4, seed=7, max_iter=4)
    with use_mesh(mesh):
        model = (KMeans().set_k(args["k"]).set_seed(args["seed"])
                 .set_max_iter(args["max_iter"])
                 .fit(Table({"features": column})))
        monkeypatch.setattr(jax, "device_put", real_device_put)
        expected = _parent_fit(column, mesh, row_multiple=row_multiple,
                               fill=fill, **args)
    (data,) = model.get_model_data()
    return np.asarray(data["centroids"][0]), expected, puts, handed


def _column_bytes(column) -> bytes:
    if column.dtype == object:
        return np.stack([v.values for v in column]).tobytes()
    return column.tobytes()


@pytest.mark.parametrize("n", [256, 250])    # a multiple of 64, and not one
@pytest.mark.parametrize("kind", _COLUMN_KINDS)
def test_fit_reads_the_column_in_place_bitexact_vs_parent(
        monkeypatch, small_relayout_pieces, kind, n):
    """One process, one device on the ``data`` axis (the benchmark's
    mesh): the rows go up as they are, flat, and the device gives them
    their layout, the mask and the fill rows.  Centroids equal the
    parent's path bit for bit; a float32 C-contiguous column is handed to
    the put WITHOUT a copy; no route writes into the user's table (on the
    CPU backend a put may alias host memory, so this also holds
    ``iterate`` to it)."""
    import jax

    from flink_ml_tpu.parallel.mesh import device_mesh

    column = _column_of(kind, n)
    before = _column_bytes(column)
    got, expected, puts, handed = _fit_recording_puts(
        monkeypatch, column, device_mesh(devices=jax.devices()[:1]),
        row_multiple=64, fill="zero")

    assert got.dtype == np.float32 and got.shape == (4, 1 if kind == "f32_1d"
                                                     else 5)
    assert got.tobytes() == expected.tobytes()
    assert puts == []                        # no 2-D put, no host pad
    (points_put,) = handed                   # pad and mask: the device's
    assert points_put.flags.c_contiguous
    assert points_put.shape == (n * got.shape[1],)   # flat and unpadded
    in_place = kind in ("f32_c", "f32_1d")
    assert (column.dtype != object
            and np.shares_memory(points_put, column)) == in_place
    assert _column_bytes(column) == before


@pytest.mark.parametrize("n", [256, 250])
def test_fit_on_a_sharding_mesh_pads_on_its_devices_bitexact_vs_parent(
        monkeypatch, cpu_mesh_8, n):
    """Eight devices on the ``data`` axis of one process: the rows are
    sharded, and every device gets its run of them flat and pads it
    itself (``_put_and_lay_out``; 8 x 16 = 128 a multiple): the
    centroids are those of the parent's host pad (``_pad_points``, kept
    as the expectation in ``_parent_fit``), and what is handed to a
    device is a view of the column, remainder or not."""
    column = _column_of("f32_c", n)
    before = column.tobytes()
    got, expected, puts, handed = _fit_recording_puts(
        monkeypatch, column, cpu_mesh_8, row_multiple=16, fill="zero")
    assert got.tobytes() == expected.tobytes()
    assert all(np.shares_memory(a, column) for a in handed)
    assert sum(a.size for a in handed) == n * 5      # each row put once
    assert puts == []        # no 2-D put of the rows, no host pad
    assert column.tobytes() == before


@pytest.mark.parametrize("fill", ["zero", "first_row"])
@pytest.mark.parametrize("shape", [(250, 5), (250, 1), (256, 5), (40, 5)])
def test_rows_on_device_equals_the_host_pad(small_relayout_pieces, shape,
                                            fill):
    """The one ingest route on a one-device mesh, the table in one put:
    one relayout step (40 rows), whole steps (256 = 4 x 64), and a last
    step that overlaps the one before it (250)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from flink_ml_tpu.models.clustering.kmeans import (FitPlan,
                                                       _put_and_lay_out)
    from flink_ml_tpu.parallel.mesh import device_mesh, put_sharded
    from flink_ml_tpu.utils.backend import count_compiles
    from flink_ml_tpu.utils.padding import pad_rows_with_mask

    mesh = device_mesh(devices=jax.devices()[:1])
    pts = np.random.default_rng(5).normal(size=shape).astype(np.float32)
    want = pad_rows_with_mask(pts, 64, fill=fill)
    plan = FitPlan("xla", None, 64, fill, 4, shape[1])

    def on_device(rows):
        return _put_and_lay_out(rows, plan, mesh, P("data"))

    for got, expected in zip(on_device(pts), want):
        assert got.sharding == put_sharded(expected, mesh, P("data")).sharding
        assert got.dtype == expected.dtype and got.shape == expected.shape
        assert np.asarray(got).tobytes() == expected.tobytes()
    # one program per shapes, kept: the same shapes compile once
    with count_compiles() as compiles:
        on_device(pts + 1)
    assert compiles() == 0


@pytest.mark.parametrize("fill", ["zero", "first_row"])
@pytest.mark.parametrize("n,put_rows", [(250, 128), (256, 128), (300, 192),
                                        (100, 192)])
def test_rows_put_in_pieces_equal_the_host_pad(small_relayout_pieces, n,
                                               put_rows, fill):
    """A buffer put in pieces of ``put_rows`` rows (a last piece shorter
    than the rest, shorter than a relayout step, or the only one) is laid
    out as the whole buffer is."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from flink_ml_tpu.models.clustering.kmeans import _rows_from_pieces
    from flink_ml_tpu.parallel.mesh import device_mesh, put_sharded
    from flink_ml_tpu.utils.padding import pad_rows_with_mask

    mesh = device_mesh(devices=jax.devices()[:1])
    pts = np.random.default_rng(6).normal(size=(n, 5)).astype(np.float32)
    empty, place, finish = _rows_from_pieces(
        (n, 5), -n % 64, fill, NamedSharding(mesh, P("data")))
    points, mask = empty()
    for first in range(0, n, put_rows):
        points = place(points, put_sharded(
            pts[first:first + put_rows].reshape(-1), mesh, P("data")), first)
    got = finish(points), mask
    for have, expected in zip(got, pad_rows_with_mask(pts, 64, fill=fill)):
        assert np.asarray(have).tobytes() == expected.tobytes()


def test_fit_from_a_table_put_in_pieces_is_the_fit_from_one_put(
        monkeypatch, small_relayout_pieces):
    """``KMeans.fit`` on a one-device mesh with the thresholds lowered so
    that 300 rows go up in three puts: the same centroids, bit for bit,
    as from the one put."""
    import jax

    from flink_ml_tpu.parallel import mesh as pm
    from flink_ml_tpu.parallel.mesh import device_mesh, use_mesh

    pts = np.random.default_rng(8).normal(size=(300, 5)).astype(np.float32)

    def fit():
        with use_mesh(device_mesh(devices=jax.devices()[:1])):
            model = (KMeans().set_k(4).set_seed(3).set_max_iter(3)
                     .fit(Table({"features": pts})))
        return np.asarray(model.get_model_data()[0]["centroids"][0])

    whole = fit()
    puts = []
    real_put = jax.device_put
    monkeypatch.setattr(jax, "device_put", lambda x, *a, **kw: (
        puts.append(x) if isinstance(x, np.ndarray) and x.ndim == 1
        else None, real_put(x, *a, **kw))[1])
    monkeypatch.setattr(pm, "PUT_BYTES", 2 * 64 * 5 * 4)
    assert fit().tobytes() == whole.tobytes()
    assert [a.shape for a in puts] == [(640,), (640,), (220,)]
    assert all(np.shares_memory(a, pts) for a in puts)


def test_put_rows_pieces_only_what_one_put_is_slow_at():
    """HiBench's 1.6 GB go in one put, as before; ``kmeans_mnist8m``'s
    6.35 GB in four pieces of whole relayout steps, 2 GiB at most (the
    cap is ``parallel/mesh.py``'s since PR 39; a shard of
    ``kmeans_mnist8m_full`` is cut the same way)."""
    from flink_ml_tpu.models.clustering import kmeans as km
    from flink_ml_tpu.parallel.mesh import PUT_BYTES, rows_a_put

    assert PUT_BYTES == 1 << 31
    assert km._put_rows(2_025_472, 784) == 655360
    # ... and four chips' pieces of a round are 2 GiB in all: the cap is
    # on what the process has in flight (16 rounds of 4 x 411 MB)
    assert km._put_rows(2_025_472, 784, 4) == 131072
    assert rows_a_put(10, 8) == 10 and rows_a_put(1 << 30, 8, 1000) == 268435000

    assert km._put_rows(20_000_000, 20) == 20_000_000
    rows = km._put_rows(2_025_000, 784)
    assert rows % km._RELAYOUT_ROWS == 0 and rows == 655360
    assert 4 * rows * 784 <= 1 << 31
    assert km._put_rows(10_000_000, 100_000) == km._RELAYOUT_ROWS


@pytest.mark.parametrize("kind", _COLUMN_KINDS + ["i64"])
def test_float32_rows_values_and_copies(kind):
    """``float32_rows`` gives ``stack_vectors(column).astype(float32)``'s
    values on every route, and the column itself where it can."""
    from flink_ml_tpu.linalg import float32_rows, stack_vectors

    column = _column_of(kind, 37)
    got = float32_rows(column)
    expected = stack_vectors(column).astype(np.float32)
    assert got.dtype == np.float32 and got.flags.c_contiguous
    assert got.shape == expected.shape
    assert got.tobytes() == expected.tobytes()
    assert (column.dtype != object and np.shares_memory(got, column)) == (
        kind in ("f32_c", "f32_1d"))


# ---------------------------------------------------------------------------
# the epoch bodies' program keys (iteration/body.py: with_program_key)
# ---------------------------------------------------------------------------

def _centroids(model):
    return np.asarray(model.get_model_data()[0]["centroids"][0])


def _blobs(n=1024, d=8, seed=2):
    return Table({"features": np.random.default_rng(seed).normal(
        size=(n, d)).astype(np.float32)})


def _steer_to_the_kernel(monkeypatch, km, k_tile=None):
    """The plan and the body a TPU would take, the interpreter standing
    in for the chip (the hook ``test_fit_through_the_ktiled_body_...``
    uses)."""
    step = km.kmeans_epoch_step_pallas
    monkeypatch.setattr(
        km, "_fit_plan", lambda n, d, k, measure, mesh, **how:
        km.FitPlan("pallas", 128, 128, "zero", k, d, k_tile=k_tile))
    monkeypatch.setattr(
        km, "kmeans_epoch_step_pallas",
        lambda *a, **kw: step(*a, **{**kw, "interpret": True}))


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_a_second_fit_of_one_table_reuses_the_firsts_program(
        impl,
        monkeypatch,
        fit_noting_reuse):
    from flink_ml_tpu.models.clustering import kmeans as km

    if impl == "pallas":
        _steer_to_the_kernel(monkeypatch, km)
    table = _blobs()

    def est():
        return KMeans().set_k(5).set_max_iter(3).set_seed(7)

    first, reused_first = fit_noting_reuse(est(), table)
    second, reused_second = fit_noting_reuse(est(), table)
    assert (reused_first, reused_second) == (0, 1)
    assert _centroids(second).tobytes() == _centroids(first).tobytes()
    # another start is data, not program
    third, reused = fit_noting_reuse(est().set_seed(8), table)
    assert reused == 1
    assert _centroids(third).tobytes() != _centroids(first).tobytes()


@pytest.mark.parametrize("what", ["k", "max_iter", "measure", "rows"])
def test_another_k_or_shape_is_another_program(what, fit_noting_reuse):
    table = _blobs()
    est = KMeans().set_k(5).set_max_iter(3).set_seed(7)
    _, reused = fit_noting_reuse(est, table)
    assert reused == 0
    if what == "k":
        est = est.set_k(6)
    elif what == "max_iter":
        est = est.set_max_iter(4)
    elif what == "measure":
        est = est.set_distance_measure("cosine")
    else:
        table = _blobs(n=1000)
    _, reused = fit_noting_reuse(est, table)
    assert reused == 0
    _, reused = fit_noting_reuse(est, table)
    assert reused == 1


def test_a_fit_planned_onto_the_kernel_is_not_served_the_xla_fits_program(
        monkeypatch,
        fit_noting_reuse):
    """Equal shapes (rows padded to the same multiple, zero fill), the
    stats through the XLA body, then through the kernel's two layouts:
    the factory and its tiles are in the key, so each builds its own
    program, and all three end at the same centroids."""
    from flink_ml_tpu.models.clustering import kmeans as km

    table = _blobs()
    est = KMeans().set_k(5).set_max_iter(3).set_seed(7)
    monkeypatch.setattr(
        km, "_fit_plan", lambda n, d, k, measure, mesh, **how:
        km.FitPlan("xla", None, 128, "zero", k, d))
    plain, reused = fit_noting_reuse(est, table)
    assert reused == 0
    _steer_to_the_kernel(monkeypatch, km)
    feature_major, reused = fit_noting_reuse(est, table)
    assert reused == 0
    _steer_to_the_kernel(monkeypatch, km, k_tile=8)
    k_tiled, reused = fit_noting_reuse(est, table)
    assert reused == 0
    np.testing.assert_allclose(_centroids(feature_major), _centroids(plain),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(_centroids(k_tiled), _centroids(plain),
                               rtol=2e-2, atol=2e-2)
    _, reused = fit_noting_reuse(est, table)
    assert reused == 1
