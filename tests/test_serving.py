"""Serving runtime tests: save -> load -> serve round trips per model
family (bit-exact with offline transform), zero-recompile steady state,
atomic hot-swap under concurrent load, admission control, micro-batcher
coalescing, bucket padding helpers, prefetch metric gauges, and the
diagnosable persist load errors the registry depends on."""

import json
import os
import threading

import numpy as np
import pytest

from flink_ml_tpu import Table
from flink_ml_tpu.serving import (
    MicroBatcher,
    ModelRegistry,
    ServingEndpoint,
    ServingOverloadedError,
    make_servable,
    serve_model,
)
from flink_ml_tpu.utils.padding import (
    bucket_rows,
    bucket_sizes,
    pad_rows_to_bucket,
)


def _lr_table(n=64, d=8, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d))
    y = (X[:, 0] + 0.3 * rng.normal(size=n) > 0).astype(np.int64)
    return Table({"features": X, "label": y})


def _fit_lr(seed=0):
    from flink_ml_tpu.models.classification.logisticregression import (
        LogisticRegression)

    return LogisticRegression().set_max_iter(5).fit(_lr_table(seed=seed))


def _requests(table, sizes):
    """Non-overlapping request tables of the given row counts."""
    out, start = [], 0
    for s in sizes:
        out.append(table.slice(start, start + s))
        start += s
    return out


# -- bucket padding helpers --------------------------------------------------

def test_bucket_rows_ladder():
    assert bucket_rows(1) == 8 and bucket_rows(8) == 8
    assert bucket_rows(9) == 16
    assert bucket_rows(100) == 128
    assert bucket_rows(3, min_bucket=2) == 4
    assert bucket_sizes(64) == (8, 16, 32, 64)
    assert bucket_sizes(100) == (8, 16, 32, 64, 128)
    with pytest.raises(ValueError):
        bucket_rows(4, min_bucket=0)


def test_pad_rows_to_bucket_caps_huge_batches():
    from flink_ml_tpu.utils.padding import DEFAULT_BUCKET_CAP

    big = np.ones((DEFAULT_BUCKET_CAP + 1, 2), np.float32)
    (padded,), n = pad_rows_to_bucket((big,))
    assert padded.shape[0] == n == DEFAULT_BUCKET_CAP + 1  # exact shape kept
    (padded,), n = pad_rows_to_bucket((np.ones((9, 2), np.float32),),
                                      max_bucket_rows=None)
    assert padded.shape[0] == 16 and n == 9    # None = unlimited bucketing
    with pytest.raises(ValueError, match="bucket cap"):
        make_servable(_fit_lr(), _lr_table().drop("label").take(1),
                      max_batch_rows=DEFAULT_BUCKET_CAP * 2)


def test_pad_rows_to_bucket_zero_fill():
    a = np.arange(10, dtype=np.float32).reshape(5, 2)
    idx = np.ones((5, 3), np.int32)
    (pa, pidx), n = pad_rows_to_bucket((a, idx))
    assert n == 5 and pa.shape == (8, 2) and pidx.shape == (8, 3)
    np.testing.assert_array_equal(pa[:5], a)
    assert not pa[5:].any() and not pidx[5:].any()
    # exact bucket size: no copy path still returns the same rows
    (pb,), n = pad_rows_to_bucket((np.ones((8, 2), np.float32),))
    assert n == 8 and pb.shape == (8, 2)


# -- save -> load -> serve round trips, bit-exact with offline transform -----

def _roundtrip_serve(model, load_cls, request_tables, tmp_path,
                     example=None):
    """save -> load_stage -> deploy (warmed) -> serve each request; every
    response must be BIT-exact with the loaded model's offline
    transform."""
    from flink_ml_tpu.utils import persist

    path = str(tmp_path / "model")
    model.save(path)
    loaded = persist.load_stage(path)
    assert isinstance(loaded, load_cls)

    example = example if example is not None else request_tables[0]
    registry = ModelRegistry()
    registry.deploy("m", path, example, max_batch_rows=64)
    endpoint = ServingEndpoint(registry, "m", max_wait_ms=0.5).start()
    try:
        for req in request_tables:
            served = endpoint.predict(req)
            offline = loaded.transform(req)[0]
            assert served.column_names == offline.column_names
            for col in offline.column_names:
                np.testing.assert_array_equal(served[col], offline[col])
    finally:
        endpoint.close()


def test_roundtrip_logisticregression(tmp_path):
    from flink_ml_tpu.models.classification.logisticregression import (
        LogisticRegressionModel)

    model = _fit_lr()
    reqs = _requests(_lr_table(seed=3).drop("label"), (1, 3, 8, 13, 30))
    _roundtrip_serve(model, LogisticRegressionModel, reqs, tmp_path)


def test_roundtrip_linearregression(tmp_path):
    from flink_ml_tpu.models.regression.linearregression import (
        LinearRegression, LinearRegressionModel)

    rng = np.random.default_rng(1)
    X = rng.normal(size=(64, 6))
    t = Table({"features": X, "label": X @ rng.normal(size=6) + 0.2})
    model = LinearRegression().set_max_iter(5).fit(t)
    reqs = _requests(t.drop("label"), (2, 5, 16, 31))
    _roundtrip_serve(model, LinearRegressionModel, reqs, tmp_path)


def test_roundtrip_kmeans(tmp_path):
    from flink_ml_tpu.models.clustering.kmeans import KMeans, KMeansModel

    rng = np.random.default_rng(2)
    pts = np.concatenate([rng.normal(loc=c, size=(20, 3))
                          for c in (-4.0, 0.0, 4.0)]).astype(np.float32)
    model = KMeans().set_k(3).set_max_iter(5).fit(Table({"features": pts}))
    reqs = _requests(Table({"features": pts}), (1, 7, 20, 32))
    _roundtrip_serve(model, KMeansModel, reqs, tmp_path)


def test_roundtrip_gbt_classifier(tmp_path):
    from flink_ml_tpu.models.classification.gbtclassifier import (
        GBTClassifier, GBTClassifierModel)

    t = _lr_table(n=96, seed=4)
    model = (GBTClassifier().set_max_iter(3).set_max_depth(2)
             .set_max_bins(16).fit(t))
    reqs = _requests(t.drop("label"), (1, 5, 12, 40))
    _roundtrip_serve(model, GBTClassifierModel, reqs, tmp_path)


def test_roundtrip_gbt_regressor(tmp_path):
    from flink_ml_tpu.models.regression.gbtregressor import (
        GBTRegressor, GBTRegressorModel)

    rng = np.random.default_rng(5)
    X = rng.normal(size=(96, 5))
    t = Table({"features": X, "label": X[:, 0] * 2 + X[:, 1]})
    model = (GBTRegressor().set_max_iter(3).set_max_depth(2)
             .set_max_bins(16).fit(t))
    reqs = _requests(t.drop("label"), (2, 9, 33))
    _roundtrip_serve(model, GBTRegressorModel, reqs, tmp_path)


def test_roundtrip_widedeep(tmp_path):
    from flink_ml_tpu.models.recommendation.widedeep import (
        WideDeep, WideDeepModel)

    rng = np.random.default_rng(6)
    n = 128
    dense = rng.normal(size=(n, 4)).astype(np.float32)
    cat = np.stack([rng.integers(0, 10, size=n),
                    rng.integers(0, 7, size=n)], axis=1).astype(np.int32)
    label = (cat[:, 0] > 4).astype(np.int64)
    t = Table({"denseFeatures": dense, "catFeatures": cat, "label": label})
    model = WideDeep().set_vocab_sizes([10, 7]).set_max_iter(5).fit(t)
    reqs = _requests(t.drop("label"), (1, 6, 14, 32))
    _roundtrip_serve(model, WideDeepModel, reqs, tmp_path)


# -- zero retraces in steady state -------------------------------------------

def test_zero_recompile_steady_state():
    from flink_ml_tpu.utils.backend import count_compiles

    model = _fit_lr()
    feats = _lr_table(n=128, seed=7).drop("label")
    endpoint = serve_model(model, feats.take(2), max_batch_rows=64,
                           max_wait_ms=0.5)
    try:
        # settle wave: anything lazily built outside the warm-up ladder
        # (e.g. weight device_puts) happens here
        for n in (1, 2, 64):
            endpoint.predict(feats.take(n))
        with count_compiles() as count:
            for n in (1, 3, 4, 7, 8, 11, 16, 23, 33, 48, 64):
                endpoint.predict(feats.take(n))
        assert count() == 0, (
            f"{count()} new XLA lowerings in steady state — the bucket "
            "warm-up did not cover the serving shapes")
    finally:
        endpoint.close()


def test_warmup_required_before_start():
    registry = ModelRegistry()
    endpoint = ServingEndpoint(registry, "missing")
    with pytest.raises(KeyError):
        endpoint.start()   # nothing deployed

    class _Factory:
        def __call__(self, model, example, **kw):
            servable = make_servable(model, example, **kw)
            servable.warm_up = lambda: servable   # deploy skips warming
            return servable

    cold = ModelRegistry(servable_factory=_Factory())
    cold.deploy("m", _fit_lr(), _lr_table().drop("label").take(1))
    with pytest.raises(RuntimeError, match="not.*warmed"):
        ServingEndpoint(cold, "m").start()


# -- micro-batcher ----------------------------------------------------------

def test_microbatcher_coalesces_and_respects_capacity():
    batcher = MicroBatcher(max_batch_rows=16, max_wait_ms=20.0,
                           queue_capacity=4)
    t = _lr_table(n=32).drop("label")
    for _ in range(3):
        batcher.submit(t.take(4))
    batch = batcher.next_batch(timeout=0.1)
    assert [r.rows for r in batch] == [4, 4, 4]   # coalesced in order

    # a request that would overflow max_batch_rows stays for the next batch
    batcher.submit(t.take(12))
    batcher.submit(t.take(8))
    batch = batcher.next_batch(timeout=0.1)
    assert [r.rows for r in batch] == [12]
    batch = batcher.next_batch(timeout=0.1)
    assert [r.rows for r in batch] == [8]

    # bounded queue: capacity 4, fifth submit sheds
    for _ in range(4):
        batcher.submit(t.take(1))
    with pytest.raises(ServingOverloadedError, match="queue full"):
        batcher.submit(t.take(1))

    with pytest.raises(ValueError, match="max_batch_rows"):
        batcher.submit(t.take(17))
    with pytest.raises(ValueError, match="empty"):
        batcher.submit(t.take(0))


def test_queue_full_requests_shed_with_documented_error():
    model = _fit_lr()
    feats = _lr_table(seed=8).drop("label")
    registry = ModelRegistry()
    registry.deploy("m", model, feats.take(1), max_batch_rows=32)
    endpoint = ServingEndpoint(registry, "m", max_batch_rows=32,
                               queue_capacity=3)
    # endpoint NOT started: submits accumulate in the bounded queue
    futures = [endpoint.submit(feats.take(1)) for _ in range(3)]
    with pytest.raises(ServingOverloadedError, match="shed"):
        endpoint.submit(feats.take(1))
    assert endpoint.metrics.shed.value == 1
    endpoint.start()   # queued requests drain once serving begins
    ref = model.transform(feats.take(1))[0]["rawPrediction"]
    for future in futures:
        np.testing.assert_array_equal(
            future.result(10)["rawPrediction"], ref)
    endpoint.close()


def test_schema_mismatch_rejected():
    endpoint = serve_model(_fit_lr(), _lr_table().drop("label").take(1),
                           max_batch_rows=32)
    try:
        with pytest.raises(ValueError, match="schema"):
            endpoint.predict(Table({"wrong": np.ones((2, 8))}))
    finally:
        endpoint.close()


# -- hot swap ----------------------------------------------------------------

def _lr_from_weights(w, b):
    from flink_ml_tpu.models.classification.logisticregression import (
        LogisticRegressionModel)

    model = LogisticRegressionModel()
    model.set_model_data(Table({"coefficients": np.asarray(w)[None, :],
                                "intercept": np.array([b])}))
    return model


def test_hot_swap_atomic_and_bitexact_under_load():
    rng = np.random.default_rng(9)
    d = 8
    model_a = _lr_from_weights(rng.normal(size=d), 0.0)
    model_b = _lr_from_weights(rng.normal(size=d) + 3.0, -1.0)
    feats = Table({"features": rng.normal(size=(256, d))})
    reqs = _requests(feats, [1 + i % 7 for i in range(40)])
    ref_a = [model_a.transform(r)[0]["rawPrediction"] for r in reqs]
    ref_b = [model_b.transform(r)[0]["rawPrediction"] for r in reqs]

    endpoint = serve_model(model_a, feats.take(1), max_batch_rows=64,
                           max_wait_ms=0.5, queue_capacity=4096)
    results = [None] * len(reqs)
    errors = []

    def client(worker, n_workers):
        try:
            for i in range(worker, len(reqs), n_workers):
                results[i] = endpoint.predict(reqs[i], timeout=30)
        except Exception as exc:  # noqa: BLE001
            errors.append(exc)

    try:
        threads = [threading.Thread(target=client, args=(w, 4))
                   for w in range(4)]
        for t in threads:
            t.start()
        # swap mid-flight: warm-up runs here, OFF the serving path
        deployed = endpoint.registry.deploy("default", model_b)
        assert deployed.generation == 2
        # a request submitted after the deploy returned must see B
        post = feats.take(5)
        np.testing.assert_array_equal(
            endpoint.predict(post)["rawPrediction"],
            model_b.transform(post)[0]["rawPrediction"])
        for t in threads:
            t.join(30)
        assert not errors
        # atomicity: every response equals EXACTLY one version's offline
        # transform — never a mix of generations within one response
        for i, out in enumerate(results):
            raw = out["rawPrediction"]
            is_a = np.array_equal(raw, ref_a[i])
            is_b = np.array_equal(raw, ref_b[i])
            assert is_a or is_b, f"request {i} matches neither version"
        assert endpoint.metrics.group.snapshot()["model_generation"] == 2
    finally:
        endpoint.close()


def test_registry_redeploy_inherits_example_and_generation():
    registry = ModelRegistry()
    feats = _lr_table().drop("label")
    gen1 = registry.deploy("m", _fit_lr(), feats.take(2), max_batch_rows=32)
    assert gen1.generation == 1 and gen1.servable.ready
    gen2 = registry.deploy("m", _fit_lr(seed=11))   # example inherited
    assert gen2.generation == 2
    assert gen2.servable.example is gen1.servable.example
    assert gen2.servable.max_batch_rows == 32
    with pytest.raises(ValueError, match="example"):
        registry.deploy("fresh", _fit_lr())


# -- persist diagnosability (the registry's load path) -----------------------

def test_load_stage_missing_class_is_clear_ioerror(tmp_path):
    from flink_ml_tpu.utils import persist

    path = str(tmp_path / "m")
    _fit_lr().save(path)
    meta_path = os.path.join(path, "metadata")
    with open(meta_path) as f:
        meta = json.load(f)

    meta["className"] = "flink_ml_tpu.models.classification." \
        "logisticregression.RenamedAway"
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    with pytest.raises(IOError, match="RenamedAway") as exc_info:
        persist.load_stage(path)
    assert path in str(exc_info.value)

    meta["className"] = "no_such_module.Thing"
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    with pytest.raises(IOError, match="no_such_module.Thing"):
        persist.load_stage(path)

    del meta["className"]
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    with pytest.raises(IOError, match="className"):
        persist.load_stage(path)


# -- prefetch per-chunk stats as gauges --------------------------------------

def test_prefetch_chunk_stats_published_as_gauges():
    from flink_ml_tpu.data.prefetch import prefetch_to_device
    from flink_ml_tpu.utils.metrics import MetricGroup

    group = MetricGroup("prefetch")
    batches = [{"x": np.full((4, 2), i, np.float32)} for i in range(7)]
    seen = 0
    for chunk, mask, n_valid in prefetch_to_device(
            iter(batches), chunks=3, metric_group=group,
            transform=lambda b: (b["x"],)):
        seen += n_valid
    assert seen == 7
    snap = group.snapshot()
    assert snap["chunks_emitted"] == 3      # ceil(7 / 3)
    assert snap["batches"] == 7
    # final chunk padded 3 -> 1 real: 2 pad slots of 9 total
    assert snap["pad_fraction"] == pytest.approx(2 / 9, abs=1e-4)
    assert snap["put_overlap_s"] >= 0.0
    assert snap["chunk_assemble_s"] >= 0.0


# -- concurrency smoke + slow sweep ------------------------------------------

def test_concurrent_clients_coalesce_and_stay_exact():
    model = _fit_lr()
    feats = _lr_table(n=256, seed=12).drop("label")
    reqs = _requests(feats, [1 + i % 5 for i in range(48)])
    refs = [model.transform(r)[0]["rawPrediction"] for r in reqs]
    endpoint = serve_model(model, feats.take(1), max_batch_rows=64,
                           max_wait_ms=5.0, queue_capacity=4096)
    results = [None] * len(reqs)

    def client(worker, n_workers):
        for i in range(worker, len(reqs), n_workers):
            results[i] = endpoint.predict(reqs[i], timeout=30)

    try:
        threads = [threading.Thread(target=client, args=(w, 8))
                   for w in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        for out, ref in zip(results, refs):
            np.testing.assert_array_equal(out["rawPrediction"], ref)
        snap = endpoint.metrics.snapshot()
        assert snap["requests"] == len(reqs)
        # 8 concurrent clients against a 5ms wait: batches must coalesce
        assert snap["batches"] < snap["requests"]
        assert 0.0 < snap["batch_fill_ratio"] <= 1.0
        assert snap["latency_p99_ms"] >= snap["latency_p50_ms"] > 0.0
    finally:
        endpoint.close()


@pytest.mark.slow
def test_serving_concurrency_sweep():
    """1, 8 and 64 concurrent clients: every answer equals the offline
    transform and nothing is shed at ample capacity."""
    model = _fit_lr()
    feats = _lr_table(n=512, seed=13).drop("label")
    endpoint = serve_model(model, feats.take(1), max_batch_rows=256,
                           max_wait_ms=1.0, queue_capacity=8192)
    ref = model.transform(feats)[0]["rawPrediction"]
    try:
        for clients in (1, 8, 64):
            per_client = 20 if clients < 64 else 5
            errors = []

            def client(worker):
                rng = np.random.default_rng(worker)
                try:
                    for _ in range(per_client):
                        start = int(rng.integers(0, 500))
                        rows = int(rng.integers(1, 9))
                        req = feats.slice(start, start + rows)
                        out = endpoint.predict(req, timeout=60)
                        np.testing.assert_array_equal(
                            out["rawPrediction"], ref[start:start + rows])
                except Exception as exc:  # noqa: BLE001
                    errors.append(exc)

            threads = [threading.Thread(target=client, args=(w,))
                       for w in range(clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(120)
            assert not errors
        assert endpoint.metrics.shed.value == 0
    finally:
        endpoint.close()


def test_metrics_publish_skips_quantiles_when_no_new_samples():
    """The p50/p99 recompute is an O(window) np.quantile pass under the
    ring lock — a metric tick with no new samples must skip it, and the
    pair must come from ONE quantiles() call, not two ring passes."""
    from flink_ml_tpu.serving.metrics import LatencyTracker, ServingMetrics

    m = ServingMetrics()
    calls = []
    real = LatencyTracker.quantiles
    m.latency.quantiles = lambda qs: (calls.append(tuple(qs)) or
                                      real(m.latency, qs))

    m.publish()                       # nothing recorded yet: no pass
    assert calls == []
    m.latency.record(0.010)
    m.publish()
    assert calls == [(0.50, 0.99)]    # one pass for both quantiles
    snap = m.snapshot()
    assert snap["latency_p50_ms"] == pytest.approx(10.0, abs=0.1)

    m.publish()                       # no new samples: skipped
    m.publish()
    assert len(calls) == 1

    m.latency.record(0.030)
    m.publish()                       # new sample: recomputed
    assert len(calls) == 2
