"""Int8 serving path tests (ISSUE 18): quantized-precision plumbing
through ``make_servable`` / the scheduler, the accuracy envelope on
served bits, per-generation bit-stability, the embedding-row cache's
int8 pools (codes + per-row scales — half the bytes, twice the resident
rows at the same device budget), warm-up / admission precision
attribution, and the compilation-free admission contract for int8
tenants (zero new lowerings for tenant N+1 of a served int8 schema).

Contract under test (ARCHITECTURE.md "Int8 serving"): calibration is
captured at publish/bind time from the published params themselves,
re-derived on every rebind; within a generation repeat predicts are
bit-identical; agreement with f32 is gated at the decision/rank
envelope, never bitwise."""

import numpy as np
import pytest

from flink_ml_tpu import Table
from flink_ml_tpu.serving import (
    SLO_BULK,
    SLO_INTERACTIVE,
    SLO_STANDARD,
    EmbeddingRowCache,
    SharedScheduler,
    make_servable,
)

ENVELOPE = 0.99


# -- fixtures ----------------------------------------------------------------

def _lr_table(n=64, d=8, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d))
    y = (X[:, 0] + 0.3 * rng.normal(size=n) > 0).astype(np.int64)
    return Table({"features": X, "label": y})


def _fit_lr(seed=0):
    from flink_ml_tpu.models.classification.logisticregression import (
        LogisticRegression)

    return LogisticRegression().set_max_iter(3).fit(_lr_table(seed=seed))


def _feats(n=256, seed=1):
    return _lr_table(n=n, seed=seed).drop("label")


def _widedeep(seed=6, vocab=(50, 30), n=128):
    from flink_ml_tpu.models.recommendation.widedeep import WideDeep

    rng = np.random.default_rng(seed)
    dense = rng.normal(size=(n, 4)).astype(np.float32)
    cat = np.stack([rng.integers(0, v, size=n) for v in vocab],
                   axis=1).astype(np.int32)
    label = (cat[:, 0] > vocab[0] // 2).astype(np.int64)
    t = Table({"denseFeatures": dense, "catFeatures": cat, "label": label})
    return WideDeep().set_vocab_sizes(list(vocab)).set_max_iter(2).fit(t), t


def _agreement(a, b):
    a, b = np.asarray(a).ravel(), np.asarray(b).ravel()
    return float(np.mean(a == b))


# -- servable precision plumbing ---------------------------------------------

def test_int8_linear_servable_envelope_and_bitstable():
    model = _fit_lr()
    feats = _feats(n=256)
    sv8 = make_servable(model, feats.take(2), max_batch_rows=64,
                        precision="int8").warm_up()
    svf = make_servable(model, feats.take(2), max_batch_rows=64).warm_up()
    assert sv8.precision == "int8" and svf.precision == "f32"
    out8 = sv8.predict(feats)
    outf = svf.predict(feats)
    # decisions agree to the envelope, never required bitwise
    assert _agreement(out8["prediction"], outf["prediction"]) >= ENVELOPE
    # within a generation the quantized program is bit-stable
    again = sv8.predict(feats)
    np.testing.assert_array_equal(
        np.asarray(again["rawPrediction"]),
        np.asarray(out8["rawPrediction"]))


def test_int8_kmeans_servable_envelope():
    from flink_ml_tpu.models.clustering.kmeans import KMeans

    rng = np.random.default_rng(4)
    centers = rng.normal(scale=6.0, size=(5, 6))
    X = np.concatenate(
        [c + rng.normal(size=(40, 6)) for c in centers])
    t = Table({"features": X})
    model = KMeans().set_k(5).set_max_iter(5).set_seed(1).fit(t)
    sv8 = make_servable(model, t.take(2), max_batch_rows=64,
                        precision="int8").warm_up()
    svf = make_servable(model, t.take(2), max_batch_rows=64).warm_up()
    assert _agreement(sv8.predict(t)["prediction"],
                      svf.predict(t)["prediction"]) >= ENVELOPE


def test_int8_widedeep_servable_envelope():
    model, t = _widedeep()
    feats = t.drop("label")
    sv8 = make_servable(model, feats.take(2), max_batch_rows=64,
                        precision="int8").warm_up()
    svf = make_servable(model, feats.take(2), max_batch_rows=64).warm_up()
    assert _agreement(sv8.predict(feats)["prediction"],
                      svf.predict(feats)["prediction"]) >= ENVELOPE


def test_precision_refused_without_a_quantized_seam():
    """Families with no int8 backend refuse loudly at construction —
    silently serving f32 under precision='int8' would fake the
    models-per-chip ledger."""
    from flink_ml_tpu.models.classification.gbtclassifier import (
        GBTClassifier)

    t = _lr_table(n=96, seed=4)
    gbt = (GBTClassifier().set_max_iter(2).set_max_depth(2)
           .set_max_bins(16).fit(t))
    with pytest.raises(TypeError, match="precision"):
        make_servable(gbt, t.drop("label").take(2), precision="int8")
    with pytest.raises(TypeError, match="precision"):
        make_servable(_fit_lr(), _feats().take(2), precision="fp8")


def test_int8_requires_the_registry_dispatched_plan():
    """A linear config whose transform_kernel is unported (returns
    None) cannot serve int8 — the quantized path exists only through
    the registry's "int8" backends, never a silent f32 fallback."""
    model = _fit_lr()
    model.transform_kernel = lambda schema: None
    with pytest.raises(TypeError, match="int8"):
        make_servable(model, _feats().take(2), precision="int8")


def test_warmup_report_attributes_precision_per_bucket():
    model = _fit_lr()
    sv8 = make_servable(model, _feats().take(2), max_batch_rows=32,
                        precision="int8").warm_up()
    rep = sv8.warmup_report
    assert rep["precision"] == "int8"
    assert rep["buckets"]
    assert all(b["precision"] == "int8" for b in rep["buckets"].values())
    svf = make_servable(model, _feats().take(2), max_batch_rows=32)
    repf = svf.warm_up().warmup_report
    assert repf["precision"] == "f32"
    assert all(b["precision"] == "f32" for b in repf["buckets"].values())


# -- embedding-row cache int8 pools ------------------------------------------

def test_embcache_int8_pools_double_resident_rows_at_equal_bytes():
    """THE footprint dividend: codes+scales pools cost about half the
    f32 pool bytes per block, so the same device budget holds ~2x the
    resident rows."""
    rng = np.random.default_rng(5)
    V, E, B = 256, 16, 8
    emb = rng.normal(size=(V, E)).astype(np.float32)
    cache_f = EmbeddingRowCache({"emb": emb}, block_rows=B,
                                capacity_blocks=8)
    cache_q = EmbeddingRowCache({"emb": emb}, block_rows=B,
                                capacity_blocks=8, precision="int8")
    assert cache_q.snapshot()["precision"] == "int8"
    budget = cache_f.pool_bytes
    per_block_q = cache_q.pool_bytes // 8
    assert cache_q.pool_bytes * 2 <= budget + 8 * B * 4  # ~half + scales
    cap_q = budget // per_block_q
    assert cap_q >= 2 * 8, (
        f"int8 pools hold {cap_q} blocks in the f32 budget of 8 — "
        "expected at least 2x resident rows at equal pool bytes")
    cache_q2 = EmbeddingRowCache({"emb": emb}, block_rows=B,
                                 capacity_blocks=int(cap_q),
                                 precision="int8")
    assert cache_q2.pool_bytes <= budget
    assert cache_q2.capacity_blocks * B >= 2 * 8 * B


def test_embcache_int8_cached_and_bypass_paths_agree_bitwise():
    """Gather-then-dequantize on device and host-side dequantize in the
    bypass path are the same f32 multiply — one quantized truth, bit
    equal either way."""
    rng = np.random.default_rng(6)
    V, E = 64, 6
    emb = rng.normal(size=(V, E)).astype(np.float32)
    wc = rng.normal(size=(V,)).astype(np.float32)
    cache = EmbeddingRowCache({"emb": emb, "wide_cat": wc}, block_rows=8,
                              capacity_blocks=2, precision="int8")
    ids = np.array([[0, 9], [1, 8]])
    cached = np.asarray(cache.lookup(ids)["emb"])
    big = np.array([[0, 9], [1, 8], [16, 24], [32, 40], [48, 56]])
    out = cache.lookup(big)                      # exceeds capacity
    assert cache.bypasses == 1
    np.testing.assert_array_equal(np.asarray(out["emb"])[:2], cached)
    # 1-d scalar-row tables never quantize: wide_cat rows stay exact
    np.testing.assert_array_equal(np.asarray(out["wide_cat"]), wc[big])


def test_cached_widedeep_int8_envelope_and_bitstable():
    model, t = _widedeep(seed=9)
    feats = t.drop("label")
    sv8 = make_servable(model, feats.take(2), emb_cache=True,
                        cache_block_rows=8, cache_capacity_blocks=6,
                        max_batch_rows=64, precision="int8").warm_up()
    assert sv8.precision == "int8"
    assert sv8.cache.snapshot()["precision"] == "int8"
    offline = model.transform(feats)[0]
    served = sv8.predict(feats)
    assert _agreement(served["prediction"],
                      offline["prediction"]) >= ENVELOPE
    again = sv8.predict(feats)
    np.testing.assert_array_equal(np.asarray(again["rawPrediction"]),
                                  np.asarray(served["rawPrediction"]))


# -- scheduler: precision attribution + admission ----------------------------

def test_scheduler_precision_gauges_and_shared_servable_inheritance():
    s = SharedScheduler(max_batch_rows=64, max_wait_ms=0.5,
                        queue_capacity=1024)
    feats = _feats(seed=3)
    try:
        s.add_tenant("quant", _fit_lr(seed=1), feats.take(2),
                     slo=SLO_INTERACTIVE, precision="int8")
        s.add_tenant("plain", _fit_lr(seed=2), feats.take(2),
                     slo=SLO_STANDARD)
        s.add_tenant("shadow", servable_of="quant", slo=SLO_BULK)
        assert s.tenant("quant").precision == "int8"
        assert s.tenant("plain").precision == "f32"
        # a shared-servable tenant inherits the sharing tenant's
        # precision — same program, same codes
        assert s.tenant("shadow").precision == "int8"
        for name, want in (("quant", "int8"), ("plain", "f32"),
                           ("shadow", "int8")):
            gauge = s.tenant(name).metrics.group.gauge("precision")
            assert gauge.value == want
        rep = s.tenant("quant").admission_report
        assert rep is not None and rep["precision"] == "int8"
        assert all(b["precision"] == "int8"
                   for b in rep["buckets"].values())
        s._refresh_gauges()
        assert s._int8_tenants.value == 2
    finally:
        s.close()


def test_second_int8_tenant_admits_with_zero_new_lowerings():
    """The registry dividend survives quantization: tenant N+1 of an
    already-served int8 schema warms entirely out of the shared caches
    — zero new XLA lowerings, and the admission report says so at
    precision int8."""
    from flink_ml_tpu.utils.backend import count_compiles

    feats = _feats(seed=7)
    s = SharedScheduler(max_batch_rows=64, max_wait_ms=0.5,
                        queue_capacity=1024)
    s.add_tenant("q1", _fit_lr(seed=1), feats.take(2),
                 slo=SLO_INTERACTIVE, precision="int8")
    s.start()
    try:
        for n in (1, 2, 64):            # settle wave, as in the f32 test
            s.predict("q1", feats.take(n))
        model2 = _fit_lr(seed=2)
        with count_compiles() as count:
            tenant = s.add_tenant("q2", model2, feats.take(2),
                                  slo=SLO_BULK, precision="int8")
            out = s.predict("q2", feats.take(5))
        assert count() == 0, (
            f"{count()} new lowerings admitting a same-schema int8 "
            "tenant — quantized admission must be placement only")
        report = tenant.admission_report
        assert report is not None and report["compiled"] == 0
        assert report["precision"] == "int8"
        # the decisions still come from the quantized program
        sv = make_servable(model2, feats.take(2), max_batch_rows=64,
                           precision="int8").warm_up()
        np.testing.assert_array_equal(
            np.asarray(out["rawPrediction"]),
            np.asarray(sv.predict(feats.take(5))["rawPrediction"]))
    finally:
        s.close()
