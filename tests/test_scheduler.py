"""Multi-tenant serving fabric tests (ISSUE 14): WFQ fairness shares,
the shed-order contract (bulk before interactive, property-tested at the
boundary), compilation-free admission (zero new lowerings for tenant
N+1 of a served schema), cross-tenant coalescing on a shared servable,
publish-chaos isolation (a delta push to tenant A leaves tenant B's
served bits and latency ring untouched), the embedding-row cache
(exact under eviction churn, LRU order, bypass fallback, bit-exact
cached WideDeep serving incl. across rebind), the lock-free batcher
shed fast path, and the generation-stamped shed events."""

import threading

import numpy as np
import pytest

from flink_ml_tpu import Table
from flink_ml_tpu.serving import (
    SLO_BULK,
    SLO_CLASSES,
    SLO_INTERACTIVE,
    SLO_STANDARD,
    EmbeddingRowCache,
    MicroBatcher,
    ModelRegistry,
    ServingEndpoint,
    ServingOverloadedError,
    SharedScheduler,
    make_servable,
)
from flink_ml_tpu.serving.metrics import HEALTH_DEGRADED, HEALTH_SERVING


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


def _lr_from_weights(w, b):
    from flink_ml_tpu.models.classification.logisticregression import (
        LogisticRegressionModel)

    model = LogisticRegressionModel()
    model.set_model_data(Table({"coefficients": np.asarray(w)[None, :],
                                "intercept": np.array([b])}))
    return model


class _StubServable:
    """Queue-mechanics stub: echoes its input, always ready — lets the
    WFQ/shed tests exercise pure admission + placement without model
    fits or compiles."""

    ready = True
    warmup_report = None

    def __init__(self, model, example, **kwargs):
        self.model = model
        self.example = example
        self.max_batch_rows = kwargs.get("max_batch_rows", 256)
        self.min_bucket = kwargs.get("min_bucket", 8)
        self.output_cols = None

    def warm_up(self):
        return self

    def check_schema(self, table):
        pass

    def bucket_for(self, rows):
        return max(8, rows)

    def predict(self, table):
        return table


def _stub_scheduler(**kwargs):
    return SharedScheduler(ModelRegistry(servable_factory=_StubServable),
                           **kwargs)


def _feats(n=256, seed=1):
    return _lr_table(n=n, seed=seed).drop("label")


def _drain(scheduler, max_batches=10_000):
    """Run the scheduler's pick->dispatch loop inline (no thread) until
    the queue is empty; returns the number of batches formed."""
    batches = 0
    while True:
        formed = scheduler._next_batch(timeout=0.0)
        if formed is None:
            return batches
        scheduler._dispatch(*formed)
        batches += 1


# -- WFQ fairness ------------------------------------------------------------

def test_wfq_weighted_shares_within_class():
    """Backlogged same-class tenants share served rows in proportion to
    their weights; a serving prefix of the saturated queues shows the
    3:1:1 split within one batch of tolerance."""
    s = _stub_scheduler(max_batch_rows=4, max_wait_ms=0.0,
                        queue_capacity=4096)
    feats = _feats()
    for name, weight in (("heavy", 3.0), ("light1", 1.0),
                         ("light2", 1.0)):
        s.add_tenant(name, object(), feats.take(2), slo=SLO_STANDARD,
                     weight=weight)
        for _ in range(60):
            s.submit(name, feats.take(4))
    for _ in range(30):                 # a strict prefix: queues stay hot
        formed = s._next_batch(timeout=0.0)
        assert formed is not None
        s._dispatch(*formed)
    served = {name: s.tenant(name).rows_served
              for name in ("heavy", "light1", "light2")}
    total = sum(served.values())
    assert total == 30 * 4
    # weighted shares: 3/5, 1/5, 1/5 of rows, within one 4-row batch
    assert abs(served["heavy"] - total * 3 / 5) <= 4
    assert abs(served["light1"] - total / 5) <= 4
    assert abs(served["light2"] - total / 5) <= 4
    _drain(s)


def test_wfq_idle_tenant_reenters_at_class_virtual_time():
    """An idle tenant does not bank credit: when it goes backlogged it
    re-enters at the class virtual time instead of monopolizing the
    device to 'catch up'."""
    s = _stub_scheduler(max_batch_rows=4, max_wait_ms=0.0,
                        queue_capacity=4096)
    feats = _feats()
    s.add_tenant("busy", object(), feats.take(2), slo=SLO_STANDARD)
    s.add_tenant("idle", object(), feats.take(2), slo=SLO_STANDARD)
    for _ in range(20):
        s.submit("busy", feats.take(4))
    _drain(s)
    vclass = s._vclass[SLO_STANDARD]
    assert vclass > 0.0
    s.submit("idle", feats.take(4))
    assert s.tenant("idle").vft >= vclass
    _drain(s)


# -- shed order (priority shedding) ------------------------------------------

def test_shed_order_bulk_before_standard_before_interactive():
    """Under a monotone load ramp, bulk sheds strictly first, then
    standard, and interactive only when the queue is FULL."""
    s = _stub_scheduler(queue_capacity=10)   # limits: bulk 5, std 8, int 10
    feats = _feats()
    for name, slo in (("i", SLO_INTERACTIVE), ("s", SLO_STANDARD),
                      ("b", SLO_BULK)):
        s.add_tenant(name, object(), feats.take(2), slo=slo)
    assert s.admit_limits == {SLO_INTERACTIVE: 10, SLO_STANDARD: 8,
                              SLO_BULK: 5}
    # fill to the bulk threshold with interactive traffic
    for _ in range(5):
        s.submit("i", feats.take(1))
    with pytest.raises(ServingOverloadedError, match="bulk"):
        s.submit("b", feats.take(1))
    # standard still admits up to ITS threshold
    for _ in range(3):
        s.submit("s", feats.take(1))
    with pytest.raises(ServingOverloadedError, match="standard"):
        s.submit("s", feats.take(1))
    # interactive admits to full capacity, then sheds last
    for _ in range(2):
        s.submit("i", feats.take(1))
    with pytest.raises(ServingOverloadedError, match="interactive"):
        s.submit("i", feats.take(1))
    assert s.shed_counts() == {SLO_INTERACTIVE: 1, SLO_STANDARD: 1,
                               SLO_BULK: 1}
    _drain(s)


def test_shed_order_property_at_the_boundary():
    """Property check over seeded random submit interleavings: whenever
    a request of a class is shed, the queue depth was at (or above) the
    class threshold, an interactive shed implies a FULL queue — and in
    every run, the first interactive shed happens only after at least
    one bulk shed (bulk is 100% shed before interactive ever is)."""
    rng = np.random.default_rng(14)
    feats = _feats()
    for trial in range(8):
        s = _stub_scheduler(queue_capacity=int(rng.integers(4, 16)))
        tenants = {}
        for slo in SLO_CLASSES:
            s.add_tenant(slo, object(), feats.take(2), slo=slo)
            tenants[slo] = s.tenant(slo)
        shed_events = []
        for _ in range(200):
            slo = SLO_CLASSES[int(rng.integers(0, 3))]
            depth_before = s._depth
            if rng.random() < 0.25 and s._depth:
                formed = s._next_batch(timeout=0.0)
                if formed is not None:
                    s._dispatch(*formed)
                continue
            try:
                s.submit(slo, feats.take(1))
            except ServingOverloadedError:
                shed_events.append(slo)
                assert depth_before >= s.admit_limits[slo]
                if slo == SLO_INTERACTIVE:
                    assert depth_before >= s.queue_capacity
                    assert SLO_BULK in shed_events, (
                        "interactive shed before any bulk shed")
        _drain(s)


def test_admit_fractions_must_respect_priority_order():
    with pytest.raises(ValueError, match="non-increasing"):
        _stub_scheduler(queue_capacity=10,
                        admit_fractions={SLO_INTERACTIVE: 1.0,
                                         SLO_STANDARD: 0.5,
                                         SLO_BULK: 0.9})
    with pytest.raises(ValueError, match="admit fraction"):
        _stub_scheduler(queue_capacity=10,
                        admit_fractions={SLO_INTERACTIVE: 1.0,
                                         SLO_STANDARD: 0.5,
                                         SLO_BULK: 0.0})


def test_scheduler_health_degrades_on_shed_and_heals_after_drain():
    s = _stub_scheduler(queue_capacity=4)    # bulk limit: 2
    feats = _feats()
    s.add_tenant("b", object(), feats.take(2), slo=SLO_BULK)
    assert s.health == HEALTH_SERVING
    for _ in range(2):
        s.submit("b", feats.take(1))
    with pytest.raises(ServingOverloadedError):
        s.submit("b", feats.take(1))
    assert s.health == HEALTH_DEGRADED
    _drain(s)
    assert s.health == HEALTH_SERVING       # queue receded: healed


# -- dispatch priority + coalescing ------------------------------------------

def test_interactive_dispatches_before_bulk_backlog():
    s = _stub_scheduler(max_batch_rows=8, max_wait_ms=0.0,
                        queue_capacity=4096)
    feats = _feats()
    s.add_tenant("inter", object(), feats.take(2), slo=SLO_INTERACTIVE)
    s.add_tenant("bulk", object(), feats.take(2), slo=SLO_BULK)
    for _ in range(20):
        s.submit("bulk", feats.take(8))
    s.submit("inter", feats.take(1))
    serve_name, picked = s._next_batch(timeout=0.0)
    assert serve_name == "inter"
    assert [t.name for t, _ in picked] == ["inter"]
    s._dispatch(serve_name, picked)
    _drain(s)


def test_cross_tenant_coalescing_on_shared_servable():
    """Two tenants mapped to ONE servable (traffic multi-tenancy): their
    same-class requests coalesce into one batch, and each future
    resolves to exactly its own rows."""
    model = _fit_lr()
    feats = _feats(seed=3)
    registry = ModelRegistry()
    s = SharedScheduler(registry, max_batch_rows=64, max_wait_ms=5.0,
                        queue_capacity=1024)
    s.add_tenant("owner", model, feats.take(2), slo=SLO_STANDARD)
    s.add_tenant("guest", servable_of="owner", slo=SLO_STANDARD)
    reqs = [("owner", feats.slice(0, 3)), ("guest", feats.slice(3, 8)),
            ("owner", feats.slice(8, 9))]
    futures = [(name, req, s.submit(name, req)) for name, req in reqs]
    serve_name, picked = s._next_batch(timeout=0.0)
    assert serve_name == "owner"
    assert {t.name for t, _ in picked} == {"owner", "guest"}
    assert len(picked) == 3                  # ONE batch for all three
    s._dispatch(serve_name, picked)
    for name, req, future in futures:
        out = future.result(10)
        np.testing.assert_array_equal(
            out["rawPrediction"],
            model.transform(req)[0]["rawPrediction"])
    assert s.tenant("guest").admission_report is None
    assert s.tenant("guest").rows_served == 5


# -- compilation-free admission ----------------------------------------------

def test_second_tenant_of_served_schema_admits_with_zero_new_lowerings():
    """THE registry dividend (ISSUE 14 acceptance): tenant N+1 whose
    model shares an already-served schema warms entirely out of the
    shared jit cache — zero new XLA lowerings, and the admission report
    says so."""
    from flink_ml_tpu.utils.backend import count_compiles

    feats = _feats(seed=7)
    s = SharedScheduler(max_batch_rows=64, max_wait_ms=0.5,
                        queue_capacity=1024)
    s.add_tenant("t1", _fit_lr(seed=1), feats.take(2),
                 slo=SLO_INTERACTIVE)
    s.start()
    try:
        # settle wave: lazy one-time work outside the warm-up ladder
        for n in (1, 2, 64):
            s.predict("t1", feats.take(n))
        model2 = _fit_lr(seed=2)     # the FIT is training-side work;
        ref2 = model2.transform(      # admission is what must be free
            feats.take(5))[0]["rawPrediction"]
        with count_compiles() as count:
            tenant = s.add_tenant("t2", model2, feats.take(2),
                                  slo=SLO_BULK)
            out = s.predict("t2", feats.take(5))
        assert count() == 0, (
            f"{count()} new lowerings admitting a same-schema tenant — "
            "the scheduler must be purely admission + placement")
        report = tenant.admission_report
        assert report is not None and report["compiled"] == 0
        assert report["aot_loaded"] + report["cache_hits"] \
            + sum(1 for b in report["buckets"].values()
                  if b["source"] == "untracked") == len(report["buckets"])
        np.testing.assert_array_equal(out["rawPrediction"], ref2)
    finally:
        s.close()


# -- publish chaos: tenant isolation -----------------------------------------

def test_delta_publish_to_one_tenant_leaves_others_untouched():
    """Continuous publishes to tenant A must not move tenant B: B's
    served bits stay bit-exact with B's (never-republished) model, B's
    generation gauge stays 1, and B's latency ring records exactly B's
    requests."""
    rng = np.random.default_rng(21)
    d = 8
    a1 = _lr_from_weights(rng.normal(size=d), 0.0)
    a2 = _lr_from_weights(rng.normal(size=d) + 2.0, -0.5)
    model_b = _lr_from_weights(rng.normal(size=d) - 1.0, 0.3)
    feats = Table({"features": rng.normal(size=(256, d))})
    s = SharedScheduler(max_batch_rows=64, max_wait_ms=0.5,
                        queue_capacity=8192)
    s.add_tenant("a", a1, feats.take(2), slo=SLO_STANDARD)
    s.add_tenant("b", model_b, feats.take(2), slo=SLO_STANDARD)
    s.start()

    ref_b = model_b.transform(feats)[0]["rawPrediction"]
    ref_a = {0: a1.transform(feats)[0]["rawPrediction"],
             1: a2.transform(feats)[0]["rawPrediction"]}
    stop = threading.Event()
    publishes = [0]
    errors = []

    def publisher():
        import time

        models = (a1, a2)
        try:
            while not stop.is_set():
                live = s.registry.current("a")
                nxt = models[(publishes[0] + 1) % 2]
                s.registry.publish_servable(
                    "a", live.servable.rebind(nxt),
                    metrics=s.tenant("a").metrics, mode="delta")
                publishes[0] += 1
                time.sleep(0.002)
        except Exception as exc:  # noqa: BLE001
            errors.append(exc)

    def client(name, refs, worker):
        crng = np.random.default_rng(worker)
        try:
            for _ in range(30):
                start = int(crng.integers(0, 200))
                rows = int(crng.integers(1, 6))
                req = feats.slice(start, start + rows)
                out = s.predict(name, req, timeout=30)
                raw = out["rawPrediction"]
                if isinstance(refs, dict):       # tenant a: any published gen
                    assert any(
                        np.array_equal(raw, r[start:start + rows])
                        for r in refs.values()), "mixed-generation response"
                else:
                    np.testing.assert_array_equal(
                        raw, refs[start:start + rows])
        except Exception as exc:  # noqa: BLE001
            errors.append(exc)

    try:
        pub = threading.Thread(target=publisher)
        clients = [threading.Thread(target=client,
                                    args=("b", ref_b, w)) for w in range(3)]
        clients += [threading.Thread(target=client,
                                     args=("a", ref_a, 10 + w))
                    for w in range(2)]
        pub.start()
        for t in clients:
            t.start()
        for t in clients:
            t.join(60)
        stop.set()
        pub.join(10)
        assert not errors, errors[:3]
        assert publishes[0] > 0
        b_metrics = s.tenant("b").metrics
        snap = b_metrics.group.snapshot()
        # B's generation never moved and its ring holds exactly B's
        # requests — A's publishes did not leak into B's accounting
        assert snap["model_generation"] == 1
        assert b_metrics.latency.count == snap["requests"] == 90
        assert snap["publishes_delta"] == 0 and snap["publishes_full"] == 0
        assert s.registry.generation("a") == publishes[0] + 1
    finally:
        stop.set()
        s.close()


# -- embedding-row cache -----------------------------------------------------

def _widedeep(seed=6, vocab=(50, 30), n=128):
    from flink_ml_tpu.models.recommendation.widedeep import WideDeep

    rng = np.random.default_rng(seed)
    dense = rng.normal(size=(n, 4)).astype(np.float32)
    cat = np.stack([rng.integers(0, v, size=n) for v in vocab],
                   axis=1).astype(np.int32)
    label = (cat[:, 0] > vocab[0] // 2).astype(np.int64)
    t = Table({"denseFeatures": dense, "catFeatures": cat, "label": label})
    return WideDeep().set_vocab_sizes(list(vocab)).set_max_iter(2).fit(t), t


def test_embcache_exact_under_eviction_churn():
    rng = np.random.default_rng(2)
    V, E = 80, 6
    emb = rng.normal(size=(V, E)).astype(np.float32)
    wc = rng.normal(size=(V,)).astype(np.float32)
    cache = EmbeddingRowCache({"emb": emb, "wide_cat": wc},
                              block_rows=8, capacity_blocks=4)
    for _ in range(100):
        ids = rng.integers(0, V, size=(int(rng.integers(1, 9)), 2))
        out = cache.lookup(ids)
        np.testing.assert_array_equal(np.asarray(out["emb"]), emb[ids])
        np.testing.assert_array_equal(np.asarray(out["wide_cat"]),
                                      wc[ids])
    snap = cache.snapshot()
    assert snap["hits"] > 0 and snap["misses"] > 0
    assert snap["resident_blocks"] <= snap["capacity_blocks"] == 4
    assert snap["evictions"] > 0


def test_embcache_lru_evicts_least_recently_touched():
    V, E = 32, 2
    emb = np.arange(V * E, dtype=np.float32).reshape(V, E)
    cache = EmbeddingRowCache({"emb": emb}, block_rows=8,
                              capacity_blocks=2)
    cache.lookup(np.array([0]))        # block 0
    cache.lookup(np.array([8]))        # block 1
    cache.lookup(np.array([1]))        # touch block 0 -> block 1 is LRU
    cache.lookup(np.array([16]))       # block 2 evicts block 1
    assert set(cache._slot_of) == {0, 2}
    assert cache.evictions == 1
    out = cache.lookup(np.array([9]))  # block 1 re-faults, still exact
    np.testing.assert_array_equal(np.asarray(out["emb"]), emb[[9]])
    assert cache.block_faults == 4


def test_embcache_bypasses_batches_larger_than_the_cache():
    """A batch whose working set exceeds the whole cache serves uncached
    (exact host rows), leaves the resident set untouched, and counts a
    bypass — never a wrong answer."""
    V, E = 64, 3
    rng = np.random.default_rng(3)
    emb = rng.normal(size=(V, E)).astype(np.float32)
    cache = EmbeddingRowCache({"emb": emb}, block_rows=8,
                              capacity_blocks=2)
    cache.lookup(np.array([0, 8]))     # two resident blocks
    resident = dict(cache._slot_of)
    ids = np.array([0, 8, 16, 24, 32])  # 5 unique blocks > capacity 2
    out = cache.lookup(ids)
    np.testing.assert_array_equal(np.asarray(out["emb"]), emb[ids])
    assert cache.bypasses == 1
    assert cache._slot_of == resident   # resident set untouched


def test_embcache_validation():
    with pytest.raises(ValueError, match="vocab dim"):
        EmbeddingRowCache({"a": np.zeros((4, 2)), "b": np.zeros((5,))})
    with pytest.raises(ValueError, match="block_rows"):
        EmbeddingRowCache({"a": np.zeros((4, 2))}, block_rows=0)
    cache = EmbeddingRowCache({"a": np.arange(10.0)}, block_rows=4,
                              capacity_blocks=99)
    assert cache.capacity_blocks == cache.n_blocks == 3   # capped
    with pytest.raises(ValueError, match="out of range"):
        cache.lookup(np.array([10]))
    with pytest.raises(ValueError, match="out of range"):
        cache.lookup(np.array([-1]))


def test_cached_widedeep_bitexact_with_offline_transform():
    model, t = _widedeep()
    feats = t.drop("label")
    servable = make_servable(model, feats.take(2), emb_cache=True,
                             cache_block_rows=8, cache_capacity_blocks=6,
                             max_batch_rows=64)
    servable.warm_up()
    for sz in (1, 7, 10, 33):
        req = feats.slice(0, sz)
        served = servable.predict(req)
        offline = model.transform(req)[0]
        for col in ("rawPrediction", "prediction"):
            np.testing.assert_array_equal(served[col], offline[col])
    snap = servable.cache.snapshot()
    assert snap["hits"] > 0 and snap["lookups"] > 0


def test_cached_widedeep_rebind_gets_fresh_cache():
    """A delta publish (rebind) must not serve the OLD generation's
    cached rows: the clone carries a fresh cache over the new tables
    and scores bit-exactly as the new model."""
    model, t = _widedeep(seed=8)
    feats = t.drop("label")
    servable = make_servable(model, feats.take(2), emb_cache=True,
                             cache_block_rows=8, cache_capacity_blocks=8,
                             max_batch_rows=64)
    servable.warm_up()
    servable.predict(feats.take(10))    # populate the old cache

    from flink_ml_tpu.models.recommendation.widedeep import WideDeepModel

    new_model = WideDeepModel()
    new_model._params = {
        **{k: model._params[k] for k in ("wide_dense", "wide_b", "mlp")},
        "emb": np.asarray(model._params["emb"]) * 2.0 + 1.0,
        "wide_cat": np.asarray(model._params["wide_cat"]) - 3.0,
    }
    new_model._vocab_sizes = model._vocab_sizes
    clone = servable.rebind(new_model)
    assert clone.ready and clone.cache is not servable.cache
    req = feats.take(10)
    np.testing.assert_array_equal(
        clone.predict(req)["rawPrediction"],
        new_model.transform(req)[0]["rawPrediction"])
    # the incumbent keeps serving the OLD params bit-exactly
    np.testing.assert_array_equal(
        servable.predict(req)["rawPrediction"],
        model.transform(req)[0]["rawPrediction"])


def test_embcache_rejects_non_widedeep():
    with pytest.raises(TypeError, match="WideDeepModel"):
        make_servable(_fit_lr(), _feats().take(1), emb_cache=True)


def test_cached_widedeep_zero_retraces_after_warmup():
    from flink_ml_tpu.utils.backend import count_compiles

    model, t = _widedeep(seed=9)
    feats = t.drop("label")
    servable = make_servable(model, feats.take(2), emb_cache=True,
                             cache_block_rows=8,
                             cache_capacity_blocks=10, max_batch_rows=64)
    servable.warm_up()
    for n in (1, 2, 64):
        servable.predict(feats.take(n))         # settle wave
    with count_compiles() as count:
        for n in (1, 3, 7, 8, 11, 16, 33, 64):
            servable.predict(feats.take(n))
    assert count() == 0, (
        f"{count()} new lowerings in cached-WideDeep steady state — "
        "pool shapes must stay constant under residency churn")


# -- satellites: batcher fast path + shed generation stamping ----------------

class _PoisonedLock:
    """Context manager that fails the test if the fast path touches the
    queue lock."""

    def __init__(self):
        self.entered = 0

    def __enter__(self):
        self.entered += 1
        raise AssertionError("queue lock acquired on the shed fast path")

    def __exit__(self, *exc):
        return False


def test_microbatcher_fast_shed_never_touches_the_lock():
    batcher = MicroBatcher(max_batch_rows=8, queue_capacity=2)
    t = _feats()
    for _ in range(2):
        batcher.submit(t.take(1))
    batcher._cond = _PoisonedLock()             # saturation reached
    with pytest.raises(ServingOverloadedError, match="queue full"):
        batcher.submit(t.take(1))               # lock-free shed
    batcher.fast_shed = False                   # the locked path
    with pytest.raises(AssertionError, match="fast path"):
        batcher.submit(t.take(1))               # legacy path locks


def test_scheduler_fast_shed_never_touches_the_lock():
    s = _stub_scheduler(queue_capacity=4)
    feats = _feats()
    s.add_tenant("b", object(), feats.take(2), slo=SLO_BULK)
    for _ in range(2):                          # bulk limit = 2
        s.submit("b", feats.take(1))
    s._cond = _PoisonedLock()
    with pytest.raises(ServingOverloadedError, match="shed"):
        s.submit("b", feats.take(1))


def test_endpoint_shed_stamps_live_generation():
    from flink_ml_tpu.obs.trace import tracer

    model = _fit_lr()
    feats = _feats(seed=8)
    registry = ModelRegistry()
    registry.deploy("m", model, feats.take(1), max_batch_rows=32)
    endpoint = ServingEndpoint(registry, "m", max_batch_rows=32,
                               queue_capacity=1)
    # endpoint NOT started: the queue fills and the next submit sheds
    endpoint.submit(feats.take(1))
    tracer.enable()
    try:
        with pytest.raises(ServingOverloadedError):
            endpoint.submit(feats.take(1))
    finally:
        tracer.disable()
    snap = endpoint.metrics.group.snapshot()
    assert snap["last_shed_generation"] == 1
    sheds = list(tracer.find("shed"))
    assert sheds and sheds[0].ids["generation"] == 1
    tracer.clear()
    endpoint.start()
    endpoint.close()


# -- observability wiring ----------------------------------------------------

def test_scheduler_spans_carry_tenant_correlation_key():
    from flink_ml_tpu.obs.trace import CORRELATION_KEYS, tracer

    assert "tenant" in CORRELATION_KEYS
    s = _stub_scheduler(max_batch_rows=8, max_wait_ms=0.0,
                        queue_capacity=64)
    feats = _feats()
    s.add_tenant("acme", object(), feats.take(2), slo=SLO_INTERACTIVE)
    tracer.enable()
    try:
        future = s.submit("acme", feats.take(2))
        formed = s._next_batch(timeout=0.0)
        s._dispatch(*formed)
        future.result(10)
        spans = {sp.name: sp for sp in tracer.spans()}
        assert spans["request"].ids["tenant"] == "acme"
        assert spans["queue_wait"].ids["tenant"] == "acme"
        assert spans["serve_batch"].ids["tenant"] == "acme"
    finally:
        tracer.disable()
        tracer.clear()


def test_default_tree_registers_scheduler_subtree():
    from flink_ml_tpu.obs.tree import default_tree, prometheus_text

    s = _stub_scheduler(queue_capacity=16)
    feats = _feats()
    s.add_tenant("t0", object(), feats.take(2), slo=SLO_INTERACTIVE)
    s.submit("t0", feats.take(1))
    _drain(s)
    snap = default_tree(scheduler=s).snapshot()
    assert snap["scheduler"]["batches"] == 1
    assert snap["scheduler"]["tenants.t0.requests"] == 1
    text = prometheus_text(snap)
    assert "flink_ml_tpu_scheduler_tenants_t0_requests 1" in text


def test_add_tenant_validation_and_lifecycle():
    s = _stub_scheduler(queue_capacity=16)
    feats = _feats()
    s.add_tenant("a", object(), feats.take(2))
    with pytest.raises(ValueError, match="already admitted"):
        s.add_tenant("a", object(), feats.take(2))
    with pytest.raises(ValueError, match="SLO class"):
        s.add_tenant("x", object(), feats.take(2), slo="gold")
    with pytest.raises(ValueError, match="weight"):
        s.add_tenant("x", object(), feats.take(2), weight=0.0)
    with pytest.raises(ValueError, match="servable_of"):
        s.add_tenant("x", object(), servable_of="a")
    with pytest.raises(KeyError, match="not an admitted tenant"):
        s.add_tenant("x", servable_of="ghost")
    with pytest.raises(ValueError, match="needs a model"):
        s.add_tenant("x")
    with pytest.raises(KeyError, match="unknown tenant"):
        s.submit("ghost", feats.take(1))
    with pytest.raises(ValueError, match="empty"):
        s.submit("a", feats.take(0))
    with pytest.raises(ValueError, match="split it client-side"):
        s.submit("a", feats.take(16).concat(
            _feats(n=512, seed=5).take(241)))
    s.start()
    with pytest.raises(RuntimeError, match="already started"):
        s.start()
    s.close()
    with pytest.raises(RuntimeError, match="closed"):
        s.submit("a", feats.take(1))


def test_dispatch_failure_fails_futures_and_loop_survives():
    """A batch the loop cannot serve (tenant undeployed mid-flight)
    delivers its failure TO the waiting futures — callers never hang —
    and the one shared loop keeps serving every other tenant."""
    s = _stub_scheduler(queue_capacity=16, max_wait_ms=0.0)
    feats = _feats()
    s.add_tenant("a", object(), feats.take(2))
    s.add_tenant("b", object(), feats.take(2))
    s.start()
    try:
        s.registry.undeploy("a")
        future = s.submit("a", feats.take(1))
        with pytest.raises(KeyError, match="no model deployed"):
            future.result(10)
        out = s.predict("b", feats.take(2), timeout=10)
        assert out.num_rows == 2
    finally:
        s.close()


def test_scheduler_end_to_end_under_concurrent_clients():
    """Smoke: the real serve thread, three tenants, concurrent clients,
    every response bit-exact with the tenant's own model."""
    models = {name: _fit_lr(seed=i)
              for i, name in enumerate(("red", "green", "blue"))}
    feats = _feats(seed=4)
    refs = {name: m.transform(feats)[0]["rawPrediction"]
            for name, m in models.items()}
    s = SharedScheduler(max_batch_rows=64, max_wait_ms=1.0,
                        queue_capacity=8192)
    for i, (name, model) in enumerate(models.items()):
        s.add_tenant(name, model, feats.take(2),
                     slo=SLO_CLASSES[i % 3], weight=1.0 + i)
    s.start()
    errors = []

    def client(name, worker):
        crng = np.random.default_rng(worker)
        try:
            for _ in range(25):
                start = int(crng.integers(0, 200))
                rows = int(crng.integers(1, 7))
                out = s.predict(name, feats.slice(start, start + rows),
                                timeout=30)
                np.testing.assert_array_equal(
                    out["rawPrediction"],
                    refs[name][start:start + rows])
        except Exception as exc:  # noqa: BLE001
            errors.append(exc)

    try:
        threads = [threading.Thread(target=client, args=(name, 7 * i + 1))
                   for i, name in enumerate(models)
                   for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not errors, errors[:3]
        snap = s.snapshot()
        assert snap["requests"] == 150
        assert s.shed_counts() == {slo: 0 for slo in SLO_CLASSES}
    finally:
        s.close()
