"""Vector retrieval serving (ISSUE 19): IVF / IVF-PQ index build,
the registry-dispatched fused scan+top-k search, incremental updates
over the delta codec, and the servable/scheduler integration.

What these tests pin down:

- build invariants: padded posting-list row blocks honor the shared
  ELL padding contract, CSR offsets account every live row, loud
  validation errors;
- search correctness: full-probe search EQUALS the float64 brute-force
  oracle; the acceptance operating point (recall@10 >= 0.95 while
  analytically scanning <= 25% of the corpus); pad slots surface as
  neighbor -1 at +inf, never a fake id;
- PQ: the kernel's ADC distances exactly match explicit
  reconstructed-vector distances (encode and LUT agree), and PQ recall
  is high when the corpus is PQ-representable;
- incremental updates: delta insert/delete with swap-remove semantics,
  the old generation untouched (in-flight queries finish on old
  lists), overflow and centroid drift re-anchor, publish adapters
  round-trip params;
- serving: IVFIndex is the first NON-model servable — admission of a
  second same-schema index tenant costs ZERO new lowerings, delta
  publishes swap generations atomically, and the RecallProbe gauge
  rides the tenant's ServingMetrics subtree.
"""

import numpy as np
import pytest

from flink_ml_tpu import Table
from flink_ml_tpu.retrieval import (
    IVFIndex,
    PQConfig,
    RecallProbe,
    exact_neighbors,
    recall_at_k,
)
from flink_ml_tpu.serving import SLO_INTERACTIVE, SharedScheduler

# the ISSUE 19 acceptance operating point
RECALL_FLOOR = 0.95
SCAN_BUDGET = 0.25


# -- fixtures ----------------------------------------------------------------

def _gaussian(n=600, d=32, seed=3):
    return np.random.default_rng(seed).normal(size=(n, d)).astype(np.float32)


def _clustered(n=2048, d=16, nclusters=64, seed=4, spread=0.5):
    """Well-separated modes — the regime IVF's scan budget pays off in."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(nclusters, d)).astype(np.float32) * 10.0
    assign = rng.integers(0, nclusters, size=n)
    X = (centers[assign] + rng.normal(size=(n, d)) * spread
         ).astype(np.float32)
    return X


def _queries_near(X, count, seed=5, jitter=0.05):
    rng = np.random.default_rng(seed)
    pick = rng.choice(X.shape[0], size=count, replace=False)
    return (X[pick] + rng.normal(size=(count, X.shape[1])) * jitter
            ).astype(np.float32)


def _pq_friendly(nclusters=16, d=16, seed=6):
    """Core/halo corpus: each cluster holds a TIGHT core of 10 (the true
    top-10 of a near-center query, at ~zero distance) and a wide halo.
    The distance gap dwarfs the PQ quantization distortion, so recall
    measures the kernel, not codebook luck."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(nclusters, d)).astype(np.float32) * 10.0
    core = (np.repeat(centers, 10, axis=0)
            + rng.normal(size=(nclusters * 10, d)) * 0.05)
    halo = (np.repeat(centers, 30, axis=0)
            + rng.normal(size=(nclusters * 30, d)) * 1.0)
    X = np.concatenate([core, halo]).astype(np.float32)
    q = (centers[rng.integers(0, nclusters, size=32)]
         + rng.normal(size=(32, d)) * 0.02).astype(np.float32)
    return X, q


# -- build invariants --------------------------------------------------------

def test_build_validation_is_loud():
    X = _gaussian(n=64, d=8)
    with pytest.raises(ValueError, match="nlist"):
        IVFIndex.build(X, nlist=65)
    with pytest.raises(ValueError, match="non-empty"):
        IVFIndex.build(np.zeros((0, 8), np.float32), nlist=1)
    with pytest.raises(ValueError, match="unique"):
        IVFIndex.build(X, nlist=4, ids=np.zeros(64, np.int32))
    with pytest.raises(ValueError, match="non-negative"):
        IVFIndex.build(X, nlist=4, ids=np.arange(64) - 1)
    with pytest.raises(ValueError, match="must divide"):
        IVFIndex.build(X, nlist=4, pq=PQConfig(m=3))
    with pytest.raises(ValueError, match="block"):
        IVFIndex.build(X, nlist=2, block=8)   # a list must overflow 8


def test_build_posting_lists_honor_padding_contract():
    X = _gaussian(n=300, d=16, seed=7)
    idx = IVFIndex.build(X, nlist=8, k=5, seed=1)
    ids2, counts = idx.params["ids"], idx.params["counts"]
    assert idx.block % 8 == 0
    assert ids2.shape == (8, idx.block)
    assert idx.num_vectors == 300 and counts.sum() == 300
    # CSR offsets account every live row; pad slots are -1 with
    # exact-zero vector rows (the maskless pad_rows_to_block contract)
    assert idx.offsets[-1] == 300
    vecs = idx.params["vecs"].reshape(8, idx.block, 16)
    for lst in range(8):
        c = int(counts[lst])
        assert np.all(ids2[lst, :c] >= 0) and np.all(ids2[lst, c:] == -1)
        assert np.all(vecs[lst, c:] == 0.0)
    # every stored id is addressable and round-trips its vector
    sids, svecs = idx.stored_vectors()
    np.testing.assert_array_equal(sids, np.arange(300))
    np.testing.assert_array_equal(svecs, X)


# -- search correctness ------------------------------------------------------

def test_full_probe_search_equals_float64_oracle():
    X = _gaussian(n=500, d=24, seed=8)
    idx = IVFIndex.build(X, nlist=8, k=10, seed=2)
    q = _gaussian(n=20, d=24, seed=9)
    nn, dist = idx.search(q, nprobe=idx.nlist)
    expect = exact_neighbors(q, X, np.arange(500), 10)
    np.testing.assert_array_equal(nn, expect)
    assert nn.dtype == np.int64 and dist.dtype == np.float32
    assert np.all(np.diff(dist, axis=1) >= 0), "distances not ascending"
    # the reported distances ARE squared L2 (f32 expression)
    d2 = np.sum((q[:, None, :] - X[nn]) ** 2, axis=-1)
    np.testing.assert_allclose(dist, d2, rtol=1e-4, atol=1e-3)


def test_acceptance_recall_at_bounded_scan():
    """THE acceptance point: recall@10 >= 0.95 at the reference nprobe
    while the probed lists provably hold <= 25% of the corpus (analytic
    accounting from the CSR counts, not timing)."""
    X = _clustered()
    idx = IVFIndex.build(X, nlist=64, k=10, nprobe=8, seed=3)
    q = _queries_near(X, 48)
    frac = idx.scan_fraction(q)
    assert 0.0 < frac <= SCAN_BUDGET, f"scan fraction {frac}"
    nn, _ = idx.search(q)
    rec = recall_at_k(nn, exact_neighbors(q, X, np.arange(X.shape[0]), 10))
    assert rec >= RECALL_FLOOR, f"recall {rec} at scan fraction {frac}"
    # full probe scans everything, by the same accounting
    assert idx.scan_fraction(q, nprobe=idx.nlist) == pytest.approx(1.0)


def test_short_lists_pad_with_minus_one_never_fake_ids():
    X = _gaussian(n=12, d=8, seed=10)
    idx = IVFIndex.build(X, nlist=4, k=10, nprobe=1, seed=4)
    q = _gaussian(n=6, d=8, seed=11)
    nn, dist = idx.search(q)
    counts = idx.params["counts"]
    assert int(counts.max()) < 10   # every probe sees fewer than k rows
    for row_nn, row_d in zip(nn, dist):
        real = row_nn >= 0
        assert np.all(np.isfinite(row_d[real]))
        assert np.all(np.isinf(row_d[~real]))
        # a -1 slot never precedes a real id (top-k keeps real firsts)
        assert not np.any(np.diff(real.astype(int)) > 0)


def test_pq_adc_distances_match_explicit_reconstruction():
    """The ADC lookup-table scan must equal distances to the explicitly
    reconstructed vectors (centroid + decoded codewords) — encode and
    LUT disagree only through bugs, not quantization."""
    X = _gaussian(n=400, d=32, seed=12)
    idx = IVFIndex.build(X, nlist=4, k=8, pq=PQConfig(m=8, ksub=16),
                         seed=5)
    q = _gaussian(n=10, d=32, seed=13)
    nn, dist = idx.search(q, nprobe=idx.nlist)

    cb_q, cb_s = idx.params["cb_q"], idx.params["cb_s"]
    decoded = cb_q.astype(np.float32) * cb_s[..., None]     # (m, ksub, dsub)
    codes = idx.params["codes"].reshape(idx.nlist, idx.block, -1)
    ids2 = idx.params["ids"]
    recon = {}
    for lst in range(idx.nlist):
        for j in range(int(idx.params["counts"][lst])):
            vid = int(ids2[lst, j])
            parts = [decoded[s, int(codes[lst, j, s])]
                     for s in range(cb_q.shape[0])]
            recon[vid] = (idx.params["centroids"][lst]
                          + np.concatenate(parts))
    for qi in range(q.shape[0]):
        for slot in range(nn.shape[1]):
            vid = int(nn[qi, slot])
            d2 = float(np.sum((q[qi] - recon[vid]) ** 2,
                              dtype=np.float64))
            assert dist[qi, slot] == pytest.approx(d2, rel=1e-4, abs=1e-3)


def test_pq_recall_on_representable_corpus():
    """On the core/halo corpus the true top-10 gap dwarfs quantization
    distortion — the PQ index must clear the same recall floor."""
    X, q = _pq_friendly()
    idx = IVFIndex.build(X, nlist=16, k=10, nprobe=4,
                         pq=PQConfig(m=8, ksub=16), seed=6)
    nn, _ = idx.search(q)
    rec = recall_at_k(nn, exact_neighbors(q, X, np.arange(X.shape[0]), 10))
    assert rec >= RECALL_FLOOR, f"PQ recall {rec}"


def test_search_plan_and_option_views():
    X = _gaussian(n=200, d=16, seed=15)
    idx = IVFIndex.build(X, nlist=8, k=5, seed=7)
    plan = idx.search_plan()
    assert plan.sig == idx.sig() and plan.backend == "xla"  # CPU host
    view = idx.with_options(nprobe=8, k=3)
    assert (view.nprobe, view.k) == (8, 3)
    assert view.params is idx.params           # same lists, new schema
    assert (idx.nprobe, idx.k) != (8, 3)       # the view never mutates
    with pytest.raises(ValueError, match="nprobe"):
        idx.with_options(nprobe=9)
    with pytest.raises(TypeError, match="query"):
        idx.transform(Table({"wrong": X}))


# -- incremental updates -----------------------------------------------------

def test_updated_delta_insert_and_delete_with_swap_remove():
    X = _gaussian(n=160, d=8, seed=16)
    idx = IVFIndex.build(X, nlist=4, k=5, seed=8, drift_threshold=None)
    before = {k: v.copy() for k, v in idx.params.items()}

    new_vecs = _gaussian(n=3, d=8, seed=17) * 0.5
    mode, nxt = idx.updated(inserts=new_vecs, delete_ids=[0, 7])
    assert mode == "delta"
    # the OLD index is untouched — in-flight queries finish on old lists
    for name, arr in before.items():
        np.testing.assert_array_equal(idx.params[name], arr)
    assert nxt.num_vectors == 160 + 3 - 2
    # deleted ids are gone, inserted ids resolve to their vectors
    sids, svecs = nxt.stored_vectors()
    assert 0 not in sids and 7 not in sids
    for off, vid in enumerate(range(160, 163)):
        assert vid in sids
        np.testing.assert_array_equal(
            svecs[np.searchsorted(sids, vid)], new_vecs[off])
    # swap-remove kept lists dense: every live slot < count, pads -1
    ids2, counts = nxt.params["ids"], nxt.params["counts"]
    for lst in range(nxt.nlist):
        c = int(counts[lst])
        assert np.all(ids2[lst, :c] >= 0) and np.all(ids2[lst, c:] == -1)
    # full-probe search over the new index matches the oracle of the
    # surviving corpus (the moved rows' vectors moved with their ids)
    q = _gaussian(n=8, d=8, seed=18)
    nn, _ = nxt.search(q, nprobe=nxt.nlist)
    np.testing.assert_array_equal(
        nn, exact_neighbors(q, svecs, sids, nxt.k))
    with pytest.raises(KeyError, match="delete id"):
        nxt.updated(delete_ids=[0])
    with pytest.raises(ValueError, match="already live"):
        nxt.updated(inserts=new_vecs[:1], insert_ids=[161])


def test_updated_overflow_reanchors_with_full_corpus():
    X = _gaussian(n=40, d=8, seed=19)
    idx = IVFIndex.build(X, nlist=4, k=5, seed=9, list_slack=0,
                         drift_threshold=None)
    # flood one region until some list overflows its block
    target = X[int(np.argmax(np.bincount(
        np.argmin(np.sum((X[:, None, :] - idx.params["centroids"]) ** 2,
                         axis=-1), axis=1))))]
    flood = (target[None, :]
             + _gaussian(n=idx.block + 4, d=8, seed=20) * 0.01)
    mode, nxt = idx.updated(inserts=flood)
    assert mode == "reanchor"
    assert nxt.num_vectors == 40 + idx.block + 4
    sids, svecs = nxt.stored_vectors()
    q = _gaussian(n=4, d=8, seed=21)
    nn, _ = nxt.search(q, nprobe=nxt.nlist)
    np.testing.assert_array_equal(
        nn, exact_neighbors(q, svecs, sids, nxt.k))


def test_updated_drift_reanchors():
    X = _gaussian(n=120, d=8, seed=22)
    idx = IVFIndex.build(X, nlist=4, k=5, seed=10, drift_threshold=1e-6)
    assert idx.centroid_drift() >= 0.0
    shifted = _gaussian(n=6, d=8, seed=23) + 4.0   # off-distribution mass
    mode, nxt = idx.updated(inserts=shifted)
    assert mode == "reanchor"
    assert nxt.num_vectors == 126


def test_publish_adapters_round_trip_index_params():
    from flink_ml_tpu.online.publish import (
        model_with_params,
        params_of_model,
    )

    X = _gaussian(n=120, d=8, seed=24)
    idx = IVFIndex.build(X, nlist=4, k=5, seed=11, drift_threshold=None)
    params = params_of_model(idx)
    assert set(params) == set(idx.params)
    _, nxt = idx.updated(inserts=_gaussian(n=2, d=8, seed=25))
    rebound = model_with_params(idx, params_of_model(nxt))
    assert isinstance(rebound, IVFIndex)
    q = _gaussian(n=6, d=8, seed=26)
    np.testing.assert_array_equal(rebound.search(q)[0], nxt.search(q)[0])
    # the rebound clone serves the new lists; the source is untouched
    assert rebound.params is not idx.params


# -- serving integration -----------------------------------------------------

def _built_pair(seed=27):
    """Two same-shape indexes (block pinned) — the zero-lowerings
    admission fixture."""
    X1, X2 = _gaussian(n=240, d=16, seed=seed), \
        _gaussian(n=240, d=16, seed=seed + 1)
    a = IVFIndex.build(X1, nlist=8, k=5, nprobe=2, seed=1, block=80)
    b = IVFIndex.build(X2, nlist=8, k=5, nprobe=2, seed=2, block=80)
    assert a.sig() == b.sig()
    return a, b


def test_index_tenant_admits_with_zero_new_lowerings():
    """The registry dividend extends to the first NON-model servable:
    index tenant N+1 of a served (nprobe, k, dim, pq) schema warms
    entirely out of the shared jit cache."""
    from flink_ml_tpu.utils.backend import count_compiles

    a, b = _built_pair()
    q = Table({"query": _gaussian(n=16, d=16, seed=29)})
    s = SharedScheduler(max_batch_rows=64, max_wait_ms=0.5,
                        queue_capacity=1024)
    s.add_tenant("idx-a", a, q.take(2), slo=SLO_INTERACTIVE)
    s.start()
    try:
        for n in (1, 2, 16):        # settle lazy one-time work
            s.predict("idx-a", q.take(n))
        ref_b = b.transform(q.take(5))[0]["neighbors"]
        with count_compiles() as count:
            s.add_tenant("idx-b", b, q.take(2), slo=SLO_INTERACTIVE)
            out = s.predict("idx-b", q.take(5))
        assert count() == 0, (
            f"{count()} new lowerings admitting a same-schema index "
            "tenant")
        np.testing.assert_array_equal(out["neighbors"], ref_b)
    finally:
        s.close()


def test_delta_publish_swaps_generations_atomically():
    """Insert-as-delta through the PR 7 codec: the generation advances,
    the swapped lists serve the inserted vector, and the PREVIOUS
    generation's servable still answers with the old lists bit-for-bit
    (in-flight queries finish on what they started on)."""
    from flink_ml_tpu.online import DeltaEncoder

    X = _gaussian(n=240, d=16, seed=30)
    idx = IVFIndex.build(X, nlist=8, k=5, nprobe=8, seed=3,
                         drift_threshold=None)
    q = Table({"query": _gaussian(n=8, d=16, seed=31)})
    s = SharedScheduler(max_batch_rows=64, max_wait_ms=0.5,
                        queue_capacity=1024)
    s.add_tenant("retr", idx, q.take(2), slo=SLO_INTERACTIVE)
    s.start()
    try:
        ref_old = s.predict("retr", q)["neighbors"]
        live0 = s.registry.current("retr")
        old_servable = live0.servable

        # insert the queries themselves: generation 2 MUST return them
        mode, nxt = idx.updated(inserts=np.asarray(q["query"]))
        assert mode == "delta"
        pub = s.delta_publisher("retr")
        enc = DeltaEncoder()
        res1 = pub.apply(enc.encode(1, nxt.params, pub.stats))
        enc.ack()
        assert res1.generation == 2

        got = s.predict("retr", q)["neighbors"]
        np.testing.assert_array_equal(
            np.asarray(got)[:, 0], np.arange(240, 248))
        # the old generation's servable object still serves old bits
        np.testing.assert_array_equal(
            old_servable.predict(q)["neighbors"], ref_old)
        live1 = s.registry.current("retr")
        assert live1.generation > live0.generation
        assert live1.servable is not old_servable
    finally:
        s.close()


def test_recall_probe_rides_tenant_serving_metrics():
    X = _clustered(n=1024, d=16, nclusters=32, seed=32)
    idx = IVFIndex.build(X, nlist=32, k=10, nprobe=32, seed=4)
    q = Table({"query": _queries_near(X, 16, seed=33)})
    s = SharedScheduler(max_batch_rows=64, max_wait_ms=0.5,
                        queue_capacity=1024)
    tenant = s.add_tenant("retr", idx, q.take(2), slo=SLO_INTERACTIVE)
    s.start()
    try:
        out = s.predict("retr", q)
        probe = RecallProbe(idx, sample=1.0)
        assert np.isnan(probe.value)             # absent until sampled
        batch = probe.observe(np.asarray(q["query"]),
                              neighbors=np.asarray(out["neighbors"]))
        # full probe + exact scan of the same corpus: perfect recall
        assert batch == 1.0 and probe.value == 1.0
        assert probe.publish(tenant.metrics) == 1.0
        assert tenant.metrics.recall_probe == 1.0
        snap = tenant.metrics.snapshot()
        key = [k for k in snap if k.endswith("recall_probe")]
        assert key and snap[key[0]] == 1.0
        mean, count = probe.reset()
        assert mean == 1.0 and count == 160 and np.isnan(probe.value)
    finally:
        s.close()


def test_recall_probe_validates_sample():
    X = _gaussian(n=64, d=8, seed=34)
    idx = IVFIndex.build(X, nlist=4, k=5, seed=5)
    with pytest.raises(ValueError, match="sample"):
        RecallProbe(idx, sample=0.0)
    probe = RecallProbe(idx, sample=1e-12, seed=1)
    assert probe.observe(X[:4]) is None          # kept no rows: no score
    assert np.isnan(probe.value)


def test_recall_at_k_scoring_rules():
    found = np.array([[1, 2, -1], [9, 9, 9]])
    expected = np.array([[1, 2, 3], [7, 8, 9]])
    # -1 never counts; duplicates in found count the intersection once
    assert recall_at_k(found, expected) == pytest.approx((2 + 1) / 6)
    assert recall_at_k(np.zeros((0, 3)), np.zeros((0, 3))) == 1.0
    with pytest.raises(ValueError, match="matching n"):
        recall_at_k(found, expected[:1])
    # exact_neighbors pads beyond the corpus with -1
    out = exact_neighbors(np.zeros((2, 4)), np.zeros((1, 4)),
                          np.array([5]), k=3)
    np.testing.assert_array_equal(out, [[5, -1, -1], [5, -1, -1]])
