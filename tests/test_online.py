"""Continuous learning (ISSUE 7): delta codec, publish protocol,
train-while-serve.

The acceptance bar: the model served after the cut at step T is
BIT-exact with an offline ``sgd_fit_outofcore`` over all WAL windows
<= T, and steady-state delta publishes trigger zero new XLA lowerings
(the publish is a device-resident buffer swap into already-compiled
bucketed executors — no reload, no warm-up).  The crashy half of the
story lives in tests/test_faults.py.
"""

import os

import numpy as np
import pytest

from flink_ml_tpu import Table
from flink_ml_tpu.data.wal import WindowBatchReader, WindowLog
from flink_ml_tpu.iteration import (
    CheckpointConfig,
    IterationBodyResult,
    IterationConfig,
    iterate,
)
from flink_ml_tpu.models.classification.logisticregression import (
    LogisticRegression,
)
from flink_ml_tpu.models.common.losses import logistic_loss
from flink_ml_tpu.models.common.sgd import SGDConfig, sgd_fit_outofcore
from flink_ml_tpu.online import (
    ContinuousLearner,
    DeltaBaseMismatch,
    DeltaCorrupt,
    DeltaEncoder,
    DeltaPublisher,
    DeltaShapeChanged,
    DeterminismViolation,
    FullUpdate,
    ParamDelta,
    PublishingListener,
    StalenessPolicy,
    apply_delta,
    diff_params,
    flatten_params,
    params_of_model,
    tree_digest,
)
from flink_ml_tpu.serving import ModelRegistry, ServingEndpoint, serve_model


# -- delta codec -------------------------------------------------------------

def test_delta_sparse_roundtrip_bitexact():
    base = {"w": np.arange(64, dtype=np.float32), "b": np.float32(0.5)}
    new = {"w": base["w"].copy(), "b": np.float32(0.5)}
    new["w"][3] = 7.5
    new["w"][41] = -2.0
    d = diff_params(base, new, step=5)
    assert d.changed_leaves == ["w"]
    assert d.leaves["w"].idx is not None          # sparse encode
    assert d.payload_bytes == 2 * (8 + 4)         # int64 idx + f32 val
    out = apply_delta(base, d)
    flat_new = flatten_params(new)
    assert all(out[k].tobytes() == flat_new[k].tobytes() for k in flat_new)


def test_delta_dense_leaf_ships_full_buffer():
    base = {"w": np.zeros(32, np.float32)}
    new = {"w": np.ones(32, np.float32)}           # 100% changed
    d = diff_params(base, new)
    assert d.leaves["w"].idx is None
    assert d.payload_bytes == 32 * 4
    out = apply_delta(base, d)
    assert out["w"].tobytes() == new["w"].tobytes()


def test_delta_bitexact_nan_and_signed_zero():
    """Raw-byte change detection: NaN payloads round-trip (a value
    compare would mark them changed forever), and +0.0 -> -0.0 is a
    REAL change the codec must carry."""
    base = {"w": np.array([0.0, 1.0, np.nan, 3.0], np.float32)}
    new = {"w": np.array([-0.0, 1.0, np.nan, 3.0], np.float32)}
    d = diff_params(base, new)
    assert d.leaves["w"].idx.tolist() == [0]      # only the zero flip
    out = apply_delta(base, d)
    assert out["w"].tobytes() == new["w"].tobytes()
    # identical trees (NaN included) encode as the empty delta
    d2 = diff_params(new, {"w": new["w"].copy()})
    assert d2.changed_leaves == []


def test_delta_nested_pytree_and_scalar_shapes():
    base = {"mlp": [{"w": np.ones((4, 2), np.float32),
                     "b": np.zeros(2, np.float32)}],
            "bias": np.float32(1.0)}
    new = {"mlp": [{"w": base["mlp"][0]["w"] * 2,
                    "b": base["mlp"][0]["b"]}],
           "bias": np.float32(2.0)}
    out = apply_delta(base, diff_params(base, new))
    assert out["bias"].shape == ()                # 0-d preserved
    assert out["mlp/0/w"].shape == (4, 2)
    from flink_ml_tpu.online import unflatten_params

    tree = unflatten_params(base, out)
    assert np.asarray(tree["mlp"][0]["w"]).tobytes() \
        == new["mlp"][0]["w"].tobytes()


def test_delta_base_mismatch_and_corrupt_detected():
    base = {"w": np.zeros(8, np.float32)}
    new = {"w": np.ones(8, np.float32)}
    d = diff_params(base, new)
    with pytest.raises(DeltaBaseMismatch):
        apply_delta({"w": np.full(8, 2.0, np.float32)}, d)
    torn = ParamDelta(step=d.step, base_digest=d.base_digest,
                      new_digest=d.new_digest ^ 1, leaves=d.leaves)
    with pytest.raises(DeltaCorrupt):
        apply_delta(base, torn)


def test_delta_shape_change_raises():
    base = {"w": np.zeros(8, np.float32)}
    with pytest.raises(DeltaShapeChanged):
        diff_params(base, {"w": np.zeros(9, np.float32)})
    with pytest.raises(DeltaShapeChanged):
        diff_params(base, {"w": np.zeros(8, np.float64)})
    with pytest.raises(DeltaShapeChanged):
        diff_params(base, {"v": np.zeros(8, np.float32)})


# -- serving-side publish protocol -------------------------------------------

def _lr_table(n=64, d=8, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d))
    y = (X[:, 0] + 0.3 * rng.normal(size=n) > 0).astype(np.int64)
    return Table({"features": X, "label": y})


def _served_w(endpoint, name="default"):
    model = endpoint.registry.current(name).servable.model
    return np.asarray(model._state.coefficients, np.float32)


def _publish_chain(endpoint, steps):
    """Publish a chain of nudged params; returns the final params."""
    pub = endpoint.delta_publisher()
    enc = DeltaEncoder()
    p = params_of_model(endpoint.registry.current("default").servable.model)
    for step in steps:
        p = {"w": p["w"].copy(), "b": p["b"]}
        p["w"][step % p["w"].size] += np.float32(0.125)
        pub.apply(enc.encode(step, p, pub.stats))
        enc.ack()
    return pub, enc, p


def test_publish_swaps_generation_and_serves_published_bits():
    model = LogisticRegression().set_max_iter(3).fit(_lr_table())
    feats = _lr_table(seed=5).drop("label")
    endpoint = serve_model(model, feats.take(2), max_batch_rows=32,
                           max_wait_ms=0.5)
    try:
        gen0 = endpoint.registry.current("default").generation
        pub, enc, p = _publish_chain(endpoint, [1, 2, 3])
        assert endpoint.registry.current("default").generation == gen0 + 3
        assert _served_w(endpoint).tobytes() == p["w"].tobytes()
        out = endpoint.predict(feats.take(4))
        assert "prediction" in out.column_names
        # second publish was an incremental delta (one slot changed)
        assert pub.stats.deltas >= 1
    finally:
        endpoint.close()


def test_publish_zero_new_lowerings_steady_state():
    """THE tentpole property: after warm-up, a publish+serve cycle
    compiles NOTHING — same-shape generations hit the already-compiled
    bucketed executors (params are runtime args in the serving jit
    cache), so the swap is a device-resident buffer move."""
    from flink_ml_tpu.utils.backend import count_compiles

    model = LogisticRegression().set_max_iter(3).fit(_lr_table())
    feats = _lr_table(seed=5).drop("label")
    endpoint = serve_model(model, feats.take(2), max_batch_rows=64,
                           max_wait_ms=0.5)
    try:
        pub = endpoint.delta_publisher()
        enc = DeltaEncoder()
        p = params_of_model(model)
        pub.apply(enc.encode(1, p, pub.stats))
        enc.ack()
        for n in (1, 2, 64):
            endpoint.predict(feats.take(n))       # settle wave
        with count_compiles() as count:
            for step in range(2, 12):
                p = {"w": p["w"] + np.float32(0.01), "b": p["b"]}
                pub.apply(enc.encode(step, p, pub.stats))
                enc.ack()
                endpoint.predict(feats.take(1 + step % 32))
        assert count() == 0, (
            f"{count()} new XLA lowerings across 10 publish+serve "
            "cycles — a delta publish recompiled something")
        assert endpoint.registry.current("default").generation >= 11
    finally:
        endpoint.close()


def test_publish_replay_is_idempotent_and_stale_steps_skip():
    model = LogisticRegression().set_max_iter(3).fit(_lr_table())
    endpoint = serve_model(model, _lr_table(seed=5).drop("label").take(2),
                           max_batch_rows=32, max_wait_ms=0.5)
    try:
        pub, enc, p = _publish_chain(endpoint, [4, 8])
        gen = endpoint.registry.current("default").generation
        # replayed cut at the SAME step with the same bits: no-op
        same = DeltaEncoder()
        r = pub.apply(same.encode(8, p, pub.stats))
        assert r.mode == "noop"
        assert endpoint.registry.current("default").generation == gen
        # an OLDER step (restore fell back a cut): serving never moves
        # backward
        older = {"w": np.zeros_like(p["w"]), "b": p["b"]}
        r = pub.apply(DeltaEncoder().encode(4, older, pub.stats))
        assert r.mode == "noop"
        assert _served_w(endpoint).tobytes() == p["w"].tobytes()
    finally:
        endpoint.close()


def test_publish_replay_with_different_bits_is_determinism_violation():
    model = LogisticRegression().set_max_iter(3).fit(_lr_table())
    endpoint = serve_model(model, _lr_table(seed=5).drop("label").take(2),
                           max_batch_rows=32, max_wait_ms=0.5)
    try:
        pub, enc, p = _publish_chain(endpoint, [4, 8])
        diverged = {"w": p["w"] + np.float32(1.0), "b": p["b"]}
        with pytest.raises(DeterminismViolation):
            pub.apply(DeltaEncoder().encode(8, diverged, pub.stats))
    finally:
        endpoint.close()


def test_stale_encoder_base_heals_with_full_reanchor():
    """A crash between publish and ack leaves the encoder one
    generation behind: its next delta base-mismatches, and
    encode_and_publish re-anchors with a full update."""
    from flink_ml_tpu.online import encode_and_publish

    model = LogisticRegression().set_max_iter(3).fit(_lr_table())
    endpoint = serve_model(model, _lr_table(seed=5).drop("label").take(2),
                           max_batch_rows=32, max_wait_ms=0.5)
    try:
        pub = endpoint.delta_publisher()
        enc = DeltaEncoder()
        p0 = params_of_model(model)
        encode_and_publish(enc, pub, 1, p0)
        p1 = {"w": p0["w"] + np.float32(0.5), "b": p0["b"]}
        pub.apply(enc.encode(2, p1, pub.stats))    # landed, NOT acked
        p2 = {"w": p1["w"] + np.float32(0.5), "b": p1["b"]}
        enc._pending = None                        # simulate crashed ack
        r = encode_and_publish(enc, pub, 3, p2)
        assert r.mode == "full"                    # healed by re-anchor
        assert _served_w(endpoint).tobytes() == p2["w"].tobytes()
    finally:
        endpoint.close()


def test_full_publish_with_changed_shape_refused_serving_unharmed():
    """A delta is shape-guarded by its base digest; a FULL update must
    be guarded explicitly — a shape-incompatible publish riding the
    rebind fast path (which skips warm-up) would break every later
    request.  The publisher refuses, and the live generation keeps
    answering."""
    model = LogisticRegression().set_max_iter(3).fit(_lr_table(d=8))
    feats = _lr_table(seed=5, d=8).drop("label")
    endpoint = serve_model(model, feats.take(2), max_batch_rows=32,
                           max_wait_ms=0.5)
    try:
        pub = endpoint.delta_publisher()
        wrong = DeltaEncoder().encode(     # 16-wide params on an 8-wide
            1, {"w": np.zeros(16, np.float32),   # generation
                "b": np.float32(0.0)}, pub.stats)
        gen = endpoint.registry.current("default").generation
        with pytest.raises(DeltaShapeChanged, match="registry.deploy"):
            pub.apply(wrong)
        assert endpoint.registry.current("default").generation == gen
        out = endpoint.predict(feats.take(3))
        assert out.num_rows == 3
    finally:
        endpoint.close()


def test_external_hot_swap_invalidates_publisher_base():
    """An operator hot_swap between trainer publishes moves the live
    generation: the publisher must re-anchor on what actually serves —
    a pending delta heals with a full re-anchor (never applies against
    the stale lineage), and a shape-incompatible trainer update is
    refused against the LIVE shapes, not the cached ones."""
    from flink_ml_tpu.online import encode_and_publish

    model = LogisticRegression().set_max_iter(3).fit(_lr_table(d=8))
    feats = _lr_table(seed=5, d=8).drop("label")
    endpoint = serve_model(model, feats.take(2), max_batch_rows=32,
                           max_wait_ms=0.5)
    try:
        pub = endpoint.delta_publisher()
        enc = DeltaEncoder()
        p = params_of_model(model)
        encode_and_publish(enc, pub, 1, p)
        # operator deploys a DIFFERENT model into the same entry
        other = LogisticRegression().set_max_iter(5).fit(_lr_table(seed=9))
        endpoint.hot_swap(other)
        # trainer's next delta: heals via full re-anchor onto its own
        # lineage (the publish protocol owns the entry again)
        p2 = {"w": p["w"] + np.float32(0.25), "b": p["b"]}
        r = encode_and_publish(enc, pub, 2, p2)
        assert r.mode == "full"
        assert _served_w(endpoint).tobytes() == p2["w"].tobytes()
    finally:
        endpoint.close()


def test_publish_compare_and_swap_refuses_stale_generation():
    """publish_servable is a compare-and-swap: a publish validated
    against a generation that a concurrent deploy has since replaced is
    refused (GenerationConflict), never silently clobbering the newer
    model; DeltaPublisher.apply retries through re-validation."""
    from flink_ml_tpu.serving.registry import GenerationConflict

    model = LogisticRegression().set_max_iter(3).fit(_lr_table())
    endpoint = serve_model(model, _lr_table(seed=5).drop("label").take(2),
                           max_batch_rows=32, max_wait_ms=0.5)
    try:
        live = endpoint.registry.current("default")
        rebound = live.servable.rebind(live.servable.model)
        endpoint.hot_swap(LogisticRegression().set_max_iter(5)
                          .fit(_lr_table(seed=9)))   # generation moves
        with pytest.raises(GenerationConflict):
            endpoint.registry.publish_servable(
                "default", rebound, expected_generation=live.generation)
        # unconditional publish (no expectation) still works
        endpoint.registry.publish_servable("default", rebound)
    finally:
        endpoint.close()


def test_learner_publish_cadence_skips_cuts(tmp_path):
    """StalenessPolicy(publish_every=2) thins the publish cadence to
    every other cut; skipped cuts are counted and never fetched."""
    windows = list(_windows(0, 16))
    boot = LogisticRegression().set_max_iter(1).fit(windows[0])
    endpoint = serve_model(boot, windows[0].drop("label").take(2),
                           max_batch_rows=32, max_wait_ms=0.5)
    try:
        learner = ContinuousLearner(
            loss_fn=logistic_loss, num_features=4,
            source=iter(windows), wal_dir=str(tmp_path / "wal"),
            endpoint=endpoint, batch_rows=16,
            checkpoint=CheckpointConfig(str(tmp_path / "ck")),
            publish_every_steps=4,
            policy=StalenessPolicy(publish_every=2))
        learner.run(max_windows=16)
        steps = [r.step for r in learner.publish_log]
        assert steps == [8, 16]                   # cuts 4 and 12 skipped
        assert learner.publisher.stats.skips >= 2
        w_off, _ = _offline_fit(windows, 16, every=4)
        assert _served_w(endpoint).tobytes() == w_off.tobytes()
    finally:
        endpoint.close()


def test_generic_servable_refuses_rebind():
    from flink_ml_tpu.serving.executor import ServableModel

    model = LogisticRegression().set_max_iter(2).fit(_lr_table())
    servable = ServableModel(model, _lr_table().drop("label").take(1))
    assert not servable.rebind_safe
    with pytest.raises(TypeError, match="not rebind-safe"):
        servable.rebind(model)


def test_staleness_metrics_and_policy_decisions():
    model = LogisticRegression().set_max_iter(3).fit(_lr_table())
    feats = _lr_table(seed=5).drop("label")
    endpoint = serve_model(model, feats.take(2), max_batch_rows=32,
                           max_wait_ms=0.5)
    try:
        pub, enc, p = _publish_chain(endpoint, [1, 2, 3])
        endpoint.predict(feats.take(2))
        snap = endpoint.metrics.snapshot()
        assert snap["publishes_full"] >= 1
        assert snap["publishes_delta"] >= 1
        assert snap["model_staleness_seconds"] >= 0.0
        assert "publishes_per_sec" in snap and "last_publish_bytes" in snap
    finally:
        endpoint.close()
    from flink_ml_tpu.online import PublishStats

    policy = StalenessPolicy(publish_every=2, full_every=3)
    stats = PublishStats(publishes=1)
    assert policy.due(0, stats) and not policy.due(1, stats)
    # payload parity forces full (re-anchor is free at equal bytes)
    assert policy.choose(95, 100, stats) == "full"
    assert policy.choose(10, 100, stats) == "delta"
    # cadence re-anchor: every full_every-th publish ships full
    assert policy.choose(10, 100, PublishStats(publishes=3)) == "full"


# -- WAL window reader -------------------------------------------------------

def _windows(start, stop, rows=16, d=4):
    for i in range(start, stop):
        rng = np.random.default_rng(1000 + i)
        X = rng.normal(size=(rows, d)).astype(np.float32)
        yield Table({"features": X,
                     "label": (X[:, 0] > 0).astype(np.float32)})


def test_window_batch_reader_ragged_window_raises(tmp_path):
    log = WindowLog(iter([Table({"features": np.zeros((16, 4)),
                                 "label": np.zeros(16)}),
                          Table({"features": np.zeros((7, 4)),
                                 "label": np.zeros(7)})]),
                    str(tmp_path / "wal"))
    reader = WindowBatchReader(log, 16)
    it = iter(reader)
    next(it)
    with pytest.raises(ValueError, match="fixed window grid"):
        next(it)


def test_window_batch_reader_seek_rides_wal_cursor(tmp_path):
    d = str(tmp_path / "wal")
    for _ in WindowLog(_windows(0, 6), d):
        pass                                       # log 6 windows
    log = WindowLog(iter(()), d)
    reader = WindowBatchReader(log, 16)
    with pytest.raises(ValueError, match="window boundaries"):
        reader.seek(17)
    reader.seek(4 * 16)
    batches = list(reader)
    assert len(batches) == 2                       # replayed 4, 5
    oracle = list(_windows(4, 6))
    np.testing.assert_array_equal(batches[0]["features"],
                                  np.asarray(oracle[0]["features"]))


# -- the acceptance bar ------------------------------------------------------

class _SpyPublisher(DeltaPublisher):
    """Records the full published params at every landed publish."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.history = []

    def apply(self, update):
        result = super().apply(update)
        if result.mode != "noop":
            self.history.append(
                (result.step, {k: v.copy()
                               for k, v in self._base.items()}))
        return result


def _offline_fit(windows, upto, every):
    def make_reader():
        for w in windows[:upto]:
            yield w.to_dict()

    state, _ = sgd_fit_outofcore(
        logistic_loss, make_reader, num_features=4,
        config=SGDConfig(max_epochs=1, tol=0.0), steps_per_dispatch=every)
    return np.asarray(state.coefficients, np.float32), \
        np.float32(state.intercept)


def test_train_while_serve_served_bits_match_offline_fit(tmp_path):
    """ROADMAP item 1 acceptance (crash-free half): at EVERY publish
    step T, the published params are bit-exact with an offline
    single-pass fit over WAL windows <= T, and the final served model is
    bit-exact with the offline fit over all of them."""
    windows = list(_windows(0, 20))
    boot = LogisticRegression().set_max_iter(1).fit(windows[0])
    endpoint = serve_model(boot, windows[0].drop("label").take(2),
                           max_batch_rows=32, max_wait_ms=0.5)
    try:
        learner = ContinuousLearner(
            loss_fn=logistic_loss, num_features=4,
            source=iter(windows), wal_dir=str(tmp_path / "wal"),
            endpoint=endpoint, batch_rows=16,
            checkpoint=CheckpointConfig(str(tmp_path / "ck")),
            publish_every_steps=4)
        spy = _SpyPublisher(endpoint.registry, "default",
                            metrics=endpoint.metrics)
        learner.publisher = spy
        state, loss_log = learner.run(max_windows=20)
        assert len(loss_log) == 1                  # single unbounded pass
        steps = [s for s, _ in spy.history]
        assert steps == [4, 8, 12, 16, 20]
        for step, flat in spy.history:
            w_off, b_off = _offline_fit(windows, step, every=4)
            assert flat["w"].tobytes() == w_off.tobytes(), \
                f"published params at step {step} != offline fit"
            assert flat["b"].tobytes() == np.asarray(b_off).tobytes()
        w_final, _ = _offline_fit(windows, 20, every=4)
        assert _served_w(endpoint).tobytes() == w_final.tobytes()
        # serving answered on the continuously-published generations
        out = endpoint.predict(windows[3].drop("label"))
        assert out.num_rows == 16
    finally:
        endpoint.close()


def test_hosted_iterate_listener_publishes_at_checkpoints(tmp_path):
    """The hosted-``iterate`` flavor (FTRL/online-KMeans-style bodies):
    a PublishingListener on the checkpoint hook pushes every durable
    cut's state into the live generation."""
    import jax.numpy as jnp

    windows = list(_windows(0, 12))
    boot = LogisticRegression().set_max_iter(1).fit(windows[0])
    endpoint = serve_model(boot, windows[0].drop("label").take(2),
                           max_batch_rows=32, max_wait_ms=0.5)
    try:
        listener = PublishingListener(
            endpoint.delta_publisher(),
            params_of=lambda s: {"w": s["w"], "b": s["b"]})

        def body(state, epoch, data):
            X, y = data
            margin = X @ state["w"] + state["b"]
            p = 1.0 / (1.0 + jnp.exp(-margin))
            g = X.T @ (p - y) / X.shape[0]
            return IterationBodyResult({
                "w": state["w"] - 0.5 * g,
                "b": state["b"] - 0.5 * jnp.mean(p - y)})

        state0 = {"w": jnp.zeros(4, jnp.float32),
                  "b": jnp.asarray(0.0, jnp.float32)}
        payloads = ((np.asarray(w["features"], np.float32),
                     np.asarray(w["label"], np.float32))
                    for w in windows)
        result = iterate(
            body, state0, payloads,
            config=IterationConfig(mode="hosted", jit=True),
            listeners=[listener],
            checkpoint=CheckpointConfig(str(tmp_path / "ck"), interval=4))
        assert [r.step for r in listener.publish_log] == [4, 8, 12]
        final_w = np.asarray(result.state["w"], np.float32)
        assert _served_w(endpoint).tobytes() == final_w.tobytes()
    finally:
        endpoint.close()
