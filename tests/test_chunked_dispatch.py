"""Chunked-scan out-of-core execution: W prefetched batches stack into
one device chunk and run as ONE jitted lax.scan with a donated carry, so
W optimizer steps cost one host dispatch (the fused-loop dispatch
amortization applied to the streaming paths).  These tests pin the two
contracts the layer rides on:

- BIT-EXACTNESS: any two ``steps_per_dispatch`` values produce identical
  results on the same batch stream, including a padded (masked) final
  chunk — the dead steps freeze the carry exactly.
- PIPELINE HEALTH: the prefetch reassembly keeps put concurrency under
  backpressure (puts happen outside ``flush_lock``), and an in-stream
  error stops further ``device_put`` work.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ml_tpu.data.datacache import DataCacheReader, DataCacheWriter
from flink_ml_tpu.data.prefetch import PrefetchStats, prefetch_to_device
from flink_ml_tpu.models.common.losses import logistic_loss
from flink_ml_tpu.models.common.sgd import SGDConfig, sgd_fit_outofcore


def _lr_cache(tmp_path, name="chunk_cache", n=4096, d=16, seed=0):
    rng = np.random.default_rng(seed)
    true_w = rng.normal(size=(d,))
    cache = str(tmp_path / name)
    writer = DataCacheWriter(cache, segment_rows=1024)
    for start in range(0, n, 512):
        X = rng.normal(size=(512, d)).astype(np.float32)
        writer.append({"features": X,
                       "label": (X @ true_w > 0).astype(np.float32)})
    writer.finish()
    return cache


# ------------------------------------------------------ sgd streaming


def test_sgd_streaming_chunked_bitexact_w_sweep(tmp_path):
    """W in (1, 3, 8) on an 11-batch epoch: W=3 and W=8 both pad the
    final chunk (11 % 3 != 0, 11 % 8 != 0), and every W lands on
    BIT-identical parameters and loss logs."""
    cache = _lr_cache(tmp_path)
    cfg = SGDConfig(learning_rate=0.5, max_epochs=3, tol=0.0)

    results = {}
    for W in (1, 3, 8):
        info = {}
        state, log = sgd_fit_outofcore(
            logistic_loss,
            # 4096 / 384 -> 11 batches (final one partial-row as well)
            lambda: DataCacheReader(cache, batch_rows=384),
            num_features=16, config=cfg, steps_per_dispatch=W,
            stream_info=info)
        assert info["steps_per_dispatch"] == W
        assert info["dispatches_per_epoch"] == [-(-11 // W)] * 3
        results[W] = (state, log)

    ref_state, ref_log = results[1]
    for W in (3, 8):
        state, log = results[W]
        np.testing.assert_array_equal(state.coefficients,
                                      ref_state.coefficients)
        assert state.intercept == ref_state.intercept
        np.testing.assert_array_equal(log, ref_log)


def test_sgd_chunked_smoke_w2(tmp_path):
    """Tier-1-safe smoke: tiny rows, W=2, padded final chunk — the
    chunked path runs in every CI pass."""
    cache = _lr_cache(tmp_path, "smoke", n=1280, d=8, seed=1)
    info = {}
    state, log = sgd_fit_outofcore(
        logistic_loss, lambda: DataCacheReader(cache, batch_rows=256),
        num_features=8,
        config=SGDConfig(learning_rate=0.5, max_epochs=2, tol=0.0),
        steps_per_dispatch=2, stream_info=info)
    # 5 batches -> 3 dispatches (last chunk padded+masked)
    assert info["steps_per_dispatch"] == 2
    assert info["dispatches_per_epoch"] == [3, 3]
    assert np.all(np.isfinite(state.coefficients))
    assert log[-1] < log[0]


def test_sgd_chunked_checkpoint_cuts_at_chunk_boundaries(tmp_path):
    """Mid-epoch checkpoint cuts land at chunk boundaries and resume
    bit-exactly (chunk-granular exactly-once)."""
    from flink_ml_tpu.iteration.checkpoint import CheckpointConfig

    cache = _lr_cache(tmp_path, "ckpt", n=2048, d=8, seed=2)
    cfg = SGDConfig(learning_rate=0.4, max_epochs=3, tol=0.0)

    def reader():
        return DataCacheReader(cache, batch_rows=256)   # 8 batches/epoch

    ref_state, ref_log = sgd_fit_outofcore(
        logistic_loss, reader, num_features=8, config=cfg,
        steps_per_dispatch=3)

    calls = [0]

    def failing_reader():
        def gen():
            for b in DataCacheReader(cache, batch_rows=256):
                calls[0] += 1
                if calls[0] > 12:
                    raise RuntimeError("injected mid-epoch failure")
                yield b
        return gen()

    ckpt = CheckpointConfig(str(tmp_path / "ck"), max_to_keep=4)
    with pytest.raises(RuntimeError, match="injected"):
        sgd_fit_outofcore(
            logistic_loss, failing_reader, num_features=8, config=cfg,
            steps_per_dispatch=3, cache_decoded=False,
            checkpoint=ckpt, checkpoint_every_steps=2)

    resumed_state, resumed_log = sgd_fit_outofcore(
        logistic_loss, reader, num_features=8, config=cfg,
        steps_per_dispatch=3, checkpoint=ckpt, checkpoint_every_steps=2,
        resume=True)
    np.testing.assert_array_equal(resumed_state.coefficients,
                                  ref_state.coefficients)
    np.testing.assert_array_equal(resumed_log, ref_log)


def test_sgd_chunked_streaming_ell_matches_w1(tmp_path, monkeypatch):
    """The mixed ELL streaming path chunk-scans its layout-stack batches
    the same way: W=4 == W=1 bitwise through the sharded ELL update."""
    from flink_ml_tpu.models.common import sgd as sgd_mod

    rng = np.random.default_rng(7)
    n, nd, nc, d = 2000, 3, 4, 128 * 128
    dense = rng.normal(size=(n, nd)).astype(np.float32)
    cat = rng.integers(0, d, size=(n, nc)).astype(np.int32)
    y = rng.integers(0, 2, size=n).astype(np.float32)
    cache = str(tmp_path / "ell")
    w = DataCacheWriter(cache, segment_rows=1024)
    w.append({"d": dense, "c": cat, "label": y})
    w.finish()

    monkeypatch.setattr(sgd_mod, "plan_mixed_impl", lambda *a, **k: "ell")
    cfg = SGDConfig(learning_rate=0.4, max_epochs=2, tol=0)

    def fit(W):
        return sgd_mod.sgd_fit_outofcore(
            logistic_loss, lambda: DataCacheReader(cache, batch_rows=640),
            num_features=d, config=cfg, dense_key="d", indices_key="c",
            steps_per_dispatch=W)

    s1, log1 = fit(1)
    s4, log4 = fit(4)
    assert s1.planned_impl == "ell-stream"
    np.testing.assert_array_equal(s4.coefficients, s1.coefficients)
    np.testing.assert_array_equal(log4, log1)


# ------------------------------------------------------------ widedeep


def _wd_cache(tmp_path, n=500):
    rng = np.random.default_rng(5)
    dense = rng.normal(size=(n, 3)).astype(np.float32)
    cat = np.stack([rng.integers(0, 10, n),
                    rng.integers(0, 7, n)], axis=1).astype(np.int32)
    logits = dense[:, 0] + 0.3 * (cat[:, 0] % 3) - 0.5
    y = (logits > 0).astype(np.float32)
    cache = str(tmp_path / "wd")
    w = DataCacheWriter(cache, segment_rows=256)
    w.append({"denseFeatures": dense, "catFeatures": cat, "label": y})
    w.finish()
    return cache


@pytest.mark.parametrize("lazy", [False, True])
def test_widedeep_fit_outofcore_chunked_bitexact(tmp_path, lazy):
    """W in (1, 3, 8) on a 4-batch widedeep epoch (padded final chunk at
    both W=3 and W=8): params and loss logs are bit-identical — the
    masked scan freezes params AND optimizer state on dead steps."""
    from flink_ml_tpu.models.recommendation.widedeep import WideDeep

    cache = _wd_cache(tmp_path)   # 500 rows / 128 -> 4 batches

    def fit(W):
        est = (WideDeep().set_vocab_sizes([10, 7]).set_max_iter(4)
               .set_seed(0).set(WideDeep.LAZY_EMB_OPT, lazy))
        return est.fit_outofcore(
            lambda: DataCacheReader(cache, batch_rows=128),
            steps_per_dispatch=W)

    ref = fit(1)
    ref_leaves = jax.tree_util.tree_leaves(ref._params)
    for W in (3, 8):
        model = fit(W)
        leaves = jax.tree_util.tree_leaves(model._params)
        for a, b in zip(leaves, ref_leaves):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(model._loss_log, ref._loss_log)


# ----------------------------------------------------------------- gbt


def test_gbt_outofcore_chunked_matches_w1(tmp_path):
    """Chunked GBT passes (histogram/leaf/margin) are bit-exact vs W=1
    — padding batches carry zero grad/hess and are inert in every
    additive pass."""
    from flink_ml_tpu.models.common.gbt import (GBTConfig,
                                                train_forest_outofcore)

    rng = np.random.default_rng(3)
    n, d = 3000, 6
    X = rng.normal(size=(n, d))
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)

    def grad_hess(y, m):
        p = 1 / (1 + np.exp(-m))
        return p - y, np.maximum(p * (1 - p), 1e-12)

    def make_reader():
        def gen():
            for s in range(0, n, 640):
                yield {"features": X[s:s + 640], "label": y[s:s + 640]}
        return gen()

    forests = {}
    # batch_device_rows=256 -> 12 batches: W=8 runs 2 chunks (the second
    # ragged+padded) and W=3 runs 4, so the CROSS-chunk histogram
    # accumulation — the only place chunked math could diverge from
    # W=1 — is actually exercised, not just the single-chunk case
    for W in (1, 3, 8):
        cfg = GBTConfig(num_trees=3, max_depth=3, max_bins=16,
                        steps_per_dispatch=W)
        forests[W] = train_forest_outofcore(
            make_reader, grad_hess, 0.0, cfg,
            work_dir=str(tmp_path / f"gbt{W}"), batch_device_rows=256)
    for W in (3, 8):
        np.testing.assert_array_equal(forests[W].feature,
                                      forests[1].feature)
        np.testing.assert_array_equal(forests[W].threshold,
                                      forests[1].threshold)
        np.testing.assert_array_equal(forests[W].value, forests[1].value)


# ------------------------------------------------------- iterate() knob


def test_iterate_steps_per_dispatch_equivalence():
    """Hosted iterate with a termination vote + per-epoch outputs: any
    steps_per_dispatch lands on the same state, epoch count, and output
    log (the voting epoch's feedback is kept, exactly like W=1)."""
    from flink_ml_tpu.iteration import (IterationBodyResult,
                                        IterationConfig, iterate)

    def body(state, epoch, data):
        s = state + data
        return IterationBodyResult(feedback=s, outputs=s * 2,
                                   termination=s < 10)

    ref = None
    for W in (1, 3, 8):
        r = iterate(body, jnp.asarray(0.0), jnp.asarray(1.5),
                    config=IterationConfig(mode="hosted"), max_epochs=20,
                    steps_per_dispatch=W)
        got = (float(r.state), r.num_epochs,
               [float(o) for o in r.outputs],
               r.side["termination_reason"])
        if ref is None:
            ref = got
        assert got == ref, (W, got, ref)
    assert ref[1] == 7 and ref[3] == "criteria"


def test_iterate_chunked_listeners_fire_at_chunk_boundaries():
    from flink_ml_tpu.iteration import IterationConfig, iterate
    from flink_ml_tpu.iteration.body import FnListener

    seen = []
    r = iterate(lambda s, e: s + 1, jnp.asarray(0),
                config=IterationConfig(mode="hosted"), max_epochs=10,
                steps_per_dispatch=4,
                listeners=[FnListener(on_epoch=lambda e, c: seen.append(e))])
    assert int(r.state) == 10 and r.num_epochs == 10
    # chunk boundaries: epochs 0-3, 4-7, 8-9 -> last epoch of each chunk
    assert seen == [3, 7, 9]


# ------------------------------------------- prefetch pipeline health


def test_prefetch_puts_stay_concurrent_under_backpressure():
    """With the output queue full and one putter blocked emitting, the
    OTHER putters must keep completing device_puts (the flush no longer
    holds ``flush_lock`` across blocking queue puts): put count grows
    well past what a lock-serialized flush would allow while the
    consumer holds off."""
    n_batches = 12
    put_count = [0]
    lock = threading.Lock()

    def counting_put(batch, _sharding):
        with lock:
            put_count[0] += 1
        return jax.device_put(batch)

    it = prefetch_to_device(
        (np.full((2,), i, np.float32) for i in range(n_batches)),
        depth=1, workers=2, put_workers=2, put_fn=counting_put)
    first = next(it)    # consume one, then stall the consumer
    assert int(np.asarray(first)[0]) == 0
    # old behavior: the drainer blocks ON flush_lock with q full, the
    # second putter finishes ONE put then parks on the lock -> count
    # stalls around 4.  New behavior: putters keep registering and
    # pulling work; everything in fq range completes.
    deadline = time.time() + 10.0
    while put_count[0] < 6 and time.time() < deadline:
        time.sleep(0.01)
    assert put_count[0] >= 6, put_count[0]
    # stream still correct after the stall
    rest = [int(np.asarray(b)[0]) for b in it]
    assert rest == list(range(1, n_batches))


def test_prefetch_no_device_put_after_error():
    """Once an in-stream error entry is flushed, no further device_put
    is issued — the consumer will raise at that seq, so every later
    transfer would be wasted work."""
    put_seqs = []
    lock = threading.Lock()

    def counting_put(batch, _sharding):
        with lock:
            put_seqs.append(int(batch[0]))
        return jax.device_put(batch)

    def transform(i):
        if i == 0:
            raise ValueError("decode exploded at 0")
        time.sleep(0.3)   # later decodes finish AFTER the error flushes
        return np.full((2,), i, np.float32)

    with pytest.raises(ValueError, match="decode exploded"):
        list(prefetch_to_device(range(8), transform=transform,
                                workers=2, put_workers=2, depth=2,
                                put_fn=counting_put))
    # the error (seq 0) flushed before any slow decode completed, so the
    # putters saw the failed latch and skipped every transfer
    assert put_seqs == [], put_seqs


def test_prefetch_chunks_stack_pad_and_stats():
    """chunks=W yields (chunk, mask, n_valid) triples: stacked leaves,
    padded+masked final chunk, batch/chunk accounting in stats."""
    stats = PrefetchStats()
    batches = [np.full((4,), i, np.float32) for i in range(11)]
    out = list(prefetch_to_device(iter(batches), chunks=4, workers=2,
                                  put_workers=2, stats=stats))
    assert [o[2] for o in out] == [4, 4, 3]
    chunk, mask, n_valid = out[2]
    assert chunk.shape == (4, 4)
    np.testing.assert_array_equal(np.asarray(mask), [1, 1, 1, 0])
    # pad slot repeats the last real batch; masked consumers ignore it
    np.testing.assert_array_equal(np.asarray(chunk)[:3],
                                  np.stack(batches[8:]))
    assert stats.batches == 11 and stats.chunks == 3
    d = stats.as_dict()
    assert d["chunks"] == 3 and "chunk_assemble_s" in d


def test_prefetch_chunks_reject_put_fn():
    with pytest.raises(ValueError, match="chunks"):
        list(prefetch_to_device(iter([np.ones(2)]), chunks=2,
                                put_fn=lambda b, s: b))


# ------------------------------------------------- slow: chunk sweep


@pytest.mark.slow
def test_chunk_sweep_amortization(tmp_path):
    """Over W in (1, 2, 4, 8, 16): W=1 makes one dispatch a batch, W=8 at
    most a quarter of that, and the coefficients are bit-equal across the
    whole sweep."""
    cache = _lr_cache(tmp_path, "sweep", n=1 << 14, d=16, seed=9)
    cfg = SGDConfig(learning_rate=0.5, max_epochs=3, tol=0.0)
    n_batches = (1 << 14) // 512    # 32

    by_w = {}
    ref = None
    for W in (1, 2, 4, 8, 16):
        info = {}
        state, _ = sgd_fit_outofcore(
            logistic_loss, lambda: DataCacheReader(cache, batch_rows=512),
            num_features=16, config=cfg, steps_per_dispatch=W,
            cache_decoded=False, stream_info=info)
        by_w[W] = info["dispatches_per_epoch"][-1]
        if ref is None:
            ref = state.coefficients
        else:
            np.testing.assert_array_equal(state.coefficients, ref)
    assert by_w[1] == n_batches
    assert by_w[1] / by_w[8] >= 4.0
