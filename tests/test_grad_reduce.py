"""Gradient-reduction subsystem tests (parallel/grad_reduce.py): every
mode against a numpy single-program oracle on the 8-device CPU mesh, the
EF residual recursion, the hierarchical ICI x DCN composition, and the
bytes-on-wire accounting."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from flink_ml_tpu.parallel import grad_reduce as GR
from flink_ml_tpu.parallel.collectives import shard_map_fn
from flink_ml_tpu.parallel.grad_reduce import GradReduceConfig
from flink_ml_tpu.parallel.mesh import device_mesh


def _abstract(tree):
    """Hashable (structure, shapes, dtypes) signature of a pytree — what
    the compiled program actually depends on, given fixed config/mesh."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return treedef, tuple(
        (np.shape(l), np.result_type(l).name) for l in leaves)


# GradReduceConfig is a frozen dataclass and the compiled reducer is a
# pure function of (config, mesh, arg structure/shapes), so identical
# keys reuse one executable instead of re-tracing a fresh closure per
# call — results are bit-identical either way.
_JIT_CACHE = {}


def _run_reduce(grads_stack, config, axis_sizes, state=None):
    """Apply reduce_gradients once over a mesh of ``axis_sizes``;
    ``grads_stack`` leaves carry a leading participant dim covering every
    reduction axis.  Returns (reduced, new_state, per_device_reduced)."""
    mesh = device_mesh(axis_sizes)
    n_dev = int(np.prod(list(axis_sizes.values())))
    if state is None:
        grads_like = jax.tree_util.tree_map(lambda a: a[0], grads_stack)
        state = GR.init_state(config, grads_like, n_dev)
    dev_spec = P(tuple(axis_sizes.keys()))

    key = (config, tuple(sorted(axis_sizes.items())),
           _abstract(grads_stack), _abstract(state))
    fn = _JIT_CACHE.get(key)
    if fn is None:
        def body(g, st):
            g_l = jax.tree_util.tree_map(lambda a: a[0], g)
            red, new_st = GR.reduce_gradients(
                g_l, GR.squeeze_state(st), config)
            return (jax.tree_util.tree_map(lambda a: a[None], red),
                    GR.unsqueeze_state(new_st))

        fn = jax.jit(shard_map_fn(body, mesh, in_specs=(dev_spec, dev_spec),
                                  out_specs=(dev_spec, dev_spec)))
        _JIT_CACHE[key] = fn
    red, new_state = fn(grads_stack, state)
    red = jax.tree_util.tree_map(np.asarray, red)
    # the reduced gradient must come back replicated: every participant
    # holds the identical sum
    for leaf in jax.tree_util.tree_leaves(red):
        np.testing.assert_array_equal(leaf, np.broadcast_to(leaf[:1],
                                                            leaf.shape))
    return (jax.tree_util.tree_map(lambda a: a[0], red), new_state, red)


def _grads(n_dev=8, d=64, seed=0):
    rng = np.random.default_rng(seed)
    return {"w": jnp.asarray(rng.normal(size=(n_dev, d)).astype(np.float32)),
            "b": jnp.asarray(rng.normal(size=(n_dev,)).astype(np.float32))}


def _np_topk_contrib(acc, k):
    """One participant's EF top-k contribution: (sent dense, unsent)."""
    order = np.argsort(-np.abs(acc), kind="stable")[:k]
    sent = np.zeros_like(acc)
    sent[order] = acc[order]
    return sent, acc - sent


def test_config_validation():
    with pytest.raises(ValueError, match="mode"):
        GradReduceConfig(mode="fp4")
    with pytest.raises(ValueError, match="density"):
        GradReduceConfig(mode="topk", density=0.0)
    with pytest.raises(ValueError, match="block_size"):
        GradReduceConfig(mode="int8", block_size=0)
    with pytest.raises(ValueError, match="single ICI axis"):
        GradReduceConfig(axis=("a", "b"), dcn_axis="dcn")
    assert GR.reduction_axes(
        GradReduceConfig(axis="data", dcn_axis="dcn")) == ("dcn", "data")
    assert not GR.needs_state(GradReduceConfig())
    assert GR.needs_state(GradReduceConfig(mode="topk"))


def test_exact_matches_sum():
    g = _grads()
    red, state, _ = _run_reduce(g, GradReduceConfig(mode="exact"),
                                {"data": 8})
    assert state == {}
    np.testing.assert_allclose(red["w"], np.asarray(g["w"]).sum(0),
                               atol=1e-5)
    np.testing.assert_allclose(red["b"], np.asarray(g["b"]).sum(),
                               atol=1e-5)


def test_topk_matches_ef_oracle_over_steps():
    """Two reduction steps against a numpy EF-SGD oracle: step 1 sends each
    participant's top-k, step 2's accumulated gradient includes step 1's
    unsent residual."""
    cfg = GradReduceConfig(mode="topk", density=0.125)  # k = 8 of 64
    g1, g2 = _grads(seed=1), _grads(seed=2)
    n_dev, d = 8, 64
    k = GR._topk_k(d, cfg.density)

    red1, state1, _ = _run_reduce(g1, cfg, {"data": 8})
    res_np = np.zeros((n_dev, d), np.float32)
    exp1 = np.zeros(d, np.float32)
    for p in range(n_dev):
        sent, res_np[p] = _np_topk_contrib(np.asarray(g1["w"])[p], k)
        exp1 += sent
    np.testing.assert_allclose(red1["w"], exp1, atol=1e-5)
    np.testing.assert_allclose(np.asarray(state1["ef"]["w"]), res_np,
                               atol=1e-6)
    # scalar leaf: k=1 means the bias is effectively exact every step
    np.testing.assert_allclose(red1["b"], np.asarray(g1["b"]).sum(),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(state1["ef"]["b"]), 0.0,
                               atol=1e-7)

    red2, state2, _ = _run_reduce(g2, cfg, {"data": 8}, state=state1)
    exp2 = np.zeros(d, np.float32)
    for p in range(n_dev):
        sent, res_np[p] = _np_topk_contrib(
            np.asarray(g2["w"])[p] + res_np[p], k)
        exp2 += sent
    np.testing.assert_allclose(red2["w"], exp2, atol=1e-5)
    np.testing.assert_allclose(np.asarray(state2["ef"]["w"]), res_np,
                               atol=1e-6)


def test_topk_sent_plus_residual_is_lossless():
    """EF bookkeeping invariant: per participant, sent + unsent == the
    accumulated gradient exactly (nothing is dropped, only deferred)."""
    cfg = GradReduceConfig(mode="topk", density=0.1)
    g = _grads(seed=3)
    _, state, per_dev = _run_reduce(g, cfg, {"data": 8})
    # reconstruct each participant's sent part from the oracle and check
    # acc == sent + residual
    k = GR._topk_k(64, cfg.density)
    for p in range(8):
        acc = np.asarray(g["w"])[p]
        sent, _ = _np_topk_contrib(acc, k)
        np.testing.assert_allclose(
            sent + np.asarray(state["ef"]["w"])[p], acc, atol=1e-6)


def test_int8_bounded_error_and_determinism():
    cfg = GradReduceConfig(mode="int8", block_size=16, seed=7)
    g = _grads(seed=4)
    red, state, _ = _run_reduce(g, cfg, {"data": 8})
    exact = np.asarray(g["w"]).sum(0)
    # per participant the stochastic round is off by < 1 quantum
    # (scale = blockmax/127); the summed error is bounded by the sum of
    # the participants' block scales
    scales = (np.abs(np.asarray(g["w"]).reshape(8, -1, 16)).max(axis=2)
              / 127.0)
    bound = np.repeat(scales.sum(0), 16) * (1.0 + 1e-6)
    assert np.all(np.abs(red["w"] - exact) <= bound)
    # key advanced, and the same inputs + same state reproduce bit-identical
    red_again, _, _ = _run_reduce(g, cfg, {"data": 8})
    np.testing.assert_array_equal(red["w"], red_again["w"])
    assert not np.array_equal(np.asarray(state["key"]),
                              np.asarray(GR.init_state(cfg, None, 8)["key"]))


def test_hierarchical_exact_matches_flat():
    cfg = GradReduceConfig(mode="exact", axis="data", dcn_axis="dcn")
    g = _grads(seed=5, d=60)  # 60 does not divide the 4-wide ICI axis: pad
    red, _, _ = _run_reduce(g, cfg, {"dcn": 2, "data": 4})
    np.testing.assert_allclose(red["w"], np.asarray(g["w"]).sum(0),
                               atol=1e-5)


def test_hierarchical_topk_matches_shard_oracle():
    """Hierarchical EF top-k: the DCN hop compresses the ICI-summed shard;
    the oracle reduces each dcn member's 4-device ICI group exactly, then
    applies per-member top-k with shard-domain residuals."""
    cfg = GradReduceConfig(mode="topk", density=0.25, axis="data",
                           dcn_axis="dcn")
    D, I, d = 2, 4, 64
    shard_len = d // I
    k = GR._topk_k(shard_len, cfg.density)
    g1, g2 = _grads(n_dev=D * I, seed=6), _grads(n_dev=D * I, seed=7)

    res = np.zeros((D, d), np.float32)  # per-dcn-member shard residuals

    def oracle(g_np):
        out = np.zeros(d, np.float32)
        for m in range(D):
            ici_sum = g_np[m * I:(m + 1) * I].sum(0)
            for i in range(I):
                sl = slice(i * shard_len, (i + 1) * shard_len)
                acc = ici_sum[sl] + res[m, sl]
                sent, unsent = _np_topk_contrib(acc, k)
                out[sl] += sent
                res[m, sl] = unsent
        return out

    red1, state1, _ = _run_reduce(g1, cfg, {"dcn": 2, "data": 4})
    np.testing.assert_allclose(red1["w"], oracle(np.asarray(g1["w"])),
                               atol=1e-5)
    # the carried residual embeds each device's shard at its own slice
    ef = np.asarray(state1["ef"]["w"]).reshape(D, I, d)
    for m in range(D):
        for i in range(I):
            sl = slice(i * shard_len, (i + 1) * shard_len)
            np.testing.assert_allclose(ef[m, i][sl], res[m, sl], atol=1e-6)
            outside = np.delete(ef[m, i], np.r_[sl])
            np.testing.assert_allclose(outside, 0.0, atol=1e-7)

    red2, _, _ = _run_reduce(g2, cfg, {"dcn": 2, "data": 4}, state=state1)
    np.testing.assert_allclose(red2["w"], oracle(np.asarray(g2["w"])),
                               atol=1e-5)


def test_hierarchical_int8_bounded_error():
    cfg = GradReduceConfig(mode="int8", block_size=8, axis="data",
                           dcn_axis="dcn")
    g = _grads(seed=8)
    red, _, _ = _run_reduce(g, cfg, {"dcn": 2, "data": 4})
    exact = np.asarray(g["w"]).sum(0)
    # only the 2-member DCN hop quantizes (the ICI reduce is exact), so
    # the error is bounded by 2 quanta of the shard block scales; bound
    # loosely by 2 * max|exact ici sum| / 127 per element
    ici = np.asarray(g["w"]).reshape(2, 4, -1).sum(1)
    bound = 2 * np.abs(ici).max() / 127.0 + 1e-6
    assert np.abs(red["w"] - exact).max() <= bound


def test_payload_bytes_accounting():
    like = {"w": np.zeros((1 << 20,), np.float32),
            "b": np.zeros((), np.float32)}
    exact = GR.payload_bytes(like, GradReduceConfig())
    assert exact["dense_bytes"] == exact["compressed_bytes"] == \
        4 * ((1 << 20) + 1)
    topk = GR.payload_bytes(like, GradReduceConfig(mode="topk", density=0.1))
    # floor(k) makes 5x the LOWER bound at density 0.1 (idx + val = 8 B)
    assert topk["compression_ratio"] >= 5.0
    assert topk["compressed_bytes"] == 8 * ((1 << 20) // 10 + 1)
    q = GR.payload_bytes(like, GradReduceConfig(mode="int8", block_size=256))
    assert 3.5 <= q["compression_ratio"] <= 4.0
    hier = GR.payload_bytes(
        like, GradReduceConfig(mode="topk", density=0.1, dcn_axis="dcn"),
        ici_size=4)
    # the compressed hop is the 1/4-sized ICI shard; the exact ICI bytes
    # ride separately
    assert hier["dense_bytes"] == 4 * ((1 << 20) // 4 + 1)
    assert hier["compression_ratio"] >= 5.0
    assert hier["ici_bytes"] > 0


# ------------------------------------------------------------- sgd adoption


def _lr_problem(n=512, d=64, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (X @ rng.normal(size=d) > 0).astype(np.float64)
    return X, y


def test_sgd_exact_mode_bit_identical():
    """Acceptance: mode='exact' (and config=None) keep the pre-reducer
    lax.psum path bit-for-bit — no behavior change unless opted in."""
    from flink_ml_tpu.models.common.losses import LOSSES
    from flink_ml_tpu.models.common.sgd import SGDConfig, sgd_fit

    X, y = _lr_problem()
    mesh = device_mesh({"data": 8})
    kw = dict(learning_rate=0.5, max_epochs=20, tol=0, global_batch_size=64)
    s0, log0 = sgd_fit(LOSSES["logistic"], X, y, None, SGDConfig(**kw), mesh)
    s1, log1 = sgd_fit(LOSSES["logistic"], X, y, None,
                       SGDConfig(**kw, grad_reduce=GradReduceConfig()), mesh)
    np.testing.assert_array_equal(s0.coefficients, s1.coefficients)
    assert s0.intercept == s1.intercept
    np.testing.assert_array_equal(log0, log1)


def test_sgd_topk_ef_density01_converges_to_dense():
    """Acceptance: EF top-k at density 0.1 lands within 1e-3 of the dense
    loss on a convex logistic problem over the 8-device mesh."""
    from flink_ml_tpu.models.common.losses import LOSSES
    from flink_ml_tpu.models.common.sgd import SGDConfig, sgd_fit

    X, y = _lr_problem()
    mesh = device_mesh({"data": 8})
    kw = dict(learning_rate=0.2, max_epochs=200, tol=0,
              global_batch_size=64)
    _, log_dense = sgd_fit(LOSSES["logistic"], X, y, None, SGDConfig(**kw),
                           mesh)
    state, log_topk = sgd_fit(
        LOSSES["logistic"], X, y, None,
        SGDConfig(**kw, grad_reduce=GradReduceConfig(mode="topk",
                                                     density=0.1)), mesh)
    assert abs(log_dense[-1] - log_topk[-1]) < 1e-3, (
        f"dense {log_dense[-1]} vs topk {log_topk[-1]}")
    assert np.isfinite(state.coefficients).all()


def test_sgd_int8_close_to_dense():
    from flink_ml_tpu.models.common.losses import LOSSES
    from flink_ml_tpu.models.common.sgd import SGDConfig, sgd_fit

    X, y = _lr_problem()
    mesh = device_mesh({"data": 8})
    kw = dict(learning_rate=0.5, max_epochs=40, tol=0, global_batch_size=64)
    _, log_dense = sgd_fit(LOSSES["logistic"], X, y, None, SGDConfig(**kw),
                           mesh)
    _, log_q = sgd_fit(
        LOSSES["logistic"], X, y, None,
        SGDConfig(**kw, grad_reduce=GradReduceConfig(mode="int8",
                                                     block_size=32)), mesh)
    assert abs(log_dense[-1] - log_q[-1]) < 1e-3


def test_sgd_hierarchical_on_hybrid_mesh():
    """The fused fit runs the two-tier reduce on a hybrid mesh: batch
    sharded over dcn x data, compression only on the dcn hop."""
    from flink_ml_tpu.models.common.losses import LOSSES
    from flink_ml_tpu.models.common.sgd import SGDConfig, sgd_fit
    from flink_ml_tpu.parallel import distributed as dist

    X, y = _lr_problem()
    hmesh = dist.hybrid_mesh({"data": 8})
    kw = dict(learning_rate=0.5, max_epochs=40, tol=0, global_batch_size=64)
    _, log_dense = sgd_fit(LOSSES["logistic"], X, y, None, SGDConfig(**kw))
    state, log_h = sgd_fit(
        LOSSES["logistic"], X, y, None,
        SGDConfig(**kw, grad_reduce=GradReduceConfig(
            mode="topk", density=0.1, axis="data", dcn_axis="dcn")), hmesh)
    assert np.isfinite(state.coefficients).all()
    assert log_h[-1] < log_h[0]
    assert abs(log_dense[-1] - log_h[-1]) < 5e-2


def test_sgd_params_matrix_weight_compressed():
    """sgd_fit_params with a (d, C) weight (the softmax family's shape)
    routes through the same compressed update."""
    import jax.numpy as jnp

    from flink_ml_tpu.models.common.sgd import SGDConfig, sgd_fit_params

    rng = np.random.default_rng(3)
    n, d, C = 256, 16, 3
    X = rng.normal(size=(n, d)).astype(np.float32)
    labels = rng.integers(0, C, size=n).astype(np.float64)

    def softmax_loss(scores, yb, wb):
        y = jax.nn.one_hot(yb.astype(jnp.int32), C)
        logp = jax.nn.log_softmax(scores, axis=-1)
        per_row = -jnp.sum(y * logp, axis=-1)
        return jnp.sum(per_row * wb) / jnp.maximum(jnp.sum(wb), 1e-12)

    mesh = device_mesh({"data": 8})
    init = {"w": jnp.zeros((d, C), jnp.float32),
            "b": jnp.zeros((C,), jnp.float32)}
    kw = dict(learning_rate=0.5, max_epochs=30, tol=0, global_batch_size=64)
    p_dense, log_dense = sgd_fit_params(
        softmax_loss, X, labels, None, SGDConfig(**kw), mesh,
        init_params=dict(init))
    p_topk, log_topk = sgd_fit_params(
        softmax_loss, X, labels, None,
        SGDConfig(**kw, grad_reduce=GradReduceConfig(mode="topk",
                                                     density=0.25)),
        mesh, init_params=dict(init))
    assert "_gr" not in p_topk
    assert log_topk[-1] < log_topk[0]
    assert abs(log_dense[-1] - log_topk[-1]) < 5e-2


# -------------------------------------------------------- out-of-core + EF


def _stream_cache(tmp_path, n_seg=3, d=8, seed=7):
    from flink_ml_tpu.data.datacache import DataCacheWriter

    rng = np.random.default_rng(seed)
    true_w = rng.normal(size=(d,))
    cache = str(tmp_path / "cache")
    writer = DataCacheWriter(cache, segment_rows=512)
    for _ in range(n_seg):
        X = rng.normal(size=(512, d)).astype(np.float32)
        writer.append({"features": X,
                       "label": (X @ true_w > 0).astype(np.float32)})
    writer.finish()
    return cache


class _FailAfter:
    """Reader wrapper that dies after N read_batch calls across the run."""

    counter = 0

    def __init__(self, inner, fail_after):
        self._inner = inner
        self._fail_after = fail_after

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def __iter__(self):
        while True:
            _FailAfter.counter += 1
            if _FailAfter.counter > self._fail_after:
                raise RuntimeError("injected mid-epoch failure")
            b = self._inner.read_batch()
            if b is None:
                return
            yield b


def test_outofcore_ef_residual_checkpoint_roundtrip_exact(tmp_path):
    """Acceptance: the EF residual rides the donated scan carry AND the
    mid-epoch checkpoint — crash + resume reproduces the uninterrupted
    compressed run bit-for-bit (impossible if the residual were dropped
    or re-zeroed on restore)."""
    from flink_ml_tpu.data.datacache import DataCacheReader
    from flink_ml_tpu.iteration.checkpoint import CheckpointConfig
    from flink_ml_tpu.models.common.losses import logistic_loss
    from flink_ml_tpu.models.common.sgd import SGDConfig, sgd_fit_outofcore

    cache = _stream_cache(tmp_path)
    cfg = SGDConfig(learning_rate=0.4, max_epochs=4, tol=0.0,
                    grad_reduce=GradReduceConfig(mode="topk", density=0.1))

    def reader():
        return DataCacheReader(cache, batch_rows=256)

    ref_state, ref_log = sgd_fit_outofcore(
        logistic_loss, reader, num_features=8, config=cfg)
    assert ref_state.planned_impl == "dense-stream-reduced"

    ck = CheckpointConfig(str(tmp_path / "ck"), max_to_keep=3)
    _FailAfter.counter = 0
    with pytest.raises(RuntimeError, match="injected"):
        sgd_fit_outofcore(
            logistic_loss, lambda: _FailAfter(reader(), 15),
            num_features=8, config=cfg, cache_decoded=False,
            checkpoint=ck, checkpoint_every_steps=2)
    resumed_state, resumed_log = sgd_fit_outofcore(
        logistic_loss, reader, num_features=8, config=cfg,
        checkpoint=ck, checkpoint_every_steps=2, resume=True)
    np.testing.assert_array_equal(resumed_state.coefficients,
                                  ref_state.coefficients)
    assert resumed_state.intercept == ref_state.intercept
    np.testing.assert_array_equal(resumed_log, ref_log)


def test_outofcore_reduced_chunked_bit_exact_vs_w1(tmp_path):
    """steps_per_dispatch W=1 vs W=8 stay bit-exact with the reducer state
    in the carry (the masked dead steps must freeze the residual too)."""
    from flink_ml_tpu.data.datacache import DataCacheReader
    from flink_ml_tpu.models.common.losses import logistic_loss
    from flink_ml_tpu.models.common.sgd import SGDConfig, sgd_fit_outofcore

    cache = _stream_cache(tmp_path)
    cfg = SGDConfig(learning_rate=0.4, max_epochs=2, tol=0.0,
                    grad_reduce=GradReduceConfig(mode="topk", density=0.1))

    def reader():
        return DataCacheReader(cache, batch_rows=256)

    s1, log1 = sgd_fit_outofcore(logistic_loss, reader, num_features=8,
                                 config=cfg, steps_per_dispatch=1)
    s8, log8 = sgd_fit_outofcore(logistic_loss, reader, num_features=8,
                                 config=cfg, steps_per_dispatch=8)
    np.testing.assert_array_equal(s1.coefficients, s8.coefficients)
    # a step's loss is a float32 reduction over its batch, and XLA:CPU
    # (jax 0.9.0) does not order it the same in the program for a chunk
    # of 1 as in the one for a chunk of 8 (found: 6.5e-8 relative), so the
    # loss log is held to 1e-6; the parameters are bit-equal
    np.testing.assert_allclose(log1, log8, rtol=1e-6, atol=0)


def test_outofcore_rejects_compressed_sparse_layouts(tmp_path):
    from flink_ml_tpu.models.common.losses import logistic_loss
    from flink_ml_tpu.models.common.sgd import SGDConfig, sgd_fit_outofcore

    cfg = SGDConfig(grad_reduce=GradReduceConfig(mode="topk"))
    with pytest.raises(ValueError, match="sparse by construction"):
        sgd_fit_outofcore(
            logistic_loss, lambda: iter([]), num_features=8, config=cfg,
            dense_key="fd", indices_key="fi")


# ------------------------------------------------------- widedeep adoption


def test_widedeep_sharded_compressed_matches_exact_at_density_1():
    """density=1.0 sends every entry, so the compressed dp x tp step must
    reproduce the implicit-GSPMD step allclose — a full-model oracle for
    the manual data axis + auto model axis wiring."""
    from flink_ml_tpu.models.recommendation.widedeep import (
        build_sharded_train_step)

    mesh = device_mesh({"data": 4, "model": 2})
    vocab = [16, 12]
    rng = np.random.default_rng(0)
    B = 32
    dense = rng.normal(size=(B, 3)).astype(np.float32)
    cat = (np.stack([rng.integers(0, v, size=B) for v in vocab], 1)
           + np.asarray([0, 16])).astype(np.int32)
    labels = rng.integers(0, 2, size=B).astype(np.float32)
    mask = np.ones(B, np.float32)

    step_e, p_e, _, os_e, shard_e = build_sharded_train_step(
        mesh, 3, vocab, 8, (16, 8))
    batch = shard_e(dense, cat, labels, mask)
    for _ in range(3):
        p_e, os_e, loss_e = step_e(p_e, os_e, *batch)

    step_c, p_c, _, os_c, shard_c, grs = build_sharded_train_step(
        mesh, 3, vocab, 8, (16, 8),
        grad_reduce=GradReduceConfig(mode="topk", density=1.0))
    batch_c = shard_c(dense, cat, labels, mask)
    for _ in range(3):
        p_c, os_c, grs, loss_c = step_c(p_c, os_c, grs, *batch_c)
    np.testing.assert_allclose(float(loss_e), float(loss_c), rtol=1e-5,
                               atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(p_e)),
                    jax.tree_util.tree_leaves(jax.device_get(p_c))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_widedeep_sharded_topk_trains():
    from flink_ml_tpu.models.recommendation.widedeep import (
        build_sharded_train_step)

    mesh = device_mesh({"data": 4, "model": 2})
    vocab = [16, 12]
    rng = np.random.default_rng(1)
    B = 32
    dense = rng.normal(size=(B, 3)).astype(np.float32)
    cat = (np.stack([rng.integers(0, v, size=B) for v in vocab], 1)
           + np.asarray([0, 16])).astype(np.int32)
    labels = rng.integers(0, 2, size=B).astype(np.float32)
    mask = np.ones(B, np.float32)

    step, p, _, os_, shard, grs = build_sharded_train_step(
        mesh, 3, vocab, 8, (16, 8),
        grad_reduce=GradReduceConfig(mode="topk", density=0.1))
    batch = shard(dense, cat, labels, mask)
    losses = []
    for _ in range(10):
        p, os_, grs, loss = step(p, os_, grs, *batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    # the EF residual is live: after a compressed step some mass is
    # carried instead of applied
    assert any(float(np.abs(np.asarray(leaf)).max()) > 0
               for leaf in jax.tree_util.tree_leaves(
                   jax.device_get(grs)["ef"]))


# ------------------------------------------ r11: buckets / adaptive / overlap


def test_r11_config_validation():
    with pytest.raises(ValueError, match="bucket_count"):
        GradReduceConfig(bucket_count=-1)
    with pytest.raises(ValueError, match="topk-family"):
        GradReduceConfig(mode="int8", adaptive=True)
    with pytest.raises(ValueError, match="ladder rungs"):
        GradReduceConfig(mode="topk", adaptive=True,
                         density_ladder=(0.1, "fp4"))
    with pytest.raises(ValueError, match="not in"):
        GradReduceConfig(mode="topk", adaptive=True,
                         density_ladder=(0.1, 1.5))
    with pytest.raises(ValueError, match="requires adaptive"):
        GradReduceConfig(mode="topk", density_ladder=(0.1,))
    # the exact-mode fence: overlap is ignored, not an error
    assert not GR.wants_overlap(GradReduceConfig(mode="exact", overlap=True))
    assert GR.wants_overlap(GradReduceConfig(mode="topk", overlap=True))
    assert not GR.wants_overlap(None)
    assert GR.effective_ladder(
        GradReduceConfig(mode="topk", density=0.2, adaptive=True)) == \
        (0.05, 0.2, "exact")


def test_bucket_plan_balanced_and_covering():
    like = {"w": np.zeros((1000,), np.float32),
            "b": np.zeros((), np.float32),
            "v": np.zeros((7, 3), np.float32)}
    cfg = GradReduceConfig(mode="topk", bucket_count=8)
    plan = GR.plan_buckets(like, cfg)
    sizes = plan.bucket_sizes
    assert len(sizes) == 8 and sum(sizes) == plan.total == 1022
    assert max(sizes) - min(sizes) <= 1          # size-balanced
    # ranges tile [0, total) exactly, in order
    pos = 0
    for lo, hi in plan.ranges:
        assert lo == pos and hi > lo
        pos = hi
    assert pos == plan.total
    # every bucket knows exactly the leaves it overlaps
    for (lo, hi), leaves in zip(plan.ranges, plan.bucket_leaves):
        for li in leaves:
            assert plan.leaf_offsets[li] < hi and \
                plan.leaf_offsets[li + 1] > lo
    # bucket_count=0 (adaptive-only) degrades to one bucket per leaf
    # (sorted-dict-key leaf order: b (1), v (21), w (1000))
    per_leaf = GR.plan_buckets(like, GradReduceConfig(
        mode="topk", adaptive=True))
    assert per_leaf.ranges == ((0, 1), (1, 22), (22, 1022))


def test_exact_bucketed_bit_identical():
    """Acceptance: exact mode with bucketing enabled is bit-identical to
    the legacy blocking psum path (psum is elementwise — the transport
    cut cannot change a single bit)."""
    g = _grads(seed=9, d=100)
    red0, _, _ = _run_reduce(g, GradReduceConfig(mode="exact"), {"data": 8})
    red1, _, _ = _run_reduce(g, GradReduceConfig(mode="exact",
                                                 bucket_count=4),
                             {"data": 8})
    np.testing.assert_array_equal(red0["w"], red1["w"])
    np.testing.assert_array_equal(red0["b"], red1["b"])


def test_sgd_exact_bucketed_fit_bit_identical():
    """The full-fit A/B of the same fence: an exact bucketed fit equals
    the no-config legacy fit bit-for-bit."""
    from flink_ml_tpu.models.common.losses import LOSSES
    from flink_ml_tpu.models.common.sgd import SGDConfig, sgd_fit

    X, y = _lr_problem()
    mesh = device_mesh({"data": 8})
    kw = dict(learning_rate=0.5, max_epochs=20, tol=0, global_batch_size=64)
    s0, log0 = sgd_fit(LOSSES["logistic"], X, y, None, SGDConfig(**kw), mesh)
    s1, log1 = sgd_fit(
        LOSSES["logistic"], X, y, None,
        SGDConfig(**kw, grad_reduce=GradReduceConfig(
            mode="exact", bucket_count=8, overlap=True)), mesh)
    np.testing.assert_array_equal(s0.coefficients, s1.coefficients)
    assert s0.intercept == s1.intercept
    np.testing.assert_array_equal(log0, log1)


def test_topk_bucketed_ef_lossless():
    """EF bookkeeping invariant survives the bucket transport: summed
    over participants, gradient mass == reduced + carried residual
    (nothing dropped at bucket boundaries, only deferred)."""
    cfg = GradReduceConfig(mode="topk", density=0.1, bucket_count=4)
    g = _grads(seed=10, d=100)
    red, state, _ = _run_reduce(g, cfg, {"data": 8})
    total_grad = np.asarray(g["w"]).sum(0)
    total_res = np.asarray(state["ef"]["w"]).sum(0)
    np.testing.assert_allclose(red["w"] + total_res, total_grad, atol=1e-5)


def test_topk_bucketed_selects_per_bucket():
    """Bucketed top-k selects k per BUCKET: gradient mass concentrated in
    one bucket's span still leaves every other bucket sending its own
    top-k (the SparCML variable-rate posture the planner feeds)."""
    cfg = GradReduceConfig(mode="topk", density=0.25, bucket_count=2)
    w = np.zeros((8, 64), np.float32)
    w[:, :32] = 100.0        # bucket 0 span dominates
    w[:, 32:] = 0.001        # per-leaf topk would never send these
    g = {"w": jnp.asarray(w)}
    red, _, _ = _run_reduce(g, cfg, {"data": 8})
    # bucket 1 (elements 32:64) sent its own top-k despite the tiny values
    assert np.abs(red["w"][32:]).max() > 0


def test_adaptive_rung_follows_residual_ratio():
    """The policy loop: diffuse gradients (top-k residual dominates)
    climb the ladder toward exact; spiky gradients (residual ~ 0)
    descend toward the cheap rung.  Selection only moves at window
    boundaries."""
    cfg = GradReduceConfig(mode="topk", density=0.1, adaptive=True,
                           adaptive_window=2)
    rung0 = GR._initial_rung(cfg)

    # diffuse: random normal at density 0.1 keeps ~90% of the mass unsent
    state = None
    for seed in range(6):
        gi = _grads(seed=100 + seed, d=256)
        _, state, _ = _run_reduce(gi, cfg, {"data": 8}, state=state)
    rung = np.asarray(state["rung"])[0]
    assert rung[1] > rung0            # the dense leaf climbed
    assert int(np.asarray(state["tick"])[0]) == 6

    # spiky: one huge coordinate per participant — top-k captures
    # essentially everything, ratio ~ 0, the leaf descends
    spiky = np.full((8, 256), 1e-6, np.float32)
    spiky[:, 3] = 1e3
    g = {"w": jnp.asarray(spiky), "b": jnp.asarray(np.ones(8, np.float32))}
    state = None
    for _ in range(6):
        _, state, _ = _run_reduce(g, cfg, {"data": 8}, state=state)
    rung = np.asarray(state["rung"])[0]
    assert rung[1] < rung0


def test_adaptive_exact_rung_clears_residual():
    """A leaf pinned at the exact rung reduces exactly AND consumes the
    whole accumulated residual (unsent == 0)."""
    cfg = GradReduceConfig(mode="topk", density=0.1, adaptive=True,
                           density_ladder=("exact",))
    g = _grads(seed=12)
    red, state, _ = _run_reduce(g, cfg, {"data": 8})
    np.testing.assert_allclose(red["w"], np.asarray(g["w"]).sum(0),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(state["ef"]["w"]), 0.0, atol=1e-7)


def test_adaptive_int8_rung_runs():
    cfg = GradReduceConfig(mode="topk", density=0.1, adaptive=True,
                           density_ladder=("int8",), block_size=16)
    g = _grads(seed=13)
    red, state, _ = _run_reduce(g, cfg, {"data": 8})
    exact = np.asarray(g["w"]).sum(0)
    scales = (np.abs(np.asarray(g["w"]).reshape(8, -1, 16)).max(axis=2)
              / 127.0)
    bound = np.repeat(scales.sum(0), 16) * (1.0 + 1e-6)
    assert np.all(np.abs(red["w"] - exact) <= bound)
    np.testing.assert_allclose(np.asarray(state["ef"]["w"]), 0.0, atol=1e-7)


def test_pipelined_reduce_is_one_step_stale():
    """pipelined_reduce returns the reduction of the PREVIOUS call's
    gradient: call 1 reduces the zeros-initialized pending (a no-op),
    call 2 reduces call 1's gradient."""
    cfg = GradReduceConfig(mode="topk", density=1.0, overlap=True)
    mesh = device_mesh({"data": 8})
    g1, g2 = _grads(seed=14), _grads(seed=15)
    state = GR.init_state(cfg, jax.tree_util.tree_map(lambda a: a[0], g1), 8)
    dev_spec = P("data")

    def body(g, st):
        g_l = jax.tree_util.tree_map(lambda a: a[0], g)
        red, new_st = GR.pipelined_reduce(g_l, GR.squeeze_state(st), cfg)
        return (jax.tree_util.tree_map(lambda a: a[None], red),
                GR.unsqueeze_state(new_st))

    fn = jax.jit(shard_map_fn(body, mesh, in_specs=(dev_spec, dev_spec),
                              out_specs=(dev_spec, dev_spec)))
    red1, state = fn(g1, state)
    np.testing.assert_allclose(np.asarray(red1["w"])[0], 0.0, atol=1e-7)
    red2, state = fn(g2, state)
    np.testing.assert_allclose(np.asarray(red2["w"])[0],
                               np.asarray(g1["w"]).sum(0), atol=1e-5)
    # the pending buffer now carries g2, and drain_pending recovers it
    # (+ the empty residual) exactly
    drain = GR.drain_pending(jax.device_get(state))
    np.testing.assert_allclose(drain["w"], np.asarray(g2["w"]).sum(0),
                               atol=1e-5)


def test_sgd_overlap_topk_converges_to_dense():
    """Acceptance: one-step-stale bucketed EF top-k at density 0.1 lands
    within 1e-3 of the dense loss (the PR 3 tolerance) — the residual
    absorbs the staleness like it absorbs the sparsification."""
    from flink_ml_tpu.models.common.losses import LOSSES
    from flink_ml_tpu.models.common.sgd import SGDConfig, sgd_fit

    X, y = _lr_problem()
    mesh = device_mesh({"data": 8})
    kw = dict(learning_rate=0.2, max_epochs=200, tol=0,
              global_batch_size=64)
    _, log_dense = sgd_fit(LOSSES["logistic"], X, y, None, SGDConfig(**kw),
                           mesh)
    state, log_ov = sgd_fit(
        LOSSES["logistic"], X, y, None,
        SGDConfig(**kw, grad_reduce=GradReduceConfig(
            mode="topk", density=0.1, bucket_count=4, overlap=True)), mesh)
    assert abs(log_dense[-1] - log_ov[-1]) < 1e-3, (
        f"dense {log_dense[-1]} vs overlapped {log_ov[-1]}")
    assert np.isfinite(state.coefficients).all()


def test_outofcore_overlap_adaptive_chunked_bit_exact_vs_w1(tmp_path):
    """W=1 vs W=8 stay bit-exact with the whole r11 state — pending
    buffer, rung/EMA/tick, EF residual — riding the donated carry (the
    masked dead steps must freeze ALL of it)."""
    from flink_ml_tpu.data.datacache import DataCacheReader
    from flink_ml_tpu.models.common.losses import logistic_loss
    from flink_ml_tpu.models.common.sgd import SGDConfig, sgd_fit_outofcore

    cache = _stream_cache(tmp_path)
    cfg = SGDConfig(learning_rate=0.4, max_epochs=2, tol=0.0,
                    grad_reduce=GradReduceConfig(
                        mode="topk", density=0.25, bucket_count=3,
                        overlap=True, adaptive=True, adaptive_window=2))

    def reader():
        return DataCacheReader(cache, batch_rows=256)

    s1, log1 = sgd_fit_outofcore(logistic_loss, reader, num_features=8,
                                 config=cfg, steps_per_dispatch=1)
    s8, log8 = sgd_fit_outofcore(logistic_loss, reader, num_features=8,
                                 config=cfg, steps_per_dispatch=8)
    assert s1.planned_impl == "dense-stream-reduced"
    np.testing.assert_array_equal(s1.coefficients, s8.coefficients)
    np.testing.assert_array_equal(log1, log8)


def test_outofcore_overlap_checkpoint_roundtrip_exact(tmp_path):
    """Crash + resume with overlap + adaptive + buckets reproduces the
    uninterrupted run bit-for-bit: the pending gradient and the policy
    state ride the checkpoint cut, and the fit-end drain applies the
    same mass either way."""
    from flink_ml_tpu.data.datacache import DataCacheReader
    from flink_ml_tpu.iteration.checkpoint import CheckpointConfig
    from flink_ml_tpu.models.common.losses import logistic_loss
    from flink_ml_tpu.models.common.sgd import SGDConfig, sgd_fit_outofcore

    cache = _stream_cache(tmp_path)
    cfg = SGDConfig(learning_rate=0.4, max_epochs=4, tol=0.0,
                    grad_reduce=GradReduceConfig(
                        mode="topk", density=0.25, bucket_count=3,
                        overlap=True, adaptive=True, adaptive_window=3))

    def reader():
        return DataCacheReader(cache, batch_rows=256)

    ref_state, ref_log = sgd_fit_outofcore(
        logistic_loss, reader, num_features=8, config=cfg)

    ck = CheckpointConfig(str(tmp_path / "ck"), max_to_keep=3)
    _FailAfter.counter = 0
    with pytest.raises(RuntimeError, match="injected"):
        sgd_fit_outofcore(
            logistic_loss, lambda: _FailAfter(reader(), 15),
            num_features=8, config=cfg, cache_decoded=False,
            checkpoint=ck, checkpoint_every_steps=2)
    resumed_state, resumed_log = sgd_fit_outofcore(
        logistic_loss, reader, num_features=8, config=cfg,
        checkpoint=ck, checkpoint_every_steps=2, resume=True)
    np.testing.assert_array_equal(resumed_state.coefficients,
                                  ref_state.coefficients)
    assert resumed_state.intercept == ref_state.intercept
    np.testing.assert_array_equal(resumed_log, ref_log)


def test_widedeep_bucketed_density1_matches_exact():
    """Bucketed density-1.0 top-k sends every entry, so the bucket
    transport must still reproduce the implicit-GSPMD step allclose."""
    from flink_ml_tpu.models.recommendation.widedeep import (
        build_sharded_train_step)

    mesh = device_mesh({"data": 4, "model": 2})
    vocab = [16, 12]
    rng = np.random.default_rng(2)
    B = 32
    dense = rng.normal(size=(B, 3)).astype(np.float32)
    cat = (np.stack([rng.integers(0, v, size=B) for v in vocab], 1)
           + np.asarray([0, 16])).astype(np.int32)
    labels = rng.integers(0, 2, size=B).astype(np.float32)
    mask = np.ones(B, np.float32)

    step_e, p_e, _, os_e, shard_e = build_sharded_train_step(
        mesh, 3, vocab, 8, (16, 8))
    batch = shard_e(dense, cat, labels, mask)
    for _ in range(3):
        p_e, os_e, loss_e = step_e(p_e, os_e, *batch)

    step_c, p_c, _, os_c, shard_c, grs = build_sharded_train_step(
        mesh, 3, vocab, 8, (16, 8),
        grad_reduce=GradReduceConfig(mode="topk", density=1.0,
                                     bucket_count=3))
    batch_c = shard_c(dense, cat, labels, mask)
    for _ in range(3):
        p_c, os_c, grs, loss_c = step_c(p_c, os_c, grs, *batch_c)
    np.testing.assert_allclose(float(loss_e), float(loss_c), rtol=1e-5,
                               atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(p_e)),
                    jax.tree_util.tree_leaves(jax.device_get(p_c))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_widedeep_overlap_adaptive_trains():
    from flink_ml_tpu.models.recommendation.widedeep import (
        build_sharded_train_step)

    mesh = device_mesh({"data": 4, "model": 2})
    vocab = [16, 12]
    rng = np.random.default_rng(3)
    B = 32
    dense = rng.normal(size=(B, 3)).astype(np.float32)
    cat = (np.stack([rng.integers(0, v, size=B) for v in vocab], 1)
           + np.asarray([0, 16])).astype(np.int32)
    labels = rng.integers(0, 2, size=B).astype(np.float32)
    mask = np.ones(B, np.float32)

    step, p, _, os_, shard, grs = build_sharded_train_step(
        mesh, 3, vocab, 8, (16, 8),
        grad_reduce=GradReduceConfig(mode="topk", density=0.1,
                                     bucket_count=2, overlap=True,
                                     adaptive=True, adaptive_window=3))
    batch = shard(dense, cat, labels, mask)
    losses = []
    for _ in range(10):
        p, os_, grs, loss = step(p, os_, grs, *batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    assert int(np.asarray(jax.device_get(grs)["tick"])[0]) == 10


def test_payload_bytes_fabric_split_and_buckets():
    like = {"w": np.zeros((1 << 20,), np.float32),
            "b": np.zeros((), np.float32)}
    # flat: total == compressed (one fabric)
    flat = GR.payload_bytes(like, GradReduceConfig(mode="topk",
                                                   density=0.1))
    assert flat["total_wire_bytes"] == flat["compressed_bytes"]
    # hierarchical: the two fabrics report separately and total sums them
    hier = GR.payload_bytes(
        like, GradReduceConfig(mode="topk", density=0.1, dcn_axis="dcn"),
        ici_size=4)
    assert hier["dcn_compressed_bytes"] == hier["compressed_bytes"]
    assert hier["dcn_dense_bytes"] == hier["dense_bytes"]
    assert hier["total_wire_bytes"] == \
        hier["ici_bytes"] + hier["dcn_compressed_bytes"]
    assert hier["dcn_compression_ratio"] >= 5.0
    # bucketed accounting follows the transport's per-bucket k
    bucketed = GR.payload_bytes(like, GradReduceConfig(
        mode="topk", density=0.1, bucket_count=8))
    assert bucketed["bucket_count"] == 8
    assert bucketed["compression_ratio"] >= 5.0
    # adaptive with realized rungs: exact rung pays dense bytes
    cfg = GradReduceConfig(mode="topk", density=0.1, adaptive=True)
    cheap = GR.payload_bytes(like, cfg, rungs=[0, 0])
    dear = GR.payload_bytes(like, cfg, rungs=[2, 2])   # "exact" rung
    assert cheap["compressed_bytes"] < dear["compressed_bytes"]
    assert dear["compressed_bytes"] == dear["dense_bytes"]
    rep = GR.bucket_report(like, cfg, rungs=[2, 0])
    per_leaf = {e["leaf"]: e for e in rep["per_leaf"]}
    assert per_leaf[0]["mode"] == "exact"
    assert per_leaf[1]["density"] == 0.025


# ------------------------------------------- wire-protocol tier (ISSUE 16)


def test_wire_protocol_config_and_resolution():
    with pytest.raises(ValueError, match="wire_protocol"):
        GradReduceConfig(wire_protocol="ring")
    with pytest.raises(ValueError, match="int8_accum"):
        GradReduceConfig(int8_accum="fp8")
    with pytest.raises(ValueError, match="dcn_schedule"):
        GradReduceConfig(dcn_schedule="latest")
    # rd / fixed need one hop axis to run the rounds on
    with pytest.raises(ValueError, match="ONE named axis"):
        GradReduceConfig(mode="topk", axis=("a", "b"), wire_protocol="rd")
    with pytest.raises(ValueError, match="ONE named axis"):
        GradReduceConfig(mode="int8", axis=("a", "b"), int8_accum="fixed")
    # auto resolves to rd on a single hop, falls back on multi-axis
    assert GR.resolved_wire_protocol(
        GradReduceConfig(mode="topk", axis="data")) == "rd"
    assert GR.resolved_wire_protocol(
        GradReduceConfig(mode="topk", axis="data", dcn_axis="dcn")) == "rd"
    assert GR.resolved_wire_protocol(
        GradReduceConfig(mode="topk", axis=("a", "b"))) == "allgather"
    assert GR.resolved_wire_protocol(
        GradReduceConfig(mode="topk", wire_protocol="allgather")) \
        == "allgather"
    assert GR.hop_axis(GradReduceConfig(axis="data", dcn_axis="dcn")) \
        == "dcn"
    assert GR.hop_axis(GradReduceConfig(axis="data")) == "data"
    assert GR.hop_axis(GradReduceConfig(axis=("a", "b"))) is None


def test_topk_rd_matches_allgather_protocol():
    """The rd wire protocol changes BYTES, not math: same reduced
    gradient as the legacy all-gather protocol from the same state, and
    only rd carries the fill/union accounting leaves."""
    g = _grads(seed=21)
    cfg_rd = GradReduceConfig(mode="topk", density=0.25)
    cfg_ag = GradReduceConfig(mode="topk", density=0.25,
                              wire_protocol="allgather")
    red_rd, st_rd, _ = _run_reduce(g, cfg_rd, {"data": 8})
    red_ag, st_ag, _ = _run_reduce(g, cfg_ag, {"data": 8})
    np.testing.assert_allclose(red_rd["w"], red_ag["w"], atol=1e-5)
    np.testing.assert_allclose(red_rd["b"], red_ag["b"], atol=1e-5)
    np.testing.assert_allclose(np.asarray(st_rd["ef"]["w"]),
                               np.asarray(st_ag["ef"]["w"]), atol=1e-5)
    assert "fill" in st_rd and "union" in st_rd
    assert "fill" not in st_ag and "union" not in st_ag
    assert st_rd["fill"].shape == (8, 2, GR.FILL_VEC_LEN)


def test_dcn_schedule_earliest_vs_free_bit_identical():
    """The earliest-needed-bucket-first schedule is pure ORDERING — the
    chained run is bit-identical to the unconstrained one, and
    bucket_report exposes which policy a config resolves to."""
    g = _grads(seed=22, d=96)
    kw = dict(mode="topk", density=0.2, bucket_count=3, axis="data",
              dcn_axis="dcn")
    red_e, st_e, _ = _run_reduce(
        g, GradReduceConfig(**kw, dcn_schedule="earliest"),
        {"dcn": 2, "data": 4})
    red_f, st_f, _ = _run_reduce(
        g, GradReduceConfig(**kw, dcn_schedule="free"),
        {"dcn": 2, "data": 4})
    np.testing.assert_array_equal(red_e["w"], red_f["w"])
    np.testing.assert_array_equal(np.asarray(st_e["ef"]["w"]),
                                  np.asarray(st_f["ef"]["w"]))
    like = {"w": np.zeros((96,), np.float32)}
    rep = GR.bucket_report(like, GradReduceConfig(**kw))
    assert rep["schedule"]["policy"] == "earliest"
    assert rep["schedule"]["order"] == [0, 1, 2]
    flat = GR.bucket_report(like, GradReduceConfig(
        mode="topk", density=0.2, bucket_count=3))
    assert flat["schedule"]["policy"] is None
    assert flat["schedule"]["order"] is None


def test_wire_bytes_reduction_acceptance():
    """Acceptance: bytes-on-wire per participant drops >= P/4 (= 2x at
    P=8) vs the all-gather protocol at density 0.01 — analytically AND
    measured from a real run's fill accounting (~P/2 = 4x expected)."""
    like = {"g": np.zeros((4096,), np.float32)}
    cfg = GradReduceConfig(mode="topk", density=0.01, axis="data")
    rep = GR.payload_bytes(like, cfg, hop_size=8)
    w = rep["wire"]
    assert rep["wire_protocol"] == "rd"
    assert w["hop_participants"] == 8 and w["rounds"] == 3
    assert w["allgather_bytes"] == 8 * 40 * 7       # 8B/entry * k * (P-1)
    assert w["reduction_vs_allgather_best"] >= 2.0  # the P/4 floor
    assert w["reduction_vs_allgather_best"] >= 3.9  # ~P/2 expected
    # measured: run the real reducer, feed its fill state back in
    rng = np.random.default_rng(23)
    g = {"g": jnp.asarray(np.tile(
        rng.normal(size=(1, 4096)).astype(np.float32), (8, 1)))}
    _, state, _ = _run_reduce(g, cfg, {"data": 8})
    rep_m = GR.payload_bytes(like, cfg, hop_size=8, fill=state["fill"])
    wm = rep_m["wire"]
    assert wm["rd_bytes_measured"] is not None
    assert wm["reduction_vs_allgather_measured"] >= 2.0
    assert wm["switch_rate_measured"] == 0.0        # stayed sparse
    # fill-in monotone: later rounds carry >= earlier unions
    rounds = wm["fill_rounds_measured"]
    assert len(rounds) == 3 and all(r > 0 for r in rounds)
    # without a fill observation the measured fields are null, never faked
    assert rep["wire"]["rd_bytes_measured"] is None
    assert rep["wire"]["reduction_vs_allgather_measured"] is None


def test_reshard_carries_wire_state_leaves():
    """PR 15 elastic resize routing for the new leaves: ``union`` (a
    replicated statistic) broadcasts participant 0, ``fill`` (per-round
    counts specific to the OLD fleet's round structure) re-seeds to
    zeros at the new size — never refused, never averaged across
    incompatible topologies."""
    g = _grads(seed=24)
    cfg = GradReduceConfig(mode="topk", density=0.25)
    _, state, _ = _run_reduce(g, cfg, {"data": 8})
    assert np.asarray(state["fill"]).any()
    for n_new in (4, 6):
        rs = GR.reshard_state(state, n_new)
        assert rs["fill"].shape == (n_new,) + state["fill"].shape[1:]
        assert not np.asarray(rs["fill"]).any()
        np.testing.assert_array_equal(
            np.asarray(rs["union"]),
            np.broadcast_to(np.asarray(state["union"])[:1],
                            (n_new,) + state["union"].shape[1:]))


def test_int8_fixed_hop_matches_legacy_dequant_envelope():
    """Satellite: quantized_all_reduce's dequantize-then-sum is the
    LEGACY accumulation; the int32-hop mode must agree within the
    quantization envelope (sum of per-participant block quanta) — an
    agreement envelope, NOT bit-equality: the two orders round
    differently by design."""
    g = _grads(seed=25)
    legacy = GradReduceConfig(mode="int8", block_size=16, seed=7)
    fixed = GradReduceConfig(mode="int8", block_size=16, seed=7,
                             int8_accum="fixed")
    red_l, _, _ = _run_reduce(g, legacy, {"data": 8})
    red_f, _, per_dev = _run_reduce(g, fixed, {"data": 8})
    exact = np.asarray(g["w"]).sum(0)
    # fixed-point accumulates in int32 against ONE shared scale, so its
    # error bound is P quanta of the shared (pmax) scale
    shared = np.abs(np.asarray(g["w"]).reshape(8, -1, 16)).max(
        axis=(0, 2)) / 127.0
    bound = np.repeat(shared, 16) * 8 * (1.0 + 1e-6)
    assert np.all(np.abs(red_f["w"] - exact) <= bound)
    assert np.all(np.abs(red_f["w"] - red_l["w"]) <= 2 * bound)
    # the int32 hop is deterministic across participants: bit-identical
    # replicas even before the harness's replication assert
    np.testing.assert_array_equal(per_dev["w"],
                                  np.broadcast_to(per_dev["w"][:1],
                                                  per_dev["w"].shape))


def test_exact_mode_bit_identical_to_legacy_reduce():
    """Tentpole guardrail: exact mode never routes through the wire
    protocol — bit-identical to a raw lax.psum whatever wire_protocol
    says, and it carries no accounting state."""
    from jax import lax

    g = _grads(seed=26)
    mesh = device_mesh({"data": 8})

    def raw(x):
        return lax.psum(x[0], "data")[None]

    fn = shard_map_fn(raw, mesh, in_specs=P("data"), out_specs=P("data"))
    oracle = np.asarray(fn(g["w"]))[0]
    for proto in ("auto", "rd", "allgather"):
        cfg = GradReduceConfig(mode="exact", wire_protocol=proto)
        red, state, _ = _run_reduce(g, cfg, {"data": 8})
        np.testing.assert_array_equal(red["w"], oracle)
        assert state == {}


# ---------------------------------------------------------- hosted iterate


def test_hosted_iterate_carries_reducer_state(tmp_path):
    """A hosted-iterate body using reduce_gradients keeps its reducer
    state in the iterate state pytree: per-epoch checkpoints round-trip
    the residual, so crash + resume equals the uninterrupted run exactly."""
    from flink_ml_tpu.iteration import (
        IterationBodyResult,
        IterationConfig,
        iterate,
    )
    from flink_ml_tpu.iteration.checkpoint import CheckpointConfig

    mesh = device_mesh({"data": 8})
    cfg = GradReduceConfig(mode="topk", density=0.25)
    d = 32
    rng = np.random.default_rng(5)
    data = jnp.asarray(rng.normal(size=(8, d)).astype(np.float32))
    target = jnp.asarray(rng.normal(size=(d,)).astype(np.float32))
    dev_spec = P("data")

    def reduce_fn(w, st, x):
        def body(w, st, x):
            g = {"w": x[0] * (w - target)}
            red, new_st = GR.reduce_gradients(g, GR.squeeze_state(st), cfg)
            return red["w"], GR.unsqueeze_state(new_st)

        return shard_map_fn(body, mesh,
                            in_specs=(P(), dev_spec, P("data", None)),
                            out_specs=(P(), dev_spec))(w, st, x)

    def epoch_body(state, epoch, x):
        w, st = state["w"], state["gr"]
        g, st = reduce_fn(w, st, x)
        return IterationBodyResult({"w": w - 0.05 * g, "gr": st})

    init = {"w": jnp.zeros((d,), jnp.float32),
            "gr": GR.init_state(cfg, {"w": jnp.zeros((d,))}, 8)}
    ck = str(tmp_path / "ck")
    full = iterate(epoch_body, init, data, max_epochs=8,
                   config=IterationConfig(mode="hosted"),
                   checkpoint=CheckpointConfig(ck))
    # resume from the epoch-5 cut and run to 8: must equal the full run
    resumed = iterate(epoch_body, init, data, max_epochs=8,
                      config=IterationConfig(mode="hosted"),
                      checkpoint=CheckpointConfig(ck), resume=True)
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(full.state["w"])),
        np.asarray(jax.device_get(resumed.state["w"])))


def test_hosted_iterate_carries_r11_schedule_state(tmp_path):
    """The r11 reducer state — pending overlap buffer, adaptive
    rung/EMA/tick — is just more pytree leaves in the iterate state:
    per-epoch checkpoints round-trip the whole schedule, so crash +
    resume equals the uninterrupted run exactly (including which rung
    each leaf sits on)."""
    from flink_ml_tpu.iteration import (
        IterationBodyResult,
        IterationConfig,
        iterate,
    )
    from flink_ml_tpu.iteration.checkpoint import CheckpointConfig

    mesh = device_mesh({"data": 8})
    cfg = GradReduceConfig(mode="topk", density=0.25, bucket_count=2,
                           overlap=True, adaptive=True, adaptive_window=3)
    d = 32
    rng = np.random.default_rng(6)
    data = jnp.asarray(rng.normal(size=(8, d)).astype(np.float32))
    target = jnp.asarray(rng.normal(size=(d,)).astype(np.float32))
    dev_spec = P("data")

    def reduce_fn(w, st, x):
        def body(w, st, x):
            g = {"w": x[0] * (w - target)}
            red, new_st = GR.pipelined_reduce(g, GR.squeeze_state(st), cfg)
            return red["w"], GR.unsqueeze_state(new_st)

        return shard_map_fn(body, mesh,
                            in_specs=(P(), dev_spec, P("data", None)),
                            out_specs=(P(), dev_spec))(w, st, x)

    def epoch_body(state, epoch, x):
        w, st = state["w"], state["gr"]
        g, st = reduce_fn(w, st, x)
        return IterationBodyResult({"w": w - 0.05 * g, "gr": st})

    init = {"w": jnp.zeros((d,), jnp.float32),
            "gr": GR.init_state(cfg, {"w": jnp.zeros((d,))}, 8)}
    ck = str(tmp_path / "ck")
    full = iterate(epoch_body, init, data, max_epochs=8,
                   config=IterationConfig(mode="hosted"),
                   checkpoint=CheckpointConfig(ck))
    resumed = iterate(epoch_body, init, data, max_epochs=8,
                      config=IterationConfig(mode="hosted"),
                      checkpoint=CheckpointConfig(ck), resume=True)
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(full.state["w"])),
        np.asarray(jax.device_get(resumed.state["w"])))
    for a, b in zip(
            jax.tree_util.tree_leaves(jax.device_get(full.state["gr"])),
            jax.tree_util.tree_leaves(jax.device_get(resumed.state["gr"]))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(np.asarray(jax.device_get(full.state["gr"]["tick"]))[0]) == 8
