"""Parallel layer tests: mesh construction, collectives, ring attention and
Ulysses sequence parallelism vs. the dense oracle — all on the 8-device
virtual mesh (the MiniCluster analog)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from flink_ml_tpu.parallel import collectives as col
from flink_ml_tpu.parallel.mesh import device_mesh
from flink_ml_tpu.parallel.ring_attention import (
    attention_reference,
    ring_attention,
)
from flink_ml_tpu.parallel.ulysses import ulysses_attention


def _qkv(b=2, s=32, h=4, d=8, seed=0):
    rng = np.random.default_rng(seed)
    shape = (b, s, h, d)
    return (jnp.asarray(rng.normal(size=shape), jnp.float32),
            jnp.asarray(rng.normal(size=shape), jnp.float32),
            jnp.asarray(rng.normal(size=shape), jnp.float32))


def test_device_mesh_shapes():
    mesh = device_mesh({"data": 4, "model": 2})
    assert mesh.shape == {"data": 4, "model": 2}
    inferred = device_mesh({"data": -1, "model": 2})
    assert inferred.shape["data"] == 4
    with pytest.raises(ValueError):
        device_mesh({"data": 3})
    with pytest.raises(ValueError):
        device_mesh({"data": -1, "model": -1})


def test_collectives_inside_shard_map():
    mesh = device_mesh({"data": 8})

    def body(x):
        total = col.psum(jnp.sum(x), "data")
        gathered = col.all_gather(x, "data")
        rotated = col.ppermute_ring(x, "data", shift=1)
        idx = col.axis_index("data")
        return total * jnp.ones_like(x), gathered, rotated, \
            idx * jnp.ones_like(x, jnp.int32)

    x = jnp.arange(8, dtype=jnp.float32)
    fn = col.shard_map_fn(body, mesh, in_specs=P("data"),
                          out_specs=(P("data"), P("data"), P("data"),
                                     P("data")))
    total, gathered, rotated, idx = fn(x)
    np.testing.assert_array_equal(np.asarray(total), [28.0] * 8)
    # all_gather tiled: every shard sees the full vector
    assert gathered.shape == (64,)
    # ring shift by one: shard i's value moves to shard i+1
    np.testing.assert_array_equal(np.asarray(rotated),
                                  [7, 0, 1, 2, 3, 4, 5, 6])
    np.testing.assert_array_equal(np.asarray(idx), np.arange(8))


def test_reduce_scatter():
    mesh = device_mesh({"data": 8})

    def body(x):
        return col.reduce_scatter(x, "data")

    # every shard holds the full 8-vector of ones -> reduce_scatter sums the
    # 8 copies and hands each shard one element
    x = jnp.ones((64,), jnp.float32)
    fn = col.shard_map_fn(body, mesh, in_specs=P("data"), out_specs=P("data"))
    out = fn(x)
    assert out.shape == (8,)
    np.testing.assert_array_equal(np.asarray(out), [8.0] * 8)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_reference(causal):
    mesh = device_mesh({"seq": 8})
    q, k, v = _qkv()
    expected = attention_reference(q, k, v, causal=causal)
    got = ring_attention(q, k, v, mesh=mesh, axis="seq", causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_reference(causal):
    mesh = device_mesh({"seq": 4, "data": 2})
    q, k, v = _qkv(h=8)
    expected = attention_reference(q, k, v, causal=causal)
    got = ulysses_attention(q, k, v, mesh=mesh, axis="seq", causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=2e-5)


def test_ring_attention_long_context_sharded_memory():
    # The point of ring attention: each device only holds seq/n of the
    # sequence; the full (s x s) score matrix never materializes.
    mesh = device_mesh({"seq": 8})
    q, k, v = _qkv(b=1, s=256, h=2, d=4)
    out = ring_attention(q, k, v, mesh=mesh, axis="seq")
    expected = attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-5)
    # output keeps the sequence sharding (older shard_map trims trailing
    # Nones off the spec, so compare the normalized form)
    spec = tuple(out.sharding.spec)
    assert spec[:2] == (None, "seq") and all(s is None for s in spec[2:])


def test_ring_attention_rejects_ragged_seq():
    mesh = device_mesh({"seq": 8})
    q, k, v = _qkv(s=30)
    with pytest.raises(ValueError):
        ring_attention(q, k, v, mesh=mesh, axis="seq")


def test_ulysses_rejects_bad_heads():
    mesh = device_mesh({"seq": 8})
    q, k, v = _qkv(h=4)  # 4 heads < 8 devices
    with pytest.raises(ValueError):
        ulysses_attention(q, k, v, mesh=mesh, axis="seq")


def test_distributed_single_process_degradation():
    from flink_ml_tpu.parallel import distributed as dist

    dist.initialize()
    assert dist.is_initialized()
    info = dist.process_info()
    assert info.process_count == 1 and info.is_coordinator
    assert info.global_device_count == 8

    mesh = dist.global_mesh({"data": -1})
    assert mesh.shape["data"] == 8

    local = {"x": np.arange(16, dtype=np.float32)}
    global_arr = dist.host_local_to_global(local, mesh, axis="data")
    assert len(global_arr["x"].sharding.device_set) == 8
    back = dist.global_to_host_local(global_arr, mesh, axis="data")
    np.testing.assert_array_equal(back["x"], local["x"])

    dist.barrier()  # no-op single process
    assert dist.broadcast_from_host0({"v": 3})["v"] == 3


def test_hybrid_mesh_single_host():
    from flink_ml_tpu.parallel import distributed as dist

    mesh = dist.hybrid_mesh({"data": 4, "model": 2})
    assert mesh.shape == {"dcn": 1, "data": 4, "model": 2}


# ------------------------------------------------- bare-wrapper oracles


def test_reduce_scatter_oracle_random():
    """reduce_scatter vs the numpy oracle on random data: shard i of the
    output is the i-th slice of the sum over participants."""
    mesh = device_mesh({"data": 8})
    rng = np.random.default_rng(0)
    g = rng.normal(size=(8, 64)).astype(np.float32)

    def body(x):
        return col.reduce_scatter(x[0], "data")[None]

    fn = col.shard_map_fn(body, mesh, in_specs=P("data", None),
                          out_specs=P("data", None))
    out = np.asarray(fn(jnp.asarray(g)))  # (8, 8): device i's shard
    expected = g.sum(axis=0).reshape(8, 8)
    np.testing.assert_allclose(out, expected, atol=1e-5)


def test_reduce_scatter_scatter_dimension():
    """scatter_dimension=1 splits the SECOND dim across participants."""
    mesh = device_mesh({"data": 8})
    rng = np.random.default_rng(1)
    g = rng.normal(size=(8, 4, 16)).astype(np.float32)

    def body(x):
        return col.reduce_scatter(x[0], "data", scatter_dimension=1)[None]

    fn = col.shard_map_fn(body, mesh, in_specs=P("data", None, None),
                          out_specs=P("data", None, None))
    out = np.asarray(fn(jnp.asarray(g)))  # (8, 4, 2)
    total = g.sum(axis=0)
    for i in range(8):
        np.testing.assert_allclose(out[i], total[:, 2 * i:2 * i + 2],
                                   atol=1e-5)


@pytest.mark.parametrize("shift", [1, 3, -1])
def test_ppermute_ring_shift_oracle(shift):
    """ppermute_ring(shift=s) == np.roll by s: shard i's value lands on
    shard (i + s) mod n."""
    mesh = device_mesh({"data": 8})

    def body(x):
        return col.ppermute_ring(x, "data", shift=shift)

    fn = col.shard_map_fn(body, mesh, in_specs=P("data"),
                          out_specs=P("data"))
    x = jnp.arange(8, dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(fn(x)),
                                  np.roll(np.arange(8.0), shift))


def test_all_gather_untiled_oracle():
    """all_gather(tiled=False) stacks shards on a NEW leading axis — the
    (P, shard) layout the grad_reduce sparse exchange rides on."""
    mesh = device_mesh({"data": 8})
    rng = np.random.default_rng(2)
    g = rng.normal(size=(8, 5)).astype(np.float32)

    def body(x):
        return col.all_gather(x[0], "data", tiled=False)[None]

    fn = col.shard_map_fn(body, mesh, in_specs=P("data", None),
                          out_specs=P("data", None, None))
    out = np.asarray(fn(jnp.asarray(g)))  # (8, 8, 5): each device sees all
    for i in range(8):
        np.testing.assert_array_equal(out[i], g)


def test_pmean_pmax_axis_size_oracle():
    mesh = device_mesh({"data": 8})

    def body(x):
        return (col.pmean(x, "data") * jnp.ones_like(x),
                col.pmax(x, "data") * jnp.ones_like(x),
                col.axis_size("data") * jnp.ones_like(x, jnp.int32))

    x = jnp.asarray([3., -1., 4., 1., 5., -9., 2., 6.])
    fn = col.shard_map_fn(body, mesh, in_specs=P("data"),
                          out_specs=(P("data"), P("data"), P("data")))
    mean, mx, size = fn(x)
    np.testing.assert_allclose(np.asarray(mean), [float(np.mean(x))] * 8,
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(mx), [6.0] * 8)
    np.testing.assert_array_equal(np.asarray(size), [8] * 8)


# ---------------------------------------------------------------------------
# recursive-halving/doubling sparse allreduce (the wire-protocol tier)
# ---------------------------------------------------------------------------


def _run_rd(p, idx, vals, n):
    """Run sparse_all_reduce_rd on a P-subset of the virtual mesh and
    return (dense (P, n), fill (P, FILL_VEC_LEN)) as numpy."""
    mesh = device_mesh({"data": p}, devices=jax.devices()[:p])

    def body(i, v):
        dense, fill = col.sparse_all_reduce_rd(i[0], v[0], n, "data")
        return dense[None], fill[None]

    # jitted, as every product caller runs it: an eager shard_map executes
    # the body primitive by primitive, hundreds of 8-device compiles
    fn = jax.jit(col.shard_map_fn(body, mesh,
                                  in_specs=(P("data"), P("data")),
                                  out_specs=(P("data"), P("data"))))
    dense, fill = fn(jnp.asarray(idx), jnp.asarray(vals))
    return np.asarray(dense), np.asarray(fill)


def _scatter_oracle(idx, vals, n):
    """The all-gather protocol's answer: every contribution scatter-added
    into a dense (n,) — duplicate indices within one contribution sum."""
    oracle = np.zeros((n,), np.float64)
    for r in range(idx.shape[0]):
        np.add.at(oracle, idx[r], vals[r].astype(np.float64))
    return oracle.astype(np.float32)


def test_rd_topology():
    """core = 2^floor(log2 P), rounds = log2(core), extras fold."""
    assert col.rd_topology(1) == (1, 0, 0)
    assert col.rd_topology(2) == (2, 1, 0)
    assert col.rd_topology(3) == (2, 1, 1)
    assert col.rd_topology(6) == (4, 2, 2)
    assert col.rd_topology(8) == (8, 3, 0)
    with pytest.raises(ValueError):
        col.rd_topology(0)


@pytest.mark.parametrize("p,n,k", [
    # every power-of-two P appears; the shape grid runs in full only at
    # P=8 (each (p, n, k) combo is its own shard_map compile — the full
    # 3x3 cross product is ~80 s of tier-1 compile time for no extra
    # code-path coverage at the smaller rounds counts)
    (2, 100, 7), (4, 64, 4), (8, 64, 4), (8, 100, 7), (8, 16, 16)])
def test_sparse_all_reduce_rd_matches_allgather_oracle(p, n, k):
    """Power-of-two P: the log2(P) halving/doubling rounds produce the
    same dense result as the all-gather oracle, elementwise, replicated
    identically on every participant."""
    rng = np.random.default_rng(p * 100 + n)
    idx = rng.integers(0, n, size=(p, k)).astype(np.int32)
    vals = np.round(rng.normal(size=(p, k)) * 8).astype(np.float32) / 8
    dense, _ = _run_rd(p, idx, vals, n)
    oracle = _scatter_oracle(idx, vals, n)
    for r in range(p):
        np.testing.assert_allclose(dense[r], oracle, atol=1e-5)
    for r in range(1, p):
        np.testing.assert_array_equal(dense[r], dense[0])


@pytest.mark.parametrize("p", [3, 6])
def test_sparse_all_reduce_rd_non_power_of_two(p):
    """P=3/6 fold the extras onto a 2^floor(log2 P) core before the
    rounds and broadcast back after — result still equals the oracle on
    ALL P participants, extras included."""
    n, k = 48, 5
    rng = np.random.default_rng(7)
    idx = rng.integers(0, n, size=(p, k)).astype(np.int32)
    vals = np.round(rng.normal(size=(p, k)) * 8).astype(np.float32) / 8
    dense, _ = _run_rd(p, idx, vals, n)
    oracle = _scatter_oracle(idx, vals, n)
    for r in range(p):
        np.testing.assert_allclose(dense[r], oracle, atol=1e-5,
                                   err_msg=f"participant {r} of {p}")


@pytest.mark.parametrize("p", [3, 8])
def test_sparse_all_reduce_rd_duplicate_indices_sum(p):
    """Duplicate indices WITHIN one contribution sum correctly (the
    merge dedup must not collapse them before scatter semantics apply).
    P=8 exercises the pure halving/doubling dedup, P=3 the pre-fold
    merge; the oracle tests' random indices cover incidental dups at
    the other extents."""
    n, k = 32, 8
    rng = np.random.default_rng(11)
    idx = rng.integers(0, n, size=(p, k)).astype(np.int32)
    idx[:, : k // 2] = idx[:, k // 2:]          # force pairwise dups
    vals = np.round(rng.normal(size=(p, k)) * 8).astype(np.float32) / 8
    dense, _ = _run_rd(p, idx, vals, n)
    oracle = _scatter_oracle(idx, vals, n)
    for r in range(p):
        np.testing.assert_allclose(dense[r], oracle, atol=1e-5)


@pytest.mark.parametrize("p", [2, 6, 8])
def test_sparse_all_reduce_rd_empty_contribution_noop(p):
    """k=0 contributions are a no-op: the result is all zeros and the
    fill vector reports nothing shipped."""
    dense, fill = _run_rd(p, np.zeros((p, 0), np.int32),
                          np.zeros((p, 0), np.float32), 50)
    np.testing.assert_array_equal(dense, np.zeros((p, 50), np.float32))
    np.testing.assert_array_equal(fill, np.zeros_like(fill))


def test_sparse_all_reduce_rd_dense_switchover():
    """Disjoint supports at k = n/2 densify the union past break-even:
    every participant flips to the dense doubling branch (switch slot
    = 1) and the result still matches the oracle."""
    p, n, k = 8, 32, 16
    rng = np.random.default_rng(3)
    idx = rng.integers(0, n, size=(p, k)).astype(np.int32)
    vals = np.round(rng.normal(size=(p, k)) * 8).astype(np.float32) / 8
    dense, fill = _run_rd(p, idx, vals, n)
    oracle = _scatter_oracle(idx, vals, n)
    for r in range(p):
        np.testing.assert_allclose(dense[r], oracle, atol=1e-5)
    np.testing.assert_array_equal(fill[:, col.FILL_SWITCH_SLOT],
                                  np.ones((p,), np.float32))


@pytest.mark.parametrize("p", [2, 3, 6, 8])
def test_fixed_point_all_reduce_is_exact(p):
    """int32 recursive doubling == the integer sum, bit-identical on
    every participant (the SwitchML pool-semantics hop)."""
    q = np.random.default_rng(0).integers(
        -127, 127, size=(p, 33)).astype(np.int32)
    mesh = device_mesh({"data": p}, devices=jax.devices()[:p])

    def body(x):
        return col.fixed_point_all_reduce(x[0], "data")[None]

    fn = col.shard_map_fn(body, mesh, in_specs=P("data"),
                          out_specs=P("data"))
    out = np.asarray(fn(jnp.asarray(q)))
    for r in range(p):
        np.testing.assert_array_equal(out[r], q.sum(0))


# ---------------------------------------------------------------- pipeline


def _mlp_stage(params, x):
    w, b = params
    return jnp.tanh(x @ w + b)


def _stacked_mlp(n_stages, d, seed=0):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(size=(n_stages, d, d)) * 0.3, jnp.float32)
    b = jnp.asarray(rng.normal(size=(n_stages, d)) * 0.1, jnp.float32)
    return (w, b)


def _sequential(params, x):
    w, b = params
    for i in range(w.shape[0]):
        x = _mlp_stage((w[i], b[i]), x)
    return x


def test_pipeline_matches_sequential():
    from flink_ml_tpu.parallel.pipeline_parallel import build_pipeline

    mesh = device_mesh({"pipe": 8})
    d, batch = 16, 24
    params = _stacked_mlp(8, d)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(batch, d)),
                    jnp.float32)
    fn = build_pipeline(_mlp_stage, mesh, n_micro=4)
    np.testing.assert_allclose(np.asarray(fn(params, x)),
                               np.asarray(_sequential(params, x)),
                               rtol=1e-5, atol=1e-5)


def test_pipeline_grad_matches_sequential():
    # jax.grad through the scan+ppermute IS the backward pipeline; it must
    # agree with the grad of the plain stacked-layer forward.
    from flink_ml_tpu.parallel.pipeline_parallel import build_pipeline

    mesh = device_mesh({"pipe": 4}, devices=jax.devices()[:4])
    d, batch = 8, 16
    params = _stacked_mlp(4, d)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(batch, d)),
                    jnp.float32)
    y = jnp.asarray(np.random.default_rng(3).normal(size=(batch, d)),
                    jnp.float32)
    fn = build_pipeline(_mlp_stage, mesh, n_micro=4)

    def loss_pp(p):
        return jnp.mean((fn(p, x) - y) ** 2)

    def loss_seq(p):
        return jnp.mean((_sequential(p, x) - y) ** 2)

    g_pp = jax.grad(loss_pp)(params)
    g_seq = jax.grad(loss_seq)(params)
    for a, b in zip(jax.tree_util.tree_leaves(g_pp),
                    jax.tree_util.tree_leaves(g_seq)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_pipeline_composes_with_data_parallel():
    from flink_ml_tpu.parallel.pipeline_parallel import build_pipeline

    mesh = device_mesh({"data": 2, "pipe": 4})
    d, batch = 8, 32
    params = _stacked_mlp(4, d, seed=4)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(batch, d)),
                    jnp.float32)
    fn = build_pipeline(_mlp_stage, mesh, n_micro=4, data_axis="data")
    np.testing.assert_allclose(np.asarray(fn(params, x)),
                               np.asarray(_sequential(params, x)),
                               rtol=1e-5, atol=1e-5)


def test_pipeline_validation_errors():
    from flink_ml_tpu.parallel.pipeline_parallel import build_pipeline

    mesh = device_mesh({"pipe": 4}, devices=jax.devices()[:4])
    fn = build_pipeline(_mlp_stage, mesh, n_micro=3)
    params = _stacked_mlp(4, 8)
    x = jnp.zeros((16, 8), jnp.float32)  # 16 % 3 != 0
    with pytest.raises(ValueError, match="not divisible by n_micro"):
        fn(params, x)
    bad = _stacked_mlp(3, 8)  # 3 stages on a 4-wide pipe axis
    with pytest.raises(ValueError, match="params leading dim"):
        build_pipeline(_mlp_stage, mesh, n_micro=4)(bad, jnp.zeros((8, 8)))
    with pytest.raises(ValueError, match="no axis 'pipe'"):
        build_pipeline(_mlp_stage, device_mesh({"data": 8}), n_micro=2)


# ---------------------------------------------------------------- MoE / ep


def _moe_setup(n_tokens=32, d=8, hidden=16, experts=4, seed=7):
    from flink_ml_tpu.parallel.moe import init_moe

    rng = np.random.default_rng(seed)
    params = init_moe(rng, d, hidden, experts)
    x = jnp.asarray(rng.normal(size=(n_tokens, d)), jnp.float32)
    return params, x


def _moe_oracle(params, x):
    """Per-token: run the argmax expert densely (no capacity)."""
    gates = jax.nn.softmax(x @ params.wg, axis=-1)
    top1 = np.asarray(jnp.argmax(gates, axis=-1))
    out = np.zeros_like(np.asarray(x))
    for t in range(x.shape[0]):
        e = top1[t]
        h = jax.nn.gelu(x[t] @ params.w_in[e])
        out[t] = np.asarray((h @ params.w_out[e])
                            * gates[t, e])
    return out


def test_moe_matches_per_token_oracle():
    from flink_ml_tpu.parallel.moe import moe_apply

    params, x = _moe_setup()
    # generous capacity so nothing drops
    y = moe_apply(params, x, capacity_factor=4.0, mesh=None)
    np.testing.assert_allclose(np.asarray(y), _moe_oracle(params, x),
                               rtol=1e-4, atol=1e-5)


def test_moe_sharded_matches_unsharded():
    from flink_ml_tpu.parallel.moe import moe_apply, moe_sharding

    mesh = device_mesh({"data": 2, "expert": 4})
    params, x = _moe_setup(n_tokens=64)
    shardings = moe_sharding(mesh)
    params_s = jax.device_put(params, shardings)
    x_s = jax.device_put(x, jax.sharding.NamedSharding(mesh, P("data")))

    fn = jax.jit(lambda p, x: moe_apply(
        p, x, capacity_factor=4.0, mesh=mesh, data_axis="data"))
    y_sharded = fn(params_s, x_s)
    y_local = moe_apply(params, x, capacity_factor=4.0, mesh=None)
    np.testing.assert_allclose(np.asarray(y_sharded), np.asarray(y_local),
                               rtol=1e-4, atol=1e-5)


def test_moe_capacity_drops_overflow_tokens():
    from flink_ml_tpu.parallel.moe import moe_apply

    params, x = _moe_setup(n_tokens=16)
    # capacity_factor tiny -> capacity 1 per expert: at most E tokens survive
    y = moe_apply(params, x, capacity_factor=1e-6, mesh=None)
    nonzero_rows = np.count_nonzero(
        np.any(np.abs(np.asarray(y)) > 0, axis=1))
    assert nonzero_rows <= params.wg.shape[1]
    assert np.all(np.isfinite(np.asarray(y)))


def test_moe_bf16_routing_matches_f32():
    # Routing bookkeeping must be precision-independent: bf16 inputs route
    # identically to f32 (a bf16 cumsum would collide queue positions).
    from flink_ml_tpu.parallel.moe import moe_apply

    params, x = _moe_setup(n_tokens=2048, d=8, experts=4)
    y32 = moe_apply(params, x, capacity_factor=4.0, mesh=None)
    y16 = moe_apply(params, x.astype(jnp.bfloat16), capacity_factor=4.0,
                    mesh=None)
    assert y16.dtype == jnp.bfloat16
    # A few borderline tokens may flip argmax expert under bf16 gating
    # rounding (legitimate); queue-position collisions would corrupt the
    # majority of tokens (several tokens summed into one capacity slot).
    diff = np.abs(np.asarray(y16, np.float32) - np.asarray(y32))
    frac_bad = np.mean(np.any(diff > 0.05, axis=1))
    assert frac_bad < 0.02, f"{frac_bad:.1%} tokens corrupted"


def test_moe_grouped_matches_per_group_apply():
    from flink_ml_tpu.parallel.moe import moe_apply

    params, x = _moe_setup(n_tokens=64)
    grouped = moe_apply(params, x, capacity_factor=4.0, group_size=16,
                        mesh=None)
    per_group = jnp.concatenate([
        moe_apply(params, x[i:i + 16], capacity_factor=4.0, mesh=None)
        for i in range(0, 64, 16)])
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(per_group),
                               rtol=1e-5, atol=1e-6)


def test_moe_grouped_sharded_matches_local():
    from flink_ml_tpu.parallel.moe import moe_apply, moe_sharding

    mesh = device_mesh({"data": 2, "expert": 4})
    params, x = _moe_setup(n_tokens=64)
    params_s = jax.device_put(params, moe_sharding(mesh))
    x_s = jax.device_put(x, jax.sharding.NamedSharding(mesh, P("data")))
    fn = jax.jit(lambda p, t: moe_apply(
        p, t, capacity_factor=4.0, group_size=8, mesh=mesh,
        data_axis="data"))
    y = fn(params_s, x_s)
    y_local = moe_apply(params, x, capacity_factor=4.0, group_size=8,
                        mesh=None)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_local),
                               rtol=1e-4, atol=1e-5)


def test_moe_group_size_must_divide():
    from flink_ml_tpu.parallel.moe import moe_apply

    params, x = _moe_setup(n_tokens=32)
    with pytest.raises(ValueError, match="not divisible by group_size"):
        moe_apply(params, x, group_size=7, mesh=None)
