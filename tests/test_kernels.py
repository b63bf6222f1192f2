"""Unified kernel registry (``flink_ml_tpu/kernels/``, ISSUE 10).

What these tests pin down:

- registry mechanics: priority/availability/supports selection, forced
  backends (bypass availability, never supports), loud failures;
- dispatch accounting: the compile/cache-hit/latency gauges track the
  shared jit's cache keying, and serving endpoints re-export them;
- THE cross-consumer guarantee: one registry entry per (op, schema,
  backend) backs pipelines, serving, AND training — a serving warm-up
  leaves ZERO new XLA lowerings for the fused pipeline plan, the
  model's own transform, and a CV-style re-score on the same (op,
  schema, bucket), lowering-counter-asserted; the training step
  builders resolve the very same entries (fn-identity-asserted);
- the cross-backend parity matrix: every multi-backend op's alternate
  implementations agree with the XLA lowering (bit-exact where the
  kernel contract promises it), with a COVERAGE gate so registering a
  new backend without a parity harness fails this file.
"""

from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flink_ml_tpu.data.table import Table
from flink_ml_tpu.kernels import registry as kreg
from flink_ml_tpu.kernels.registry import (
    KernelEntry,
    dispatch,
    kernel_stats,
    lookup,
    register_kernel,
)


# ---------------------------------------------------------------------------
# registry mechanics
# ---------------------------------------------------------------------------

def _with_temp_op(entries):
    """Context: register throwaway entries under a test-only op name and
    drop them afterwards."""
    import contextlib

    @contextlib.contextmanager
    def cm():
        op = "_test_op_"
        for e in entries:
            register_kernel(op, **e)
        try:
            yield op
        finally:
            kreg._REGISTRY.pop(op, None)
    return cm()


def test_lookup_picks_priority_available_supported():
    with _with_temp_op([
        dict(backend="slow", fn=lambda: "slow", priority=0),
        dict(backend="fast", fn=lambda: "fast", priority=10),
        dict(backend="faster-elsewhere", fn=lambda: "x", priority=20,
             available=lambda: False),
        dict(backend="faster-elsewhen", fn=lambda: "y", priority=30,
             supports=lambda sig: False),
    ]) as op:
        assert lookup(op).backend == "fast"
        # forced backend bypasses availability...
        assert lookup(op, backend="faster-elsewhere").backend == \
            "faster-elsewhere"
        # ...but a provided sig still gates the shape contract
        with pytest.raises(ValueError, match="does not support"):
            lookup(op, sig=("some-shape",), backend="faster-elsewhen")
        # ...and with no sig the caller owns the choice entirely
        assert lookup(op, backend="faster-elsewhen").backend == \
            "faster-elsewhen"


def test_lookup_failures_are_loud():
    with pytest.raises(KeyError, match="unknown kernel op"):
        lookup("_no_such_op_")
    with _with_temp_op([
        dict(backend="narrow", fn=lambda: 0,
             supports=lambda sig: sig == ("ok",)),
    ]) as op:
        with pytest.raises(KeyError, match="no backend"):
            lookup(op, backend="missing")
        with pytest.raises(ValueError, match="no available backend"):
            lookup(op, sig=("nope",))
        assert lookup(op, sig=("ok",)).backend == "narrow"


def test_register_replaces_same_backend():
    with _with_temp_op([dict(backend="xla", fn=lambda: 1)]) as op:
        register_kernel(op, "xla", lambda: 2)
        assert len(kreg._REGISTRY[op]) == 1
        assert lookup(op, backend="xla").fn() == 2


def test_catalog_registers_every_documented_op():
    ops = kreg.ops()
    for op in ("als_cholesky_solve", "ell_margin", "ell_scatter_apply",
               "gbt_level_histograms", "kmeans_assign", "kmeans_update_stats",
               "kmeans_workset_update", "linear_margins", "retrieve",
               "routed_adam_update", "routed_table_grad", "widedeep_scores"):
        assert op in ops, f"catalog lost op {op}"
    # every op has the automatic non-TPU fallback registered
    for op in ops:
        if op.startswith("_test_"):
            continue
        assert any(e.is_available() for e in kreg._REGISTRY[op].values()), \
            f"op {op} has no available backend on this host"


# ---------------------------------------------------------------------------
# dispatch accounting
# ---------------------------------------------------------------------------

def _margin_plan(n=16, d=4, seed=0, fcol="f"):
    from flink_ml_tpu.models.common.linear import _linear_chain_kernel

    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    plan = ((_linear_chain_kernel, (fcol, "m")),)
    params = ({"w": rng.normal(size=(d,)).astype(np.float32),
               "b": np.float32(0.5)},)
    return plan, params, {fcol: X}


def test_dispatch_counts_compiles_and_cache_hits():
    plan, params, cols = _margin_plan(fcol="_acct_col_a")
    before = kernel_stats.snapshot()
    out1 = dispatch(plan, params, cols, op="_acct_op")
    mid = kernel_stats.snapshot()
    assert mid["compiles"] == before["compiles"] + 1
    out2 = dispatch(plan, params, cols, op="_acct_op")
    after = kernel_stats.snapshot()
    assert after["compiles"] == mid["compiles"]          # cache hit
    assert after["cache_hits"] == mid["cache_hits"] + 1
    assert after["per_op"]["_acct_op"]["dispatches"] >= 2
    assert after["dispatch_latency_ms"] > 0.0
    np.testing.assert_array_equal(np.asarray(out1["m"]),
                                  np.asarray(out2["m"]))
    # a different shape on the same plan is a NEW compile
    plan2, params2, cols2 = _margin_plan(n=32, fcol="_acct_col_a")
    dispatch(plan2, params2, cols2, op="_acct_op")
    assert kernel_stats.snapshot()["compiles"] == after["compiles"] + 1


def test_dispatch_accounting_tracks_lowering_counter():
    """The gauge's compile/hit split mirrors the REAL jit cache: a fresh
    (plan, shapes) key lowers once, repeats lower zero times."""
    from flink_ml_tpu.utils.backend import count_compiles

    plan, params, cols = _margin_plan(fcol="_lower_col_b")
    with count_compiles() as count:
        dispatch(plan, params, cols)
    assert count() == 1
    with count_compiles() as count:
        dispatch(plan, params, cols)
    assert count() == 0


def test_serving_metrics_republish_kernel_gauges():
    from flink_ml_tpu.serving.metrics import ServingMetrics

    m = ServingMetrics()
    plan, params, cols = _margin_plan(fcol="_gauge_col_c")
    dispatch(plan, params, cols)
    m.publish()
    snap = m.snapshot()
    assert snap["kernels.dispatches"] >= 1
    assert snap["kernels.compiles"] >= 1
    assert "kernels.dispatch_latency_ms" in snap


# ---------------------------------------------------------------------------
# THE cross-consumer compile-sharing guarantee
# ---------------------------------------------------------------------------

def test_one_executable_backs_serving_pipeline_and_transform():
    """Zero-new-lowerings: after a serving warm-up of the LR margins op,
    (a) the model's own transform (the training stack's predict entry —
    what fit-time evaluation and CV fold scoring call), (b) a fused
    PipelineModel plan, and (c) a hot-swapped same-shape generation all
    run on the SAME compiled executable per (op, schema, bucket)."""
    from flink_ml_tpu.utils.backend import count_compiles

    from flink_ml_tpu.api.pipeline import PipelineModel
    from flink_ml_tpu.models.classification.logisticregression import (
        LogisticRegression,
    )
    from flink_ml_tpu.serving.executor import make_servable

    rng = np.random.default_rng(7)
    X = rng.normal(size=(48, 6)).astype(np.float64)
    y = (X[:, 0] > 0).astype(np.float64)
    train = Table({"features": X, "label": y})
    model = LogisticRegression().set_max_iter(2).fit(train)
    feats = Table({"features": X})

    servable = make_servable(model, Table({"features": X[:4]}),
                             max_batch_rows=64)
    servable.warm_up()        # buckets 8..64 compile HERE

    with count_compiles() as count:
        # (a) serving steady state
        served = servable.predict(Table({"features": X[:5]}))
        # (b) the training stack's own predict entry
        offline = model.transform(feats)[0]
        # (c) the fused pipeline plan (singleton terminal segment)
        pipe = PipelineModel([model])
        fused = pipe.transform(feats)[0]
        # (d) a same-shape new generation (CV fold / delta publish)
        import copy

        gen2 = copy.deepcopy(model)
        gen2._state.coefficients = gen2._state.coefficients * 1.5
        servable.rebind(gen2).predict(Table({"features": X[:5]}))
    assert count() == 0, (
        f"{count()} new XLA lowerings after warm-up — pipelines, "
        "serving, and the predict entry no longer share one executable")
    np.testing.assert_array_equal(offline["prediction"],
                                  fused["prediction"])
    np.testing.assert_array_equal(served["prediction"],
                                  offline["prediction"][:5])


def test_training_builders_resolve_the_same_registry_entries():
    """The training-side consumers go through the SAME registry entries
    the parity matrix exercises — fn identity, not a parallel table."""
    from flink_ml_tpu.models.common import gbt
    from flink_ml_tpu.ops import ell_scatter, emb_grad

    assert lookup("ell_margin", sig=(16,), backend="xla").fn \
        is ell_scatter.ell_margin_xla_entry
    assert lookup("ell_scatter_apply", sig=(16,), backend="xla").fn \
        is ell_scatter.ell_scatter_apply_xla_entry
    assert lookup("gbt_level_histograms", backend="xla").fn \
        is gbt._level_histograms_segsum
    assert lookup("gbt_level_histograms", backend="pallas").fn \
        is gbt._level_histograms_pallas
    assert lookup("routed_table_grad", backend="xla").fn \
        is emb_grad.routed_apply_xla
    # off TPU the automatic picks are the XLA lowerings (the fallback
    # rule), and GBT's "auto" resolves through the same lookup
    if jax.default_backend() != "tpu":
        assert lookup("ell_margin", sig=(16,)).backend == "xla"
        assert gbt.resolve_hist_impl("auto") == "segsum"


# ---------------------------------------------------------------------------
# cross-backend parity matrix
# ---------------------------------------------------------------------------

def _parity_ell_margin(backends):
    from flink_ml_tpu.ops.ell_scatter import ell_layout

    rng = np.random.default_rng(3)
    d, batch, nnz = 128 * 8, 64, 4
    cat = rng.integers(0, d, size=(1, batch, nnz)).astype(np.int32)
    lay = ell_layout(cat, d)
    w = jnp.asarray(rng.normal(size=(d,)).astype(np.float32))
    m_len = 256
    outs = {}
    for b in backends:
        entry = lookup("ell_margin", sig=(int(lay.src.shape[1]),),
                       backend=b)
        kw = {} if b == "xla" else {"interpret": True,
                                    "precision": "highest"}
        outs[b] = np.asarray(entry.fn(
            w, lay.src[0], lay.pos[0], lay.mask[0], m_len=m_len, **kw))
    ref = outs.pop("xla")
    for b, got in outs.items():
        np.testing.assert_allclose(got[:batch], ref[:batch], atol=1e-5,
                                   err_msg=f"ell_margin[{b}] vs xla")


def _parity_ell_scatter_apply(backends):
    from flink_ml_tpu.ops.ell_scatter import ell_layout

    rng = np.random.default_rng(4)
    d, batch, nnz = 128 * 8, 64, 4
    cat = rng.integers(0, d, size=(1, batch, nnz)).astype(np.int32)
    lay = ell_layout(cat, d)
    w = jnp.asarray(rng.normal(size=(d,)).astype(np.float32))
    r_ext = jnp.asarray(
        np.concatenate([rng.normal(size=batch),
                        np.zeros(256 - batch)]).astype(np.float32))
    outs = {}
    for b in backends:
        entry = lookup("ell_scatter_apply", sig=(int(lay.src.shape[1]),),
                       backend=b)
        kw = {} if b == "xla" else {"interpret": True,
                                    "precision": "highest"}
        outs[b] = np.asarray(entry.fn(
            w, r_ext, lay.src[0], lay.pos[0], lay.mask[0], lr=0.3, **kw))
    ref = outs.pop("xla")
    for b, got in outs.items():
        np.testing.assert_allclose(got, ref, atol=1e-5,
                                   err_msg=f"ell_scatter_apply[{b}] vs xla")


def _parity_gbt_hist(backends):
    rng = np.random.default_rng(5)
    n, d, bins, nodes = 512, 6, 16, 4
    cols = tuple(jnp.asarray(rng.integers(0, bins, size=n), jnp.int32)
                 for _ in range(d))
    ids = jnp.asarray(rng.integers(-1, nodes, size=n), jnp.int32)
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h = jnp.asarray((rng.random(n) + 0.1).astype(np.float32))
    outs = {b: lookup("gbt_level_histograms", backend=b).fn(
        cols, ids, g, h, nodes, d, bins) for b in backends}
    gr, hr = outs.pop("xla")
    for b, (gg, hh) in outs.items():
        np.testing.assert_allclose(np.asarray(gg), np.asarray(gr),
                                   rtol=1e-4, atol=1e-5, err_msg=b)
        np.testing.assert_allclose(np.asarray(hh), np.asarray(hr),
                                   rtol=1e-4, atol=1e-5, err_msg=b)


def _parity_kmeans_update_stats(backends):
    from flink_ml_tpu.distance import DistanceMeasure
    from flink_ml_tpu.ops.kmeans_pallas import pad_correction

    rng = np.random.default_rng(6)
    n, d, k = 256, 8, 4
    pts = rng.normal(size=(n, d)).astype(np.float32)
    pts[-13:] = 0.0                       # maskless zero-pad contract
    mask = np.ones(n, np.float32)
    mask[-13:] = 0.0
    cents = pts[:k].copy()
    measure = DistanceMeasure.get_instance("euclidean")
    outs = {}
    for b in backends:
        entry = lookup("kmeans_update_stats", backend=b)
        if b == "xla":
            sums, counts = entry.fn(measure, k, jnp.asarray(pts),
                                    jnp.asarray(mask), jnp.asarray(cents))
        else:
            sums, counts = entry.fn(jnp.asarray(pts), jnp.asarray(cents),
                                    block_n=128, interpret=True)
            counts = pad_correction(counts, jnp.asarray(cents), 13)
        outs[b] = (np.asarray(sums), np.asarray(counts))
    sr, cr = outs.pop("xla")
    for b, (ss, cc) in outs.items():
        np.testing.assert_allclose(ss, sr, atol=1e-4, err_msg=b)
        np.testing.assert_allclose(cc, cr, atol=1e-5, err_msg=b)


def _parity_kmeans_workset_update(backends):
    from flink_ml_tpu.distance import DistanceMeasure

    rng = np.random.default_rng(7)
    n, d, k = 256, 8, 4
    pts = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    pm = np.ones(n, np.float32)
    pm[-9:] = 0.0
    cents = pts[:k]
    prev = jnp.asarray(rng.integers(0, k, n).astype(np.int32))
    act = jnp.asarray((rng.random(n) < 0.5).astype(np.float32))
    measure = DistanceMeasure.get_instance("euclidean")
    outs = {}
    for b in backends:
        entry = lookup("kmeans_workset_update", backend=b)
        if b == "xla":
            outs[b] = entry.fn(measure, k, pts, cents, prev, act,
                               jnp.asarray(pm))
        else:
            outs[b] = entry.fn(pts, cents, prev, act, jnp.asarray(pm),
                               block_n=128, interpret=True)
    a_r, db_r, ds_r, s_r, c_r = [np.asarray(x) for x in outs.pop("xla")]
    for b, got in outs.items():
        a, db, ds, s, c = [np.asarray(x) for x in got]
        # per-row outputs are expression-identical -> bitwise
        np.testing.assert_array_equal(a, a_r, err_msg=b)
        np.testing.assert_array_equal(db, db_r, err_msg=b)
        np.testing.assert_array_equal(ds, ds_r, err_msg=b)
        # stats accumulate tile-sequentially -> f32-order equivalent
        np.testing.assert_allclose(s, s_r, rtol=1e-5, atol=1e-5,
                                   err_msg=b)
        np.testing.assert_allclose(c, c_r, atol=1e-5, err_msg=b)


def _parity_routed_table_grad(backends):
    from flink_ml_tpu.ops.emb_grad import emb_grad_route

    rng = np.random.default_rng(8)
    batch, fields, vocab, E = 64, 4, 40, 3
    cat = rng.integers(0, vocab, size=(1, batch, fields))
    cat[0, :40, 0] = 5                    # heavy run -> fold_passes > 0
    route = emb_grad_route(cat, vocab)
    g = jnp.asarray(rng.normal(size=(batch * fields, E)).astype(np.float32))
    outs = {}
    for b in backends:
        entry = lookup("routed_table_grad", sig=route.kernel_sig(),
                       backend=b)
        kw = {} if b == "xla" else {"interpret": True}
        outs[b] = np.asarray(entry.fn(route, g, *route.step_slice(0), **kw))
    ref = outs.pop("xla")
    for b, got in outs.items():
        # the fused fold's shift-add tree is element-identical: bitwise
        np.testing.assert_array_equal(got, ref, err_msg=b)


def _adam_case(n, e, block_n, *, touched=40, full_block=None,
               empty_block=None, pads=24, seed=9):
    """A table of ``n`` rows (``e`` 0: the scalar wide table) with Adam
    state from an earlier step, and the run sums of one step: ``touched``
    random rows, every row of block ``full_block``, none of block
    ``empty_block``, then ``pads`` padded entries (ids ``n + rank``, zero
    sums) as ``emb_grad_route`` writes them."""
    rng = np.random.default_rng(seed)
    shape = (n, e) if e else (n,)
    block = np.arange(n) // block_n
    ids = rng.choice(np.flatnonzero(block != empty_block), touched,
                     replace=False)
    if full_block is not None:
        ids = np.union1d(ids, np.flatnonzero(block == full_block))
    ids = np.sort(ids)
    out_ids = np.concatenate([ids, n + np.arange(pads)]).astype(np.int32)
    sums = rng.normal(size=(out_ids.size,) + shape[1:]).astype(np.float32)
    sums[ids.size:] = 0.0
    p = rng.normal(size=shape).astype(np.float32)
    m, v = np.zeros(shape, np.float32), np.zeros(shape, np.float32)
    hist = rng.choice(n, n // 3, replace=False)   # rows with a history
    m[hist] = 0.1 * rng.normal(size=(hist.size,) + shape[1:])
    v[hist] = np.square(m[hist])
    return ids, tuple(map(jnp.asarray, (p, m, v))), jnp.asarray(sums), \
        jnp.asarray(out_ids)


_ADAM = dict(lr=1e-2, b1=0.9, b2=0.999, eps=1e-8)


def _adam_backends(block_n):
    return {"xla": lookup("routed_adam_update", backend="xla").fn,
            "pallas": partial(
                lookup("routed_adam_update", backend="pallas").fn,
                block_n=block_n, interpret=True)}


def _parity_routed_adam_update(backends):
    assert sorted(backends) == ["pallas", "xla"]
    _, state, sums, out_ids = _adam_case(700, 16, 128, full_block=1,
                                         empty_block=3)
    outs = {b: fn(*state, sums, out_ids, jnp.int32(3), **_ADAM)
            for b, fn in _adam_backends(128).items()}
    for got, ref, name in zip(outs["pallas"], outs["xla"], "pmv"):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-6, atol=1e-7, err_msg=name)


# Each case names what its table and its step's ids hold: a last block of
# 60 of 128 rows, a block no id falls in, a block with an id in every row,
# padded ids behind the real ones, a table of one block, both payload
# widths (``e`` 0 is the wide table's vector of scalars).
@pytest.mark.parametrize("n,e,block_n,kw", [
    pytest.param(700, 16, 128, {}, id="ragged-last-block"),
    pytest.param(700, 16, 128, {"empty_block": 2}, id="untouched-block"),
    pytest.param(700, 16, 128, {"full_block": 4},
                 id="block-touched-in-every-row"),
    pytest.param(700, 16, 128, {"full_block": 5},
                 id="ragged-block-touched-in-every-row"),
    pytest.param(640, 16, 128, {"pads": 0}, id="whole-blocks-no-padding"),
    pytest.param(700, 16, 128, {"touched": 1, "pads": 300},
                 id="one-row-many-pads"),
    pytest.param(100, 16, 128, {"touched": 30}, id="table-of-one-block"),
    pytest.param(3000, 16, 256,
                 {"touched": 1500, "full_block": 0, "empty_block": 7},
                 id="segments-pass-a-window"),
    pytest.param(700, 8, 128, {"full_block": 1}, id="width-8"),
    pytest.param(700, 0, 128, {"full_block": 1, "empty_block": 3},
                 id="wide-scalars"),
    pytest.param(3000, 0, 1024, {"touched": 900},
                 id="wide-scalars-large-block"),
])
def test_routed_adam_update_fused_equals_its_xla_backend(n, e, block_n, kw):
    """Two consecutive steps of op ``routed_adam_update``, the fused pass
    in interpret mode against the XLA composition, p, m and v each.  Step
    2 touches nothing, so a row step 1 touched keeps its momentum tail
    and moves again.  A row with no history that neither step touches is
    its start, bit for bit, on both backends."""
    ids, state, sums, out_ids = _adam_case(n, e, block_n, **kw)
    start = [np.asarray(x) for x in state]
    outs = {}
    for b, fn in _adam_backends(block_n).items():
        s1 = fn(*state, sums, out_ids, jnp.int32(1), **_ADAM)
        s2 = fn(*s1, jnp.zeros_like(sums), out_ids, jnp.int32(2), **_ADAM)
        outs[b] = [np.asarray(x) for x in s1 + s2]
    for got, ref, name in zip(outs["pallas"], outs["xla"],
                              ("p1", "m1", "v1", "p2", "m2", "v2")):
        np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-7,
                                   err_msg=name)
    p0, m0, v0 = start
    idle = np.setdiff1d(np.flatnonzero(
        (m0.reshape(n, -1) == 0).all(1) & (v0.reshape(n, -1) == 0).all(1)),
        ids)
    assert idle.size
    fresh = np.setdiff1d(ids, np.flatnonzero((m0.reshape(n, -1) != 0).any(1)))
    for b, (p1, m1, v1, p2, m2, v2) in outs.items():
        for got, was in ((p1, p0), (m1, m0), (v1, v0), (p2, p0), (m2, m0),
                         (v2, v0)):
            np.testing.assert_array_equal(got[idle], was[idle], err_msg=b)
        # the momentum tail: touched in step 1, idle in step 2, moved twice
        assert np.all(m2[fresh] == np.float32(0.9) * m1[fresh]), b
        assert np.all(p2[fresh] != p1[fresh]), b
        assert np.all(p1[ids] != p0[ids]), b


def test_routed_adam_update_is_planned_for_narrow_tables_only():
    """The fused pass takes the widths whose transposed view is the array
    the chip holds; the wide table's scalars and a table as wide as a
    lane tile stay with the XLA composition."""
    fused = lookup("routed_adam_update", backend="pallas")
    assert [fused.supports_sig((1000, e)) for e in (0, 4, 8, 16, 64, 128)] \
        == [False, False, True, True, True, False]
    assert lookup("routed_adam_update", sig=(1000, 0)).backend == "xla"


def test_routed_adam_update_xla_is_optax_adam_on_the_scattered_gradient():
    """The oracle's own oracle: the ``xla`` backend against ``optax.adam``
    handed the table-shaped gradient, same step count."""
    import optax

    ids, (p, m, v), sums, out_ids = _adam_case(700, 16, 128, full_block=1)
    g = jnp.zeros_like(p).at[out_ids].set(sums, mode="drop")
    opt = optax.adam(_ADAM["lr"])
    state = opt.init(p)
    state = (state[0]._replace(count=jnp.int32(4), mu=m, nu=v), *state[1:])
    updates, state = opt.update(g, state, p)
    got = lookup("routed_adam_update", backend="xla").fn(
        p, m, v, sums, out_ids, state[0].count, **_ADAM)
    for a, b, name in zip(got, (optax.apply_updates(p, updates),
                                state[0].mu, state[0].nu), "pmv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-8, err_msg=name)


# -- als_cholesky_solve: a tile of groups' systems solved inside VMEM --------

def _als_systems(rank, groups, seed=0):
    """``groups`` symmetric positive definite systems the way an ALS block
    holds them: Gram matrices of a few more rows than ``rank`` plus a
    ridge.  ``(A, b)`` as float32, ``(groups, rank, rank)`` and
    ``(groups, rank)``."""
    rng = np.random.default_rng(seed)
    y = rng.normal(size=(groups, rank + 3, rank)).astype(np.float32)
    A = np.einsum("gls,glt->gst", y, y) + np.float32(0.5) * np.eye(
        rank, dtype=np.float32)
    return A, rng.normal(size=(groups, rank)).astype(np.float32)


def _als_solve(backend, A, b, **kw):
    """Op ``als_cholesky_solve`` on lane-major operands, back as
    ``(groups, rank)``."""
    fn = lookup("als_cholesky_solve", backend=backend).fn
    return np.asarray(fn(jnp.transpose(jnp.asarray(A), (2, 1, 0)),
                         jnp.asarray(b).T, **kw)).T


def _parity_als_cholesky_solve(backends):
    assert sorted(backends) == ["pallas", "xla"]
    A, b = _als_systems(12, 200)
    np.testing.assert_allclose(_als_solve("pallas", A, b, interpret=True),
                               _als_solve("xla", A, b), rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("groups", [128, 300], ids=["one-tile",
                                                    "partial-last-tile"])
@pytest.mark.parametrize("rank", [8, 100])
def test_als_cholesky_solve_vmem_equals_float64_and_its_xla_backend(
        rank, groups):
    """The kernel in interpret mode against ``np.linalg.solve`` in
    float64 and against the XLA loop: the same recurrence in float32,
    another order of summation.  300 groups are two whole tiles and 44
    lanes of a third."""
    A, b = _als_systems(rank, groups, seed=rank + groups)
    exact = np.linalg.solve(A.astype(np.float64),
                            b.astype(np.float64)[..., None])[..., 0]
    scale = np.abs(exact).max(axis=1, keepdims=True)
    got = _als_solve("pallas", A, b, interpret=True)
    twin = _als_solve("xla", A, b)
    assert got.shape == twin.shape == (groups, rank)
    assert np.max(np.abs(got - exact) / scale) < 2e-5
    assert np.max(np.abs(twin - exact) / scale) < 2e-5
    assert np.max(np.abs(got - twin) / scale) < 2e-5


def test_als_cholesky_solve_vmem_keeps_a_failed_factorisation_in_its_lane():
    """One matrix that is not positive definite among sound ones: NaN in
    ITS solution on both backends (ALS then keeps that group's factors),
    its neighbours on the lanes beside it as exact as without it."""
    A, b = _als_systems(8, 300, seed=5)
    bad = 170
    A[bad] = -A[bad]
    got = _als_solve("pallas", A, b, interpret=True)
    twin = _als_solve("xla", A, b)
    sound = np.arange(len(A)) != bad
    assert np.isnan(got[bad]).all() and np.isnan(twin[bad]).all()
    exact = np.linalg.solve(A[sound].astype(np.float64),
                            b[sound].astype(np.float64)[..., None])[..., 0]
    assert np.max(np.abs(got[sound] - exact)
                  / np.abs(exact).max(axis=1, keepdims=True)) < 2e-5


def test_als_cholesky_solve_is_planned_for_a_lane_tile_of_groups_on_a_tpu(
        monkeypatch):
    """Off the TPU every block takes the XLA loop; on one the kernel takes
    a block of a lane tile of groups or more at a rank whose tile fits
    VMEM, and the split groups' one system a step stays with XLA."""
    vmem = lookup("als_cholesky_solve", backend="pallas")
    assert [vmem.supports_sig(sig) for sig in (
        (100, 30020), (100, 128), (32, 5000), (8, 128), (256, 128),
        (100, 127), (100, 1), (264, 4096))] == [True] * 5 + [False] * 3
    assert lookup("als_cholesky_solve", sig=(100, 30020)).backend == "xla"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert lookup("als_cholesky_solve", sig=(100, 30020)).backend == "pallas"
    assert lookup("als_cholesky_solve", sig=(100, 1)).backend == "xla"
    with pytest.raises(ValueError, match="does not support"):
        lookup("als_cholesky_solve", sig=(100, 1), backend="pallas")


# -- accuracy-envelope harnesses (int8 backends, ISSUE 18) ------------------
# Int8 entries are weight-only quantized: bitwise equality with f32 is
# NOT the contract — rank-order/decision agreement within the envelope
# (>= 99% on these fixtures) is.  Each harness quantizes the f32 params
# through the publish-time recipe and forces both backends explicitly
# (the int8 entry's availability gate refuses auto-pick by design).

ENVELOPE = 0.99


def _rank_corr(a, b):
    ra = np.argsort(np.argsort(a)).astype(np.float64)
    rb = np.argsort(np.argsort(b)).astype(np.float64)
    return float(np.corrcoef(ra, rb)[0, 1])


def _parity_linear_margins(backends):
    from flink_ml_tpu.kernels.quantize import quantize_stage_params

    rng = np.random.default_rng(9)
    X = rng.normal(size=(512, 16)).astype(np.float32)
    params = {"w": rng.normal(size=(16,)).astype(np.float32),
              "b": np.float32(0.1)}
    outs = {}
    for b in backends:
        p = quantize_stage_params("linear_margins", params) \
            if b == "int8" else params
        outs[b] = np.asarray(
            lookup("linear_margins", backend=b).fn(("f", "m"), p,
                                                   {"f": X})["m"])
    ref = outs.pop("xla")
    for b, got in outs.items():
        agree = float(np.mean((got > 0) == (ref > 0)))
        assert agree >= ENVELOPE, \
            f"linear_margins[{b}] decision agreement {agree} vs xla"
        corr = _rank_corr(got, ref)
        assert corr >= ENVELOPE, \
            f"linear_margins[{b}] margin rank correlation {corr}"


def _parity_kmeans_assign(backends):
    from flink_ml_tpu.distance import DistanceMeasure
    from flink_ml_tpu.kernels.quantize import quantize_stage_params

    rng = np.random.default_rng(10)
    pts = rng.normal(size=(512, 8)).astype(np.float32)
    params = {"centroids": rng.normal(size=(7, 8)).astype(np.float32)}
    measure = DistanceMeasure.get_instance("euclidean")
    static = ("f", "a", measure)
    outs = {}
    for b in backends:
        p = quantize_stage_params("kmeans_assign", params) \
            if b == "int8" else params
        outs[b] = np.asarray(
            lookup("kmeans_assign", backend=b).fn(static, p,
                                                  {"f": pts})["a"])
    ref = outs.pop("xla")
    for b, got in outs.items():
        agree = float(np.mean(got == ref))
        assert agree >= ENVELOPE, \
            f"kmeans_assign[{b}] assignment agreement {agree} vs xla"


def _parity_widedeep_scores(backends):
    from flink_ml_tpu.kernels.quantize import quantize_stage_params
    from flink_ml_tpu.models.recommendation.widedeep import (
        _field_offsets,
        init_params,
    )

    rng = np.random.default_rng(11)
    vocab = (17, 23)
    net = init_params(rng, 4, vocab, 8, (16,))
    net["wide_cat"] = (rng.normal(size=net["wide_cat"].shape) * 0.1
                       ).astype(np.float32)
    net["wide_dense"] = (rng.normal(size=net["wide_dense"].shape) * 0.1
                         ).astype(np.float32)
    params = {"net": net, "offsets": _field_offsets(vocab)}
    dense = rng.normal(size=(512, 4)).astype(np.float32)
    cat = np.stack([rng.integers(0, v, size=512) for v in vocab],
                   axis=1).astype(np.int32)
    cols = {"d": dense, "c": cat}
    outs = {}
    for b in backends:
        p = quantize_stage_params("widedeep_scores", params) \
            if b == "int8" else params
        outs[b] = np.asarray(
            lookup("widedeep_scores", backend=b).fn(("d", "c", "s"), p,
                                                    cols)["s"])
    ref = outs.pop("xla")
    for b, got in outs.items():
        agree = float(np.mean((got > 0.5) == (ref > 0.5)))
        assert agree >= ENVELOPE, \
            f"widedeep_scores[{b}] decision agreement {agree} vs xla"
        corr = _rank_corr(got, ref)
        assert corr >= ENVELOPE, \
            f"widedeep_scores[{b}] score rank correlation {corr}"


# -- retrieve harnesses (ISSUE 19) ------------------------------------------
# The fused scan+top-k stage promises BITWISE agreement between backends:
# both run under jit (eager XLA makes different fma-contraction choices
# than the plan jit does, so the harness compares like-for-like), and the
# shared pq_lut/decode helpers carry a runtime-1.0 rounding pin so
# fusion-cluster shape cannot reorder the float graph.  Parity alone is
# NOT enough for a nearest-neighbor kernel — two backends can agree
# bit-for-bit on a wrong answer — so every retrieve backend must ALSO
# clear two quality gates of its own: exact agreement with a float64
# brute-force oracle at nprobe == nlist, and the recall envelope
# (recall@10 >= 0.95 at the reference nprobe while provably scanning
# <= 25% of the corpus).  The coverage gate below makes a backend missing
# EITHER harness fail this file by construction.

import functools

RECALL_ENVELOPE = 0.95      # recall@10 floor at the reference nprobe
SCAN_BUDGET = 0.25          # ... while scanning at most this corpus slice


@functools.lru_cache(maxsize=None)
def _retrieve_fixture(kind):
    """(index, queries) fixtures per shape class, built once per run."""
    from flink_ml_tpu.retrieval import IVFIndex, PQConfig

    rng = np.random.default_rng(19)
    # flat fixtures sit at the Pallas scan's DMA alignment (dim and block
    # multiples of 128 — ops/retrieve_pallas.py::fused_supported): the
    # shape class the registry plans it for on a chip
    if kind == "flat-small":        # continuous data: full-probe oracle
        X = rng.normal(size=(600, 128)).astype(np.float32)
        idx = IVFIndex.build(X, nlist=8, k=10, nprobe=4, seed=1, block=256)
        q = rng.normal(size=(16, 128)).astype(np.float32)
    elif kind == "pq-small":
        X = rng.normal(size=(600, 32)).astype(np.float32)
        idx = IVFIndex.build(X, nlist=8, k=10, nprobe=4, seed=1,
                             pq=PQConfig(m=8, ksub=16))
        q = rng.normal(size=(16, 32)).astype(np.float32)
    elif kind == "clustered":       # separated modes: the recall op point
        centers = rng.normal(size=(64, 128)).astype(np.float32) * 10.0
        assign = rng.integers(0, 64, size=2048)
        X = (centers[assign]
             + rng.normal(size=(2048, 128)) * 0.5).astype(np.float32)
        idx = IVFIndex.build(X, nlist=64, k=10, nprobe=8, seed=2,
                             block=128)
        pick = rng.choice(2048, size=32, replace=False)
        q = (X[pick] + rng.normal(size=(32, 128)) * 0.05).astype(np.float32)
    else:
        raise AssertionError(kind)
    return idx, q


def _retrieve_backend_run(index, queries, backend, *, nprobe=None):
    """Run ONE backend's retrieve stage the way production runs it: under
    jit (interpret mode for the TPU backend on CPU hosts)."""
    from flink_ml_tpu.retrieval.ivf import _DIST_STAGE, _NN_STAGE

    idx = index if nprobe is None else index.with_options(nprobe=nprobe)
    entry = lookup("retrieve", sig=idx.sig(), backend=backend)
    static = idx._static()
    params = {k: jnp.asarray(v) for k, v in idx.params.items()}
    cols = {idx.query_col: jnp.asarray(queries)}
    if backend.startswith("pallas"):
        out = entry.fn(static, params, cols, interpret=True)
    else:
        out = jax.jit(lambda p, c: entry.fn(static, p, c))(params, cols)
    return np.asarray(out[_NN_STAGE]), np.asarray(out[_DIST_STAGE])


def _parity_retrieve(backends):
    # one fused backend per scan kind, each against the XLA stage
    fused_of = {"flat-small": "pallas", "pq-small": "pallas-pq"}
    assert set(backends) == {"xla", *fused_of.values()}, backends
    for kind, fused in fused_of.items():
        idx, q = _retrieve_fixture(kind)
        outs = {b: _retrieve_backend_run(idx, q, b) for b in (fused, "xla")}
        nn_ref, d_ref = outs.pop("xla")
        for b, (nn, d) in outs.items():
            np.testing.assert_array_equal(
                nn, nn_ref, err_msg=f"{kind}[{b}] neighbor ids")
            # the fused contract: candidate distances never re-round
            # differently per backend — BITWISE, not approx
            np.testing.assert_array_equal(
                d.view(np.uint32), d_ref.view(np.uint32),
                err_msg=f"{kind}[{b}] distance bits")


def _retrieve_oracle(backend):
    """Brute-force oracle: at nprobe == nlist the index scans everything,
    so the neighbor sets must EQUAL the float64 exact scan's (continuous
    data — ties have measure zero)."""
    from flink_ml_tpu.retrieval import exact_neighbors

    idx, q = _retrieve_fixture("flat-small")
    ids, X = idx.stored_vectors()
    expect = exact_neighbors(q, X, ids, idx.k)
    nn, dist = _retrieve_backend_run(idx, q, backend, nprobe=idx.nlist)
    np.testing.assert_array_equal(nn, expect, err_msg=f"oracle[{backend}]")
    assert np.all(np.diff(dist, axis=1) >= 0), "distances not ascending"


def _retrieve_recall(backend):
    """Recall envelope: recall@10 >= 0.95 at the index's reference nprobe
    while the probed lists hold <= 25% of the corpus (asserted from the
    real posting-list counts, not assumed)."""
    from flink_ml_tpu.retrieval import exact_neighbors, recall_at_k

    idx, q = _retrieve_fixture("clustered")
    frac = idx.scan_fraction(q)
    assert frac <= SCAN_BUDGET, f"scan fraction {frac} over budget"
    ids, X = idx.stored_vectors()
    expect = exact_neighbors(q, X, ids, idx.k)
    nn, _ = _retrieve_backend_run(idx, q, backend)
    rec = recall_at_k(nn, expect)
    assert rec >= RECALL_ENVELOPE, (
        f"recall[{backend}] {rec} at nprobe={idx.nprobe} "
        f"(scan fraction {frac})")


#: both quality gates, keyed for the parametrized matrix below
_RETRIEVE_QUALITY = {"oracle": _retrieve_oracle, "recall": _retrieve_recall}

#: every retrieve backend the registry can select by itself must be
#: listed here — the harnesses above run per backend, so listing IS
#: coverage
_RETRIEVE_BACKENDS = ("pallas", "xla")


def test_every_retrieve_backend_has_quality_harnesses():
    """ISSUE 19 coverage gate: a retrieve backend registered without BOTH
    the brute-force-oracle harness and the recall-envelope harness fails
    by construction.  A forced-lookup-only backend (the parked PQ scan)
    ships behind no plan and is exempt until it is un-parked."""
    regd = {b for b in kreg.backends("retrieve")
            if kreg.lookup("retrieve", backend=b).forced_only is None}
    missing = regd - set(_RETRIEVE_BACKENDS)
    assert not missing, (
        f"retrieve backend(s) {sorted(missing)} registered without "
        "oracle+recall quality harnesses — add them to "
        "_RETRIEVE_BACKENDS and make both gates pass")
    stale = set(_RETRIEVE_BACKENDS) - regd
    assert not stale, f"_RETRIEVE_BACKENDS lists unregistered {sorted(stale)}"


@pytest.mark.parametrize("backend", _RETRIEVE_BACKENDS)
@pytest.mark.parametrize("gate", sorted(_RETRIEVE_QUALITY))
def test_retrieve_quality_gates(gate, backend):
    _RETRIEVE_QUALITY[gate](backend)


_PARITY = {
    "als_cholesky_solve": _parity_als_cholesky_solve,
    "ell_margin": _parity_ell_margin,
    "ell_scatter_apply": _parity_ell_scatter_apply,
    "gbt_level_histograms": _parity_gbt_hist,
    "kmeans_assign": _parity_kmeans_assign,
    "kmeans_update_stats": _parity_kmeans_update_stats,
    "kmeans_workset_update": _parity_kmeans_workset_update,
    "linear_margins": _parity_linear_margins,
    "retrieve": _parity_retrieve,
    "routed_adam_update": _parity_routed_adam_update,
    "routed_table_grad": _parity_routed_table_grad,
    "widedeep_scores": _parity_widedeep_scores,
}


def test_every_multi_backend_op_has_a_parity_harness():
    """Coverage gate: registering a second backend for an op WITHOUT
    adding its parity harness here fails loudly — an unverified kernel
    must not ship behind the registry's automatic selection."""
    for op in kreg.ops():
        if op.startswith("_"):
            continue
        if len(kreg.backends(op)) > 1:
            assert op in _PARITY, (
                f"op {op} grew a second backend with no parity harness")


@pytest.mark.parametrize("op", sorted(_PARITY))
def test_parity_matrix(op):
    backends = kreg.backends(op)
    if len(backends) < 2:
        pytest.skip(f"{op} has one backend")
    assert "xla" in backends, f"{op} lost its XLA fallback"
    _PARITY[op](list(backends))


# ---------------------------------------------------------------------------
# padding contract
# ---------------------------------------------------------------------------

def test_shared_block_padding_contract():
    from flink_ml_tpu.utils.padding import (
        pad_rows_to_block,
        require_block_rows,
    )

    arrs, n = pad_rows_to_block((np.ones((10, 3)), np.arange(10)), 8)
    assert n == 10 and arrs[0].shape[0] == 16 and arrs[1].shape[0] == 16
    assert np.all(arrs[0][10:] == 0.0) and np.all(arrs[1][10:] == 0)
    require_block_rows(16, 8, op="t")                  # divisible: fine
    with pytest.raises(ValueError, match="pad_rows_to_block"):
        require_block_rows(10, 8, op="t")


def test_kmeans_pallas_raises_shared_contract_error():
    from flink_ml_tpu.ops.kmeans_pallas import kmeans_update_stats

    pts = jnp.ones((100, 4), jnp.float32)
    cents = jnp.ones((2, 4), jnp.float32)
    with pytest.raises(ValueError, match="pad_rows_to_block"):
        kmeans_update_stats(pts, cents, block_n=64, interpret=True)


# ---------------------------------------------------------------------------
# registry-resolved training paths stay value-correct end to end
# ---------------------------------------------------------------------------

def test_forced_xla_ell_builder_matches_default_on_cpu():
    """On a non-TPU host the registry's automatic pick IS the XLA
    lowering, so the default-resolved builder and the forced-"xla"
    builder must be the same computation."""
    from flink_ml_tpu.models.common.losses import logistic_loss
    from flink_ml_tpu.models.common.sgd import SGDConfig, _mixed_update_ell
    from flink_ml_tpu.ops.ell_scatter import ell_layout

    if jax.default_backend() == "tpu":
        pytest.skip("CPU-resolution test")
    rng = np.random.default_rng(11)
    d, batch, nnz = 128 * 4, 32, 3
    cat = rng.integers(0, d, size=(1, batch, nnz)).astype(np.int32)
    lay = ell_layout(cat, d)
    dense = rng.normal(size=(batch, 2)).astype(np.float32)
    y = rng.integers(0, 2, size=batch).astype(np.float32)
    wb = np.ones(batch, np.float32)
    cfg = SGDConfig(learning_rate=0.3, tol=0)
    params = {"w": jnp.zeros(d, jnp.float32), "b": jnp.zeros((), jnp.float32)}
    args = (jnp.asarray(dense), lay.src[0], lay.pos[0], lay.mask[0],
            lay.ovf_idx[0], lay.ovf_src[0], lay.heavy_idx[0],
            lay.heavy_cnt[0], jnp.asarray(y), jnp.asarray(wb))
    auto, _ = _mixed_update_ell(logistic_loss, cfg)(params, *args)
    forced, _ = _mixed_update_ell(logistic_loss, cfg, backend="xla")(
        params, *args)
    np.testing.assert_array_equal(np.asarray(auto["w"]),
                                  np.asarray(forced["w"]))


def test_workset_fused_body_matches_xla_body_in_interpret():
    """The fused workset body (what a TPU fit plans) drives the SAME
    convergence as the XLA body: same rounds, same final centroids to
    f32 summation order, same exit — interpret mode standing in for the
    chip."""
    from flink_ml_tpu.distance import DistanceMeasure
    from flink_ml_tpu.iteration import IterationConfig, iterate
    from flink_ml_tpu.models.clustering.kmeans import (
        FitPlan,
        kmeans_workset_epoch_step,
    )

    rng = np.random.default_rng(12)
    n, d, k = 256, 6, 3
    pts = rng.normal(size=(n, d)).astype(np.float32)
    pts[:n // 3] += 4.0
    pts[n // 3: 2 * n // 3] -= 4.0
    mask = jnp.ones((n,), jnp.float32)
    init = jnp.asarray(pts[:k].copy())
    measure = DistanceMeasure.get_instance("euclidean")
    plan = FitPlan("xla", None, 1, "first_row", k, d)

    results = {}
    for name, body in (
            ("xla", kmeans_workset_epoch_step(measure, k)),
            ("fused", kmeans_workset_epoch_step(measure, k, block_n=128,
                                                interpret=True))):
        results[name] = iterate(
            body, init, (jnp.asarray(pts), mask), max_epochs=40,
            workset=plan.init_workset(mask),
            workset_tol=0.0,
            config=IterationConfig(mode="fused"))
    assert results["fused"].num_epochs == results["xla"].num_epochs
    np.testing.assert_allclose(np.asarray(results["fused"].state),
                               np.asarray(results["xla"].state),
                               rtol=1e-5, atol=1e-5)
