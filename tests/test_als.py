"""ALS matrix factorization tests (CPU mesh; fused iterate path)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ml_tpu import Table
from flink_ml_tpu.models.recommendation import ALS, ALSModel


def _synthetic(n_users=40, n_items=30, rank=4, density=0.5, seed=0,
               noise=0.0):
    rng = np.random.default_rng(seed)
    U = rng.normal(size=(n_users, rank)) / np.sqrt(rank)
    V = rng.normal(size=(n_items, rank)) / np.sqrt(rank)
    full = U @ V.T
    mask = rng.random((n_users, n_items)) < density
    u, i = np.nonzero(mask)
    r = full[u, i] + noise * rng.normal(size=len(u))
    return Table({"user": u.astype(np.int64), "item": i.astype(np.int64),
                  "rating": r.astype(np.float64)}), full


def test_param_defaults():
    als = ALS()
    assert als.get_rank() == 10
    assert als.get_reg_param() == pytest.approx(0.1)
    assert not als.get_implicit_prefs()
    assert als.get_user_col() == "user"
    assert als.get_rating_col() == "rating"


def test_explicit_recovers_low_rank_matrix():
    table, full = _synthetic()
    # rank 6 > true rank 4: at the exact rank ALS can stall in an
    # init-dependent local minimum (rmse ~0.06 for some seeds); mild
    # overparameterization makes recovery seed-robust (verified over seeds).
    als = (ALS().set_rank(6).set_max_iter(20).set_reg_param(1e-3)
           .set_seed(1))
    model = als.fit(table)
    out = model.transform(table)[0]
    pred = np.asarray(out["prediction"])
    rmse = np.sqrt(np.mean((pred - np.asarray(table["rating"])) ** 2))
    assert rmse < 0.02, rmse
    # held-out entries of the low-rank matrix are recovered too
    uh, ih = np.meshgrid(np.arange(full.shape[0]), np.arange(full.shape[1]),
                         indexing="ij")
    held = model.transform(Table({
        "user": uh.ravel().astype(np.int64),
        "item": ih.ravel().astype(np.int64)}))[0]
    rmse_all = np.sqrt(np.nanmean(
        (np.asarray(held["prediction"]).reshape(full.shape) - full) ** 2))
    assert rmse_all < 0.15, rmse_all


def test_rmse_decreases_with_iterations():
    table, _ = _synthetic(noise=0.01, seed=3)
    truth = np.asarray(table["rating"])

    def rmse_after(iters):
        m = (ALS().set_rank(4).set_max_iter(iters).set_reg_param(0.01)
             .set_seed(2).fit(table))
        p = np.asarray(m.transform(table)[0]["prediction"])
        return np.sqrt(np.mean((p - truth) ** 2))

    assert rmse_after(10) < rmse_after(1)


def test_implicit_prefs_ranks_observed_above_unobserved():
    rng = np.random.default_rng(7)
    n_users, n_items = 30, 20
    # two taste groups: users prefer even or odd items
    u, i, r = [], [], []
    for user in range(n_users):
        group = user % 2
        for item in range(group, n_items, 2):
            if rng.random() < 0.7:
                u.append(user); i.append(item); r.append(1.0 + rng.random())
    table = Table({"user": np.asarray(u, np.int64),
                   "item": np.asarray(i, np.int64),
                   "rating": np.asarray(r, np.float64)})
    model = (ALS().set_implicit_prefs(True).set_alpha(10.0).set_rank(4)
             .set_reg_param(0.05).set_max_iter(10).set_seed(0).fit(table))
    users = np.repeat(np.arange(n_users, dtype=np.int64), n_items)
    items = np.tile(np.arange(n_items, dtype=np.int64), n_users)
    scores = np.asarray(model.transform(Table({
        "user": users, "item": items}))[0]["prediction"])
    scores = scores.reshape(n_users, n_items)
    same = np.array([[1.0 if (it % 2) == (us % 2) else 0.0
                      for it in range(n_items)] for us in range(n_users)])
    # mean score for in-group items must clearly beat out-of-group
    assert (scores * same).sum() / same.sum() > \
        (scores * (1 - same)).sum() / (1 - same).sum() + 0.2


def test_cold_start_predicts_nan():
    table, _ = _synthetic()
    model = ALS().set_rank(3).set_max_iter(3).fit(table)
    out = model.transform(Table({
        "user": np.asarray([0, 10**6], np.int64),
        "item": np.asarray([0, 0], np.int64)}))[0]
    pred = np.asarray(out["prediction"])
    assert np.isfinite(pred[0])
    assert np.isnan(pred[1])


def test_save_load_round_trip(tmp_path):
    table, _ = _synthetic(n_users=12, n_items=9)
    model = ALS().set_rank(3).set_max_iter(5).set_seed(4).fit(table)
    p1 = np.asarray(model.transform(table)[0]["prediction"])
    model.save(str(tmp_path / "m"))
    re = ALSModel.load(str(tmp_path / "m"))
    p2 = np.asarray(re.transform(table)[0]["prediction"])
    np.testing.assert_allclose(p1, p2)
    assert re.get_prediction_col() == model.get_prediction_col()


def test_estimator_save_load_round_trip(tmp_path):
    als = ALS().set_rank(7).set_implicit_prefs(True).set_alpha(2.5)
    als.save(str(tmp_path / "e"))
    re = ALS.load(str(tmp_path / "e"))
    assert re.get_rank() == 7
    assert re.get_implicit_prefs()
    assert re.get_alpha() == pytest.approx(2.5)


def test_negative_ratings_rejected_for_implicit():
    table = Table({"user": np.asarray([0], np.int64),
                   "item": np.asarray([0], np.int64),
                   "rating": np.asarray([-1.0])})
    with pytest.raises(ValueError):
        ALS().set_implicit_prefs(True).fit(table)


def test_unobserved_users_keep_factors_finite():
    # user ids with gaps: all factor rows must stay finite (singular normal
    # equations guarded)
    table = Table({"user": np.asarray([0, 0, 5, 5], np.int64),
                   "item": np.asarray([0, 1, 0, 1], np.int64),
                   "rating": np.asarray([1.0, 2.0, 3.0, 4.0])})
    model = ALS().set_rank(2).set_max_iter(4).fit(table)
    data = model.get_model_data()[0]
    assert np.isfinite(np.asarray(data["userFactors"][0])).all()
    assert np.isfinite(np.asarray(data["itemFactors"][0])).all()


def test_zero_reg_singular_solve_keeps_finite_factors():
    # regParam=0 with fewer ratings than rank: the singular solve must not
    # poison the factors with NaN (regression).
    table = Table({"user": np.asarray([0, 0, 1], np.int64),
                   "item": np.asarray([0, 1, 0], np.int64),
                   "rating": np.asarray([1.0, 2.0, 3.0])})
    model = ALS().set_rank(4).set_reg_param(0.0).set_max_iter(3).fit(table)
    pred = np.asarray(model.transform(table)[0]["prediction"])
    assert np.isfinite(pred).all()


def test_empty_ratings_rejected():
    table = Table({"user": np.asarray([], np.int64),
                   "item": np.asarray([], np.int64),
                   "rating": np.asarray([], np.float64)})
    with pytest.raises(ValueError, match="at least one rating"):
        ALS().fit(table)


def test_implicit_fractional_weights_consistent():
    # The implicit normal equations must weight A and b consistently:
    # duplicating a rating must equal doubling its weight.
    import jax.numpy as jnp

    from flink_ml_tpu.models.recommendation.als import _solve_side

    rng = np.random.default_rng(0)
    V = jnp.asarray(rng.normal(size=(3, 2)).astype(np.float32))
    prev = jnp.asarray(np.zeros((2, 2), np.float32))
    u = jnp.asarray([0, 0, 1], jnp.int32)
    i = jnp.asarray([0, 1, 2], jnp.int32)
    r = jnp.asarray([1.0, 2.0, 1.5], jnp.float32)
    dup = _solve_side(prev, V, jnp.concatenate([u, u[:1]]),
                      jnp.concatenate([i, i[:1]]),
                      jnp.concatenate([r, r[:1]]),
                      jnp.ones(4, jnp.float32), 2, 0.1, True, 2.0)
    wt = _solve_side(prev, V, u, i, r,
                     jnp.asarray([2.0, 1.0, 1.0], jnp.float32), 2, 0.1,
                     True, 2.0)
    np.testing.assert_allclose(np.asarray(dup), np.asarray(wt), atol=1e-5)


def test_recommend_for_users_topk_and_exclude():
    """recommend_for_users: matmul top-k, train-pair exclusion, and the
    RankingEvaluator-consumable output shape."""
    users = np.repeat(np.arange(8), 5)
    items = np.tile(np.arange(5), 8)
    # user u loves item u % 5 (rating 5), others 1
    ratings = np.where(items == (users % 5), 5.0, 1.0)
    t = Table({"user": users, "item": items, "rating": ratings})
    model = (ALS().set_rank(4).set_max_iter(10).set_reg_param(0.05)
             .fit(t))

    recs = model.recommend_for_users(np.arange(8), k=2)
    assert recs.num_rows == 8
    for u in range(8):
        top = recs["recommendations"][u]
        assert len(top) == 2
        # rank-4 factorization is approximate: the loved item must at
        # least make the top 2, and scores come back ranked
        assert (u % 5) in top
        scores = recs["scores"][u]
        assert scores[0] >= scores[1]

    # every user rated ALL 5 items, so excluding the training
    # interactions leaves nothing to recommend: lists come back EMPTY
    # (excluded items are removed, never padded back in)
    excl = model.recommend_for_users(np.arange(8), k=5, exclude=t)
    for u in range(8):
        assert excl["recommendations"][u] == []
        assert excl["scores"][u] == []

    # partial exclusion: drop only item (u % 5); it must vanish from the
    # list while the rest stay ranked
    part = model.recommend_for_users(
        np.arange(8), k=5,
        exclude=Table({"user": np.arange(8), "item": np.arange(8) % 5}))
    for u in range(8):
        got = part["recommendations"][u]
        assert len(got) == 4 and (u % 5) not in got
        s = part["scores"][u]
        assert all(s[i] >= s[i + 1] for i in range(len(s) - 1))

    with pytest.raises(ValueError, match="unknown user"):
        model.recommend_for_users([999], k=1)
    with pytest.raises(ValueError, match="positive"):
        model.recommend_for_users([0], k=0)


def _long_tailed(seed=41, n_groups=40, n_other=30, nnz=3000):
    """A long-tailed rating set: most groups small, one of 700 ratings
    (several blocks, or several parts, at the block sizes the tests pass),
    one of ONE rating, one EMPTY (the last), a tenth of the weights 0."""
    rng = np.random.default_rng(seed)
    g = (rng.pareto(0.7, nnz) * 3).astype(np.int64) % (n_groups - 1)
    g[:700] = 3
    g = np.concatenate([g[g != 12], [12]])
    o = rng.integers(0, n_other, len(g)).astype(np.int32)
    r = rng.normal(size=len(g)).astype(np.float32)
    w = np.where(rng.random(len(g)) < 0.1, 0.0, 1.0).astype(np.float32)
    return g, o, r, w


def _plain_normal_equations(Y, g, o, r, w, n_groups, implicit, alpha):
    """The per-group sums, one rating at a time, in float64."""
    Y = Y.astype(np.float64)
    rank = Y.shape[1]
    A = np.zeros((n_groups, rank, rank))
    b = np.zeros((n_groups, rank))
    cnt = np.zeros(n_groups)
    for k in range(len(g)):
        y = Y[o[k]]
        if implicit:
            aw = alpha * abs(r[k]) * w[k]
            bw = w[k] + aw
        else:
            aw, bw = w[k], w[k] * r[k]
        A[g[k]] += aw * np.outer(y, y)
        b[g[k]] += bw * y
        cnt[g[k]] += w[k]
    return A, b, cnt


# (block_groups, block_slots): one block; many blocks and the long group
# split into parts of 64 slots; blocks of three groups, parts of 256
_BLOCKS = [(None, None), (4, 64), (3, 256)]


@pytest.mark.parametrize("implicit", [False, True])
@pytest.mark.parametrize("block_groups,block_slots", _BLOCKS)
def test_grouped_normal_equations_match_plain_sums(block_groups, block_slots,
                                                   implicit):
    """The grouped form's ``A``, ``b`` and ``cnt`` against the plain
    per-group sums in float64, explicit AND implicit, on a long-tailed
    set with a group of one rating, a group that spans several blocks (or
    is split into parts) and an empty group, and zero-weight ratings."""
    import jax.numpy as jnp

    from flink_ml_tpu.models.recommendation.als import (
        GroupedPlan, grouped_normal_equations)

    n_groups, n_other, rank = 40, 30, 5
    g, o, r, w = _long_tailed()
    r = np.abs(r) if implicit else r
    Y = np.random.default_rng(1).normal(size=(n_other, rank)).astype(
        np.float32)
    plan = GroupedPlan(g, n_groups, rank, block_groups, block_slots)
    if block_slots:
        assert plan.parts > 1 and plan.blocks > 1
    A1, b1, c1 = grouped_normal_equations(
        jnp.asarray(Y), plan, plan.arrays(o, r, w), implicit, 0.7)
    A0, b0, c0 = _plain_normal_equations(Y, g, o, r, w, n_groups, implicit,
                                         0.7)
    assert c0[-1] == 0 and np.count_nonzero(g == 12) == 1
    np.testing.assert_allclose(np.asarray(A1), A0, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(b1), b0, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(c1), c0, rtol=0, atol=0)


@pytest.mark.parametrize("implicit", [False, True])
@pytest.mark.parametrize("block_groups,block_slots", _BLOCKS)
def test_grouped_half_epoch_matches_scatter(block_groups, block_slots,
                                            implicit):
    """One half-epoch: the grouped form's blocks, formed and solved one
    at a time by the Cholesky with the groups on the lanes, against the
    scatter form's one batched ``cho_solve``; the empty group keeps its
    factors in both."""
    import jax
    import jax.numpy as jnp

    from flink_ml_tpu.models.recommendation.als import (
        GroupedPlan, _solve_side, _solve_side_grouped)

    n_groups, n_other, rank = 40, 30, 5
    g, o, r, w = _long_tailed(seed=7)
    r = np.abs(r) if implicit else r
    rng = np.random.default_rng(2)
    Y = jnp.asarray(rng.normal(size=(n_other, rank)).astype(np.float32))
    prev = jnp.asarray(rng.normal(size=(n_groups, rank)).astype(np.float32))
    plan = GroupedPlan(g, n_groups, rank, block_groups, block_slots)
    arrays = jax.tree_util.tree_map(jnp.asarray, plan.arrays(o, r, w))
    with jax.default_matmul_precision("highest"):
        grouped = _solve_side_grouped(prev, Y, plan, arrays, 0.05, implicit,
                                      0.7)
        scatter = _solve_side(prev, Y, jnp.asarray(g, jnp.int32),
                              jnp.asarray(o), jnp.asarray(r), jnp.asarray(w),
                              n_groups, 0.05, implicit, 0.7)
    np.testing.assert_array_equal(np.asarray(grouped)[-1],
                                  np.asarray(prev)[-1])
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(scatter),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("implicit", [False, True])
def test_grouped_fit_matches_scatter_fit(implicit):
    """End-to-end: ``normalEquationsImpl='sorted'`` (the grouped form)
    reproduces the scatter fit's factors allclose, explicit and
    implicit, and each model says which form its fit planned."""
    rng = np.random.default_rng(42)
    n = 1500
    users = rng.integers(0, 40, n).astype(np.int64)
    items = rng.integers(0, 25, n).astype(np.int64)
    ratings = (np.sin(users * 0.3) + np.cos(items * 0.5)
               + 0.05 * rng.normal(size=n)).astype(np.float32)
    t = Table({"user": users, "item": items,
               "rating": np.abs(ratings) if implicit else ratings})

    def fit(impl):
        return (ALS().set_rank(6).set_max_iter(4).set_seed(0)
                .set_implicit_prefs(implicit).set(ALS.NEQ_IMPL, impl)
                .fit(t))

    m_grouped, m_scatter = fit("sorted"), fit("scatter")
    assert (m_grouped.neq_plan, m_scatter.neq_plan) == ("grouped", "scatter")
    (a,), (b,) = m_grouped.get_model_data(), m_scatter.get_model_data()
    for col in ("userFactors", "itemFactors"):
        np.testing.assert_allclose(np.asarray(a[col]), np.asarray(b[col]),
                                   rtol=5e-3, atol=5e-3)


def _parent_cholesky_solve_lanes(A, b):
    """PR 33's ``als._cholesky_solve_lanes``, word for word: what the
    grouped form called before the solve became registry op
    ``als_cholesky_solve``."""
    rank = A.shape[-1]
    At = jnp.transpose(A, (2, 1, 0))         # At[k, i] = A[i, k]: column k
    index = jnp.arange(rank)[:, None]

    def factor(j, L):
        row = jax.lax.dynamic_index_in_dim(L, j, 1, keepdims=False)
        col = (jax.lax.dynamic_index_in_dim(At, j, 0, keepdims=False)
               - jnp.sum(L * row[:, None, :], axis=0))
        pivot = jnp.sqrt(jax.lax.dynamic_index_in_dim(col, j, 0))
        col = jnp.where(index >= j, col / pivot, 0.0)
        return jax.lax.dynamic_update_index_in_dim(L, col, j, 0)

    L = jax.lax.fori_loop(0, rank, factor, jnp.zeros_like(At))

    def forward(j, y):
        col = jax.lax.dynamic_index_in_dim(L, j, 0, keepdims=False)
        yj = (jax.lax.dynamic_index_in_dim(y, j, 0)
              / jax.lax.dynamic_index_in_dim(col, j, 0))
        return jnp.where(index > j, y - col * yj,
                         jnp.where(index == j, yj, y))

    def backward(t, x):
        j = rank - 1 - t
        col = jax.lax.dynamic_index_in_dim(L, j, 0, keepdims=False)
        below = jnp.sum(jnp.where(index > j, col * x, 0.0), axis=0,
                        keepdims=True)
        xj = ((jax.lax.dynamic_index_in_dim(x, j, 0) - below)
              / jax.lax.dynamic_index_in_dim(col, j, 0))
        return jax.lax.dynamic_update_index_in_dim(x, xj, j, 0)

    y = jax.lax.fori_loop(0, rank, forward, b.T)
    return jax.lax.fori_loop(0, rank, backward, y).T


def test_grouped_fit_solves_with_the_parents_code_off_the_tpu(monkeypatch):
    """Off the TPU a grouped fit's blocks take op ``als_cholesky_solve``'s
    ``xla`` backend, which is the parent's solver moved: the span note
    ``solve`` and ``ALSModel.solve_plan`` say so, and the factors are
    those of a fit that calls the parent's function, bit for bit.
    ``solve_plan`` is a record of the fit like ``neq_plan``: a loaded
    model has none."""
    import types

    from flink_ml_tpu.models.recommendation import als as als_mod
    from flink_ml_tpu.obs.trace import tracer

    rng = np.random.default_rng(44)
    n = 4000
    t = Table({"user": rng.integers(0, 300, n).astype(np.int64),
               "item": rng.integers(0, 40, n).astype(np.int64),
               "rating": rng.normal(size=n).astype(np.float32)})
    est = (ALS().set_rank(6).set_max_iter(3).set_seed(1)
           .set(ALS.NEQ_IMPL, "sorted"))
    tracer.enable()
    try:
        model = est.fit(t)
        (span,) = tracer.find("fit.arrange.plan")
    finally:
        tracer.disable()
        tracer.clear()
    assert (span.ids["neq_plan"], span.ids["solve"]) == ("grouped", "xla")
    assert (model.neq_plan, model.solve_plan) == ("grouped", "xla")
    assert est.set(ALS.NEQ_IMPL, "scatter").fit(t).solve_plan == "xla"

    monkeypatch.setattr(
        als_mod, "_block_solve", lambda rank, groups: types.SimpleNamespace(
            backend="xla", fn=lambda At, bt: _parent_cholesky_solve_lanes(
                jnp.transpose(At, (2, 1, 0)), bt.T).T))
    parent = est.set(ALS.NEQ_IMPL, "sorted").fit(t)
    (a,), (b,) = model.get_model_data(), parent.get_model_data()
    for col in ("userFactors", "itemFactors"):
        np.testing.assert_array_equal(np.asarray(a[col]), np.asarray(b[col]))


def test_solve_plan_is_not_saved_with_the_model(tmp_path):
    rng = np.random.default_rng(45)
    t = Table({"user": rng.integers(0, 30, 400).astype(np.int64),
               "item": rng.integers(0, 20, 400).astype(np.int64),
               "rating": rng.normal(size=400).astype(np.float32)})
    model = ALS().set_rank(4).set_max_iter(2).fit(t)
    assert (model.neq_plan, model.solve_plan) == ("scatter", "xla")
    model.save(str(tmp_path / "m"))
    loaded = ALSModel.load(str(tmp_path / "m"))
    assert (loaded.neq_plan, loaded.solve_plan) == (None, None)
    np.testing.assert_array_equal(
        np.asarray(loaded.transform(t)[0]["prediction"]),
        np.asarray(model.transform(t)[0]["prediction"]))


def test_auto_takes_the_grouped_form_where_a_side_outgrows_a_block(
        monkeypatch):
    """'auto' keeps the scatter form while both sides' dense
    ``(n_groups, rank, rank)`` fit one block, and takes the grouped form
    as soon as one does not (long-tail data: every user one rating) — the
    block is known from the rank and the device before any plan is
    built."""
    from flink_ml_tpu.models.recommendation import als as als_mod

    rng = np.random.default_rng(43)
    n = 600
    t = Table({"user": np.arange(n).astype(np.int64),
               "item": rng.integers(0, 20, n).astype(np.int64),
               "rating": rng.normal(size=n).astype(np.float32)})
    est = ALS().set_rank(4).set_max_iter(2).set_seed(0)
    small = est.fit(t)
    assert small.neq_plan == "scatter"
    monkeypatch.setattr(als_mod, "_block_sizes", lambda rank: (256, 1024))
    large = est.fit(t)
    assert large.neq_plan == "grouped"
    np.testing.assert_allclose(
        np.asarray(large.get_model_data()[0]["userFactors"]),
        np.asarray(small.get_model_data()[0]["userFactors"]),
        rtol=1e-3, atol=1e-4)


def _plan_cases():
    rng = np.random.default_rng(0)
    cases = []
    for _ in range(8):
        n_groups = int(rng.integers(1, 500))
        nnz = int(rng.integers(1, 5000))
        cases.append(rng.integers(0, n_groups, nnz))       # uniform
        cases.append((rng.pareto(0.5, nnz) * 10).astype(np.int64)
                     % n_groups)                           # long tail
    cases.append(np.zeros(300, np.int64))                  # single group
    cases.append(np.arange(300))                           # all singletons
    return cases


@pytest.mark.parametrize("block_groups,block_slots",
                         [(7, 64), (64, 512), (8192, 1 << 21)])
def test_grouped_plan_places_every_rating_once(block_groups, block_slots):
    """The plan's arithmetic over uniform, long-tailed, single-group and
    all-singleton sets: every rating has a slot of its own inside its
    group's run, every block has one shape within the block sizes, a
    group is whole in one class or split into parts, and what the plan
    says of its padding is what its slots hold."""
    from flink_ml_tpu.models.recommendation.als import (GroupedPlan,
                                                        _padded_lengths)

    for g in _plan_cases():
        n_groups = int(g.max()) + 2                        # the last empty
        plan = GroupedPlan(g, n_groups, 4, block_groups, block_slots)
        counts = np.bincount(g, minlength=n_groups)
        assert len(np.unique(plan.slot)) == len(g) == plan.nnz
        assert plan.slot.min() >= 0 and plan.slot.max() < plan.slots
        # each class rounds its share of a block up
        assert plan.block_groups < block_groups + len(plan.classes)
        whole, split = plan.arrays(np.zeros(len(g)), g + 1.0,
                                   np.ones(len(g)))
        # a weight of 1 a rating, made from the counts alone, is the same
        for a, b in zip(jax.tree_util.tree_leaves((whole, split)),
                        jax.tree_util.tree_leaves(
                            plan.arrays(np.zeros(len(g)), g + 1.0)),
                        strict=True):
            np.testing.assert_array_equal(a, b)
        seen = np.zeros(n_groups, np.int64)
        for (o, r, w, rows), c in zip(whole, plan.classes, strict=True):
            assert c.length % 8 == 0 and c.groups * c.length <= block_slots
            assert o.shape == (plan.blocks, c.groups * c.length)
            r = r.reshape(plan.blocks, c.groups, c.length)
            w = w.reshape(r.shape)
            real = rows < n_groups
            # a group's slots hold its own ratings and nothing else
            assert ((r == 0) | (r == rows[:, :, None] + 1.0)).all()
            assert (w.sum(axis=2)[real] == counts[rows[real]]).all()
            assert (w.sum(axis=2)[~real] == 0).all()
            assert (_padded_lengths(counts[rows[real]]) == c.length).all()
            np.add.at(seen, rows[real], 1)
        if plan.parts:
            o, r, w, rows, first, last = split
            assert o.shape == (plan.parts, block_slots)
            assert ((r == 0) | (r == rows + 1.0)).all()
            assert first.sum() == last.sum() == len(np.unique(rows))
            assert (counts[rows[:, 0]] > block_slots).all()
            np.add.at(seen, rows[last, 0], 1)
        assert (seen == (counts > 0)).all()
        assert plan.padded_share == pytest.approx(
            1.0 - len(g) / plan.slots)


@pytest.mark.parametrize("host_threads", [2, 4, 6, 16])
@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("block_groups,block_slots",
                         [(7, 64), (64, 512), (8192, 1 << 21)])
def test_native_placement_equals_the_numpy_form(monkeypatch, block_groups,
                                                block_slots, weighted,
                                                host_threads):
    """``native/als_plan.cpp`` against the NumPy form it stands in for:
    every leaf of ``plan.arrays(...)`` the same in value, dtype and shape,
    over the sets of the test above (split groups at the small blocks) and
    sets of one and of three ratings, which have fewer ratings than the
    pass has threads; with and without a weights column; at 1, 2, 3 and 8
    threads (a side takes half of ``_HOST_THREADS``)."""
    from flink_ml_tpu.models.recommendation import als as als_mod

    lib = als_mod._native_plan()
    assert lib is not None
    monkeypatch.setattr(als_mod, "_HOST_THREADS", host_threads)
    for g in _plan_cases() + [np.array([3]), np.array([0, 0, 5])]:
        rng = np.random.default_rng(len(g))
        other = rng.integers(0, 1 << 20, len(g))
        ratings = rng.normal(size=len(g)).astype(np.float32)
        weights = (rng.random(len(g)).astype(np.float32) if weighted
                   else None)
        plan = als_mod.GroupedPlan(g, int(g.max()) + 2, 4, block_groups,
                                   block_slots)
        monkeypatch.setattr(als_mod, "_native_plan", lambda: None)
        want = jax.tree_util.tree_leaves(
            plan.arrays(other, ratings, weights))
        monkeypatch.setattr(als_mod, "_native_plan", lambda: lib)
        got = jax.tree_util.tree_leaves(plan.arrays(other, ratings, weights))
        for a, b in zip(got, want, strict=True):
            assert (a.dtype, a.shape) == (b.dtype, b.shape)
            np.testing.assert_array_equal(a, b)


def test_native_placement_refuses_what_the_lay_out_cannot_hold():
    """``als_place`` checks what it is given before it writes: a group
    index outside the lay-out and a group whose ratings would run past the
    last slot are errors, not writes out of bounds."""
    from flink_ml_tpu.models.recommendation import als as als_mod

    g = np.array([0, 1, 1, 2])
    plan = als_mod.GroupedPlan(g, 3, 4, 8, 64)
    lib = als_mod._native_plan()
    assert lib is not None
    plan._group_idx = np.array([0, 1, 1, 3])
    with pytest.raises(RuntimeError, match="als_place failed with 1"):
        plan.arrays(g, g + 1.0)
    plan._group_idx, plan._slot0 = g, plan._slot0 + plan.slots - 1
    with pytest.raises(RuntimeError, match="als_place failed with 2"):
        plan.arrays(g, g + 1.0)
    with pytest.raises(ValueError, match="one value a rating"):
        als_mod.GroupedPlan(g, 3, 4, 8, 64).arrays(g[:3], g + 1.0)


def _fit_and_its_plan_span(est, table, name="fit.arrange.plan"):
    """The notes of the fit's span ``name``, and the model's columns."""
    from flink_ml_tpu.obs.trace import tracer

    tracer.enable()
    try:
        model = est.fit(table)
        (span,) = tracer.find(name)
    finally:
        tracer.disable()
        tracer.clear()
    (data,) = model.get_model_data()
    return span.ids, {col: np.asarray(data[col]) for col in
                      ("userIds", "itemIds", "userFactors", "itemFactors")}


def _grouped_als():
    return (ALS().set_rank(6).set_max_iter(3).set_seed(1)
            .set(ALS.NEQ_IMPL, "sorted"))


def _ratings_table(seed=46, n=4000):
    rng = np.random.default_rng(seed)
    return Table({"user": rng.integers(0, 300, n).astype(np.int64),
                  "item": rng.integers(0, 40, n).astype(np.int64),
                  "rating": rng.normal(size=n).astype(np.float32)})


def test_grouped_fit_places_natively_and_says_so():
    """Guard against a silent fall back: where the library loads (this
    machine has ``make``), a grouped fit's span ``fit.arrange.plan`` notes
    ``placed_native`` 1; a scatter fit places nothing and notes 0."""
    from flink_ml_tpu.models.recommendation import als as als_mod

    assert als_mod._native_plan() is not None
    ids, _ = _fit_and_its_plan_span(_grouped_als(), _ratings_table())
    assert (ids["neq_plan"], ids["placed_native"]) == ("grouped", 1)
    ids, _ = _fit_and_its_plan_span(ALS().set_rank(4).set_max_iter(1),
                                    _ratings_table(n=400))
    assert (ids["neq_plan"], ids["placed_native"]) == ("scatter", 0)


def test_fit_without_the_library_gives_the_same_model(monkeypatch):
    """A machine with no ``make`` and no built library: the loader gives
    ``None``, the plan places in NumPy, the span says ``placed_native`` 0
    and the model is the native fit's to the bit."""
    from flink_ml_tpu.models.recommendation import als as als_mod

    table = _ratings_table(seed=47)
    native_ids, native = _fit_and_its_plan_span(_grouped_als(), table)
    monkeypatch.setattr(als_mod, "_native_plan", lambda: None)
    numpy_ids, numpy_ = _fit_and_its_plan_span(_grouped_als(), table)
    assert (native_ids["placed_native"], numpy_ids["placed_native"]) == (1, 0)
    for col, value in native.items():
        np.testing.assert_array_equal(value, numpy_[col])


def _labels(case):
    """A label column of the named kind."""
    rng = np.random.default_rng(5)
    pool = rng.integers(1, 1 << 40, size=5000)
    if case.startswith("pool"):
        return pool[rng.integers(0, len(pool), size=int(case.split("_")[1]))]
    n = (1 << 20) + 77
    if case == "one_label":
        return np.full(n, -3, np.int64)
    if case == "all_distinct":
        return rng.permutation(np.arange(n, dtype=np.int64) * 7919 - (1 << 40))
    if case == "extremes":
        info = np.iinfo(np.int64)
        return np.array([info.min, info.max, 0, -1, -(1 << 40), 5])[
            rng.integers(0, 6, size=n)]
    if case == "int32":
        return pool[rng.integers(0, len(pool), size=n)].astype(np.int32)
    if case == "float":
        return pool[rng.integers(0, len(pool), size=n)] / 3.0
    raise ValueError(case)


@pytest.mark.parametrize("case,threads,native", [
    ("pool_1000", 8, False),
    (f"pool_{(1 << 20) - 1}", 8, False),
    (f"pool_{1 << 20}", 8, True),
    (f"pool_{(1 << 20) + 77}", 8, True),
    ("one_label", 8, True),
    ("all_distinct", 8, True),
    ("extremes", 8, True),
    ("int32", 8, True),
    ("float", 8, False),
    # parts of 149,808 rows, the seventh of 149,805
    (f"pool_{(1 << 20) + 77}", 7, True),
    (f"pool_{(1 << 20) + 77}", 1, True),
])
def test_index_labels_is_np_unique_with_inverse(monkeypatch, case, threads,
                                                native):
    """The index of a label column gives ``np.unique(...,
    return_inverse=True)``'s ids and positions, in value and dtype: a
    long integer column by the native pass (every int64 a label, the
    extremes and 0 too; one label or all distinct; int32 widened into it
    and kept), a long float column a part a thread in NumPy, a short column
    ``np.unique`` itself; at 8 threads, at 7 (the last part shorter than
    the others) and at 1."""
    from flink_ml_tpu.models.recommendation import als as als_mod

    assert als_mod._native_plan() is not None
    monkeypatch.setattr(als_mod, "_HOST_THREADS", threads)
    labels = _labels(case)
    ids, index, was_native = als_mod._index_labels(labels)
    want_ids, want_index = np.unique(labels, return_inverse=True)
    assert was_native == native
    assert (ids.dtype, index.dtype) == (want_ids.dtype, want_index.dtype)
    assert index.shape == want_index.shape
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_array_equal(index, want_index)


def test_fit_indexes_natively_and_says_so(monkeypatch):
    """The span ``fit.gather.index`` notes ``native`` 1 where both columns
    took the native pass and 0 where the machine has no library; the
    grouped fit's plan arrays and its model are the same to the bit under
    both forms (the columns count as long here)."""
    from flink_ml_tpu.models.recommendation import als as als_mod

    monkeypatch.setattr(als_mod, "_THREADED_LABELS", 1000)
    planned = {}
    planned_side = als_mod._planned_side

    def recorded_side(*side):    # the two sides plan on threads of their own
        planned[side[2]] = planned_side(*side)
        return planned[side[2]]

    monkeypatch.setattr(als_mod, "_planned_side", recorded_side)
    table = _ratings_table(seed=48)
    runs = []
    for lib in (als_mod._native_plan(), None):
        monkeypatch.setattr(als_mod, "_native_plan", lambda lib=lib: lib)
        planned.clear()
        ids, model = _fit_and_its_plan_span(_grouped_als(), table,
                                            "fit.gather.index")
        runs.append((ids["native"], jax.tree_util.tree_leaves(
            [planned[n][1] for n in sorted(planned)]), model))
    (native, native_arrays, native_model), (numpy_, numpy_arrays,
                                            numpy_model) = runs
    assert (native, numpy_) == (1, 0)
    for a, b in zip(native_arrays, numpy_arrays, strict=True):
        assert (a.dtype, a.shape) == (b.dtype, b.shape)
        np.testing.assert_array_equal(a, b)
    for col, value in native_model.items():
        np.testing.assert_array_equal(value, numpy_model[col])


def _benchmark_module(kind, name):
    """``benchmarks/<kind>/<name>.py``: the benchmark's plain reference
    and generator import nothing of the program."""
    import importlib
    import os
    import sys

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    return importlib.import_module(f"{kind}.{name}")


@pytest.mark.parametrize("impl", ["sorted", "scatter"])
def test_fit_matches_the_plain_reference_and_the_bf16_control_does_not(impl):
    """``ALS.fit`` against ``benchmarks/references/als_wr.py`` (the dense
    masked normal equations, LU solves, its own ids and start) at the
    benchmark cell's rehearsal sizes, rank 16, within the cell's limits;
    the reference with its contractions at one bf16 pass is not."""
    files = _benchmark_module("harness", "files")
    _, config = files.cell("als_netflix.fit", rehearsal=True)
    seed = 2147483777
    data = files.generate(config, seed)
    reference = _benchmark_module("references", "als_wr")
    model = (ALS().set_rank(config["rank"])
             .set_reg_param(config["reg_param"])
             .set_max_iter(config["max_iter"]).set_seed(seed)
             .set(ALS.NEQ_IMPL, impl).fit(Table(data)))
    (table,) = model.get_model_data()
    answer = {name: np.asarray(table[name][0])
              for name in table.column_names}
    numbers = reference.compare(config, data, answer, seed)
    for name, limit in config["limits"].items():
        assert numbers[name] <= limit, (name, numbers)
    control = reference.compare(
        config, data, reference.control(config, data, seed), seed)
    assert any(control[name] > limit
               for name, limit in config["limits"].items()), control


# -- workset (delta-iteration) fit, ISSUE 9 ----------------------------------

def test_workset_fit_converges_early_and_tracks_bsp():
    """worksetTol > 0: users/items whose neighborhoods settled skip their
    solves, the fused while_loop exits as soon as every movement falls
    below the threshold (strictly before maxIter), and the factors stay
    within threshold-scale distance of the BSP fit."""
    table, _ = _synthetic(noise=0.01, seed=2)
    kw = dict(rank=4, max_iter=60, reg=1e-2, seed=5)

    def build(**extra):
        est = (ALS().set_rank(kw["rank"]).set_max_iter(kw["max_iter"])
               .set_reg_param(kw["reg"]).set_seed(kw["seed"]))
        for name, v in extra.items():
            getattr(est, f"set_{name}")(v)
        return est

    base = build().fit(table)
    est = build(workset_tol=1e-4)
    model = est.fit(table)

    rep = est.last_workset_report
    assert rep["rounds"] < kw["max_iter"]        # convergence-driven exit
    assert rep["rounds"] == len(rep["active_fraction"])
    assert rep["active_fraction"][-1] == 0.0     # both masks drained
    # the skip rule shrinks the workset before it drains (some round
    # solved strictly fewer than all groups)
    assert rep["active_fraction"].min() == 0.0
    assert np.any((rep["active_fraction"] > 0)
                  & (rep["active_fraction"] < 1))

    pb = base.transform(table)[0]["prediction"]
    pw = model.transform(table)[0]["prediction"]
    np.testing.assert_allclose(pw, pb, atol=5e-3)


def test_workset_tol_param_defaults_and_validation():
    assert ALS().get_workset_tol() == 0.0
    assert ALS().set_workset_tol(1e-3).get_workset_tol() == 1e-3
    with pytest.raises(Exception):
        ALS().set_workset_tol(-1.0)


def test_workset_zero_tol_is_plain_bsp_fit():
    """worksetTol=0 (the default) must take the classic path — bitwise
    identical to a fit that never heard of worksets."""
    table, _ = _synthetic(seed=4)
    a = (ALS().set_rank(4).set_max_iter(8).set_seed(3)).fit(table)
    b = (ALS().set_rank(4).set_max_iter(8).set_seed(3)
         .set_workset_tol(0.0)).fit(table)
    np.testing.assert_array_equal(
        a.get_model_data()[0]["userFactors"][0],
        b.get_model_data()[0]["userFactors"][0])


# ---------------------------------------------------------------------------
# the epoch body's program key (iteration/body.py: with_program_key)
# ---------------------------------------------------------------------------

def _ratings(seed, n=4000, users=300, items=40):
    rng = np.random.default_rng(seed)
    return Table({"user": rng.integers(0, users, n).astype(np.int64),
                  "item": rng.integers(0, items, n).astype(np.int64),
                  "rating": rng.normal(size=n).astype(np.float32)})


def _factors(model):
    (data,) = model.get_model_data()
    return [np.asarray(data[c]).tobytes()
            for c in ("userFactors", "itemFactors")]


@pytest.mark.parametrize("neq", ["sorted", "scatter"])
def test_a_second_fit_of_one_table_reuses_the_firsts_program(
        neq,
        fit_noting_reuse):
    """Two fresh estimators, one table, one process: the second fit's
    body states the first's program key (each side's ``PlanShape``, the
    scalars, the solves' backends), so its dispatch enqueues the kept
    executable, and the model is the first's bit for bit."""
    table = _ratings(44)

    def est():
        return (ALS().set_rank(6).set_max_iter(3).set_seed(1)
                .set(ALS.NEQ_IMPL, neq))

    first, reused_first = fit_noting_reuse(est(), table)
    second, reused_second = fit_noting_reuse(est(), table)
    assert (reused_first, reused_second) == (0, 1)
    assert first.neq_plan == ("grouped" if neq == "sorted" else "scatter")
    assert _factors(second) == _factors(first)


@pytest.mark.parametrize("what", ["degrees", "reg", "alpha_implicit"])
def test_another_histogram_or_scalar_is_another_program(
        what,
        fit_noting_reuse):
    """The same counts of users, items and ratings under another degree
    histogram are other classes, hence another program; so is one scalar
    the trace bakes in."""
    table = _ratings(44)
    est = (ALS().set_rank(6).set_max_iter(3).set_seed(1)
           .set(ALS.NEQ_IMPL, "sorted"))
    _, reused = fit_noting_reuse(est, table)
    assert reused == 0
    if what == "degrees":
        other = _ratings(45)
        assert len(np.unique(other["user"])) == len(np.unique(table["user"]))
        table = other
    elif what == "reg":
        est = est.set_reg_param(0.3)
    else:
        table = Table({"user": table["user"], "item": table["item"],
                       "rating": np.abs(table["rating"])})
        _, reused = fit_noting_reuse(est, table)
        assert reused == 1               # the ratings' values are data
        est = est.set_implicit_prefs(True).set_alpha(2.0)
    _, reused = fit_noting_reuse(est, table)
    assert reused == 0
    _, reused = fit_noting_reuse(est, table)
    assert reused == 1


def test_a_solve_the_registry_answers_otherwise_is_not_served_the_kept_program(
        monkeypatch,
        fit_noting_reuse):
    """The trace reads the block solve from the registry, not from its
    arguments, so the key names what the registry answered: a fit of
    equal shapes under another solve builds its own program."""
    import types

    from flink_ml_tpu.models.recommendation import als as als_mod

    table = _ratings(44)
    est = (ALS().set_rank(6).set_max_iter(3).set_seed(1)
           .set(ALS.NEQ_IMPL, "sorted"))
    first, _ = fit_noting_reuse(est, table)
    real = als_mod._block_solve

    def halved(rank, groups):
        solve = real(rank, groups)
        return types.SimpleNamespace(
            backend=solve.backend, fn=lambda At, bt: 0.5 * solve.fn(At, bt))

    monkeypatch.setattr(als_mod, "_block_solve", halved)
    other, reused = fit_noting_reuse(est, table)
    assert reused == 0
    assert _factors(other) != _factors(first)
    monkeypatch.setattr(als_mod, "_block_solve", real)
    again, reused = fit_noting_reuse(est, table)
    assert reused == 1 and _factors(again) == _factors(first)


def test_the_epoch_body_holds_a_plans_shape_and_not_the_plan():
    """``GroupedPlan.shape`` is all the body's program follows from: small,
    hashable, equal for equal lay-outs, and the body refuses factors of
    another rank than the plans' (the key names the solves at that
    rank)."""
    from flink_ml_tpu.models.recommendation import als as als_mod

    g = np.random.default_rng(5).integers(0, 50, 700)
    plans = [als_mod.GroupedPlan(g, 51, 4, 8, 64) for _ in range(2)]
    shape = plans[0].shape
    assert shape == plans[1].shape and hash(shape) == hash(plans[1].shape)
    assert (shape.n_groups, shape.rank, shape.parts) == (51, 4, plans[0].parts)
    assert shape.classes == tuple((c.length, c.groups)
                                  for c in plans[0].classes)
    assert shape.block_groups == plans[0].block_groups
    assert all(not isinstance(x, np.ndarray)
               for x in jax.tree_util.tree_leaves(tuple(shape)))
    body = als_mod.als_epoch_step(51, 51, 0.1, False, 1.0,
                                  plans=(shape, shape))
    assert body.program_key == als_mod.als_epoch_step(
        51, 51, 0.1, False, 1.0, plans=(plans[1].shape,) * 2).program_key
    assert body.__closure__ is not None and not any(
        isinstance(c.cell_contents, als_mod.GroupedPlan)
        for c in body.__closure__)
    arrays = plans[0].arrays(g, np.ones(700, np.float32))
    wrong = (jnp.zeros((51, 5)), jnp.zeros((51, 5)))
    with pytest.raises(ValueError, match="rank"):
        body(wrong, 0, (arrays, arrays))
