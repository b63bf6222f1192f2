"""Mosaic compile + XLA-twin parity for every kernel the registry can
select by itself on a TPU, plus the forced-lookup entries: the three
int8 kernels, and the parked fold kernel as expected
failures that turn into errors the day they pass.  Most shapes are the
smallest each kernel supports, so a failure there is a compiler/runtime
break, never an OOM or capacity artifact; the
``full-width`` cases repeat the kernels of the Criteo LR step and the
KMeans fit at the block sizes ``chip_smoke.py`` runs them at."""

import jax
import numpy as np
import pytest

pytestmark = pytest.mark.tpu

#: every (op, backend) the registry auto-selects on a TPU and the test
#: that compiles it here; test_every_auto_selected_entry_is_covered keeps
#: this equal to the registry, so a new Pallas entry cannot land untested
COVERED = {
    ("als_cholesky_solve", "pallas"): "test_als_cholesky_solve_on_device",
    ("ell_margin", "pallas"): "test_ell_margin_kernel_parity",
    ("ell_scatter_apply", "pallas"): "test_ell_fused_gather_kernel_parity",
    ("ell_scatter_apply", "pallas-pair"):
        "test_ell_scatter_mixed_kernel_parity",
    ("kmeans_update_stats", "pallas"): "test_kmeans_kernel_parity",
    ("kmeans_workset_update", "pallas"): "test_kmeans_workset_kernel_parity",
    ("retrieve", "pallas"): "test_retrieve_flat_kernel_parity",
    ("routed_adam_update", "pallas"):
        "test_routed_adam_update_under_heavy_skew_on_device",
}


def test_every_auto_selected_entry_is_covered(tpu):
    from flink_ml_tpu.kernels import registry

    auto = set()
    for op in registry.ops():
        for backend in registry.backends(op):
            entry = registry.lookup(op, backend=backend)
            if backend != "xla" and entry.is_available():
                auto.add((op, backend))
    assert auto == set(COVERED), (
        f"uncovered: {sorted(auto - set(COVERED))}; "
        f"stale: {sorted(set(COVERED) - auto)}")
    for name in COVERED.values():
        assert name in globals(), name


# smallest supported table (one 128-row grid block), and the Criteo
# table, whose 8192 rows run as 2048-row blocks
@pytest.mark.parametrize("d", [128 * 128, 1 << 20],
                         ids=["smallest", "full-width"])
def test_ell_scatter_mixed_kernel_parity(tpu, rng, d):
    import jax.numpy as jnp

    from flink_ml_tpu.ops.ell_scatter import (
        ell_layout,
        ell_scatter_apply,
        ell_scatter_apply_xla,
    )

    cat = rng.integers(0, d, size=(1, 64, 8)).astype(np.int32)
    lay = ell_layout(cat, d)
    u = rng.normal(size=(d // 128, 128)).astype(np.float32)
    w0 = rng.normal(size=d).astype(np.float32)
    got = np.asarray(ell_scatter_apply(
        jnp.asarray(w0), jnp.asarray(u), lay.pos[0], lay.mask[0]))
    want = np.asarray(ell_scatter_apply_xla(
        jnp.asarray(w0), jnp.asarray(u), lay.pos[0], lay.mask[0]))
    np.testing.assert_allclose(got, want, atol=1e-4)


@pytest.mark.parametrize(
    "d,batch,nnz,nd", [(128 * 128, 64, 4, 3), (1 << 20, 1 << 15, 26, 13)],
    ids=["smallest", "full-width"])
def test_ell_full_step_matches_xla_update(tpu, rng, d, batch, nnz, nd):
    """One whole _mixed_update_ell step (gather + kernel + overflow +
    heavy) against the plain-XLA mixed update, on a 64-row batch and at
    the Criteo shape chip_smoke.py fits.  The step starts from random
    weights: from zeros every margin is 0 and every update a power of
    two, which any precision gets right."""
    import jax
    import jax.numpy as jnp

    from flink_ml_tpu.models.common.losses import LOSSES
    from flink_ml_tpu.models.common.sgd import (
        SGDConfig,
        _mixed_update,
        _mixed_update_ell,
    )
    from flink_ml_tpu.ops.ell_scatter import ell_layout

    dense = rng.normal(size=(batch, nd)).astype(np.float32)
    cat = rng.integers(nd, d, size=(1, batch, nnz)).astype(np.int32)
    y = rng.integers(0, 2, size=batch).astype(np.float32)
    wb = np.ones(batch, np.float32)
    lay = ell_layout(cat, d)
    w0 = (0.1 * rng.normal(size=d)).astype(np.float32)
    # residuals are batch-normalized: scale lr with the batch so one
    # slot's update (~4e-3) stays far above f32 rounding of w0 + update
    lr = 0.5 * batch / 64

    def params():       # fresh buffers per call: the steps may donate
        return {"w": jnp.asarray(w0), "b": jnp.zeros((), jnp.float32)}

    xla_cfg = SGDConfig(learning_rate=lr, global_batch_size=batch)
    p_xla, v_xla = jax.jit(_mixed_update(LOSSES["logistic"], xla_cfg))(
        params(), dense, cat[0], y, wb)
    step_xla = np.asarray(p_xla["w"]) - w0
    # "default" (what fits run) truncates the in-kernel one-hot
    # contractions' operands to bf16, ~2^-8 relative per gathered term;
    # "highest" is the multi-pass f32 mode, exact against the XLA gather
    for precision, tol in (("highest", 1e-4), ("default", 5e-2)):
        cfg = SGDConfig(learning_rate=lr, global_batch_size=batch,
                        ell_precision=precision)
        p_ell, v_ell = jax.jit(_mixed_update_ell(LOSSES["logistic"], cfg))(
            params(), dense, lay.src[0], lay.pos[0], lay.mask[0],
            lay.ovf_idx[0], lay.ovf_src[0], lay.heavy_idx[0],
            lay.heavy_cnt[0], y, wb)
        step_ell = np.asarray(p_ell["w"]) - w0
        np.testing.assert_allclose(float(v_ell), float(v_xla), rtol=tol,
                                   err_msg=precision)
        err = np.linalg.norm(step_ell - step_xla) / np.linalg.norm(step_xla)
        assert err < tol, f"{precision}: relative step error {err}"


def test_ell_scatter_values_kernel_parity(tpu, rng):
    """The values-aware layout (sgd_fit_sparse's path) through the same
    kernel."""
    import jax.numpy as jnp

    from flink_ml_tpu.ops.ell_scatter import (
        ell_layout,
        ell_scatter_apply,
        ell_scatter_apply_xla,
    )

    d = 128 * 128
    idx = rng.integers(0, d, size=(1, 64, 8)).astype(np.int32)
    vals = rng.normal(size=(1, 64, 8)).astype(np.float32)
    lay = ell_layout(idx, d, values=vals)
    r = rng.normal(size=65).astype(np.float32)  # extended residual
    u = np.asarray(lay.val[0]) * r[np.asarray(lay.src[0])]
    w0 = rng.normal(size=d).astype(np.float32)
    got = np.asarray(ell_scatter_apply(
        jnp.asarray(w0), jnp.asarray(u), lay.pos[0], lay.mask[0]))
    want = np.asarray(ell_scatter_apply_xla(
        jnp.asarray(w0), jnp.asarray(u), lay.pos[0], lay.mask[0]))
    np.testing.assert_allclose(got, want, atol=1e-4)


def _separated_clusters(rng, n, dcol, k):
    """Points around k centers no MXU pass can confuse: this tier tests
    the Mosaic compile, not matmul tie-breaking — with overlapping
    random-normal data the TPU's reduced-precision MXU pass flips ~0.1%
    of near-boundary assignments vs a float64 oracle (observed r4), which
    is fit-quality noise, not a kernel bug.  Centers are the k-bit codes
    of their index times 16 (exact in bf16, >= 16 apart, sigma = 1
    noise), so every margin is precision-proof at any k <= 2^dcol."""
    bits = (np.arange(k)[:, None] >> np.arange(dcol)[None, :]) & 1
    true_c = (16.0 * bits).astype(np.float32)
    label = rng.integers(0, k, size=n)
    pts = (true_c[label] + rng.normal(size=(n, dcol))).astype(np.float32)
    cents = (true_c + 0.5 * rng.normal(size=(k, dcol))).astype(np.float32)
    return pts, cents


def _lloyd_oracle(pts, cents):
    """float64 single-assignment Lloyd's stats (separated clusters have
    no ties, so all tie policies must agree with it)."""
    p, c = pts.astype(np.float64), cents.astype(np.float64)
    d2 = (p * p).sum(1)[:, None] - 2.0 * p @ c.T + (c * c).sum(1)[None, :]
    assign = d2.argmin(1)
    counts = np.bincount(assign, minlength=len(c)).astype(np.float64)
    sums = np.zeros_like(c)
    np.add.at(sums, assign, p)
    return assign, d2, sums, counts


# one block_n tile at the smallest shape; two 8192-row tiles at the
# k = 256, d = 64 shape chip_smoke.py fits
_KMEANS_SHAPES = pytest.mark.parametrize(
    "n,dcol,k", [(8192, 8, 4), (16384, 64, 256)],
    ids=["smallest", "full-width"])


# the same two at blocks of 8192 lanes, and the benchmark cell's d 20,
# k 10 at the block its plan picks (32768 lanes): 16 x 8192 rows and a
# remainder, zero rows up to the block
_KMEANS_STATS_SHAPES = pytest.mark.parametrize(
    "n,dcol,k,block_n",
    [(8192, 8, 4, 8192), (16384, 64, 256, 8192),
     (16 * 8192 + 1000, 20, 10, None)],
    ids=["smallest", "full-width", "hibench"])


@_KMEANS_STATS_SHAPES
@pytest.mark.parametrize("tie_policy", ["first", "split", "fast"])
def test_kmeans_kernel_parity(tpu, rng, tie_policy, n, dcol, k, block_n):
    """kmeans_update_stats (the fused Lloyd's kernel) vs a numpy oracle."""
    import jax.numpy as jnp

    from flink_ml_tpu.ops.kmeans_pallas import (
        kmeans_update_stats, pad_correction, pick_block_n)
    from flink_ml_tpu.utils.padding import pad_rows_to_block

    block_n = block_n or pick_block_n(None, dcol, k)
    pts, cents = _separated_clusters(rng, n, dcol, k)
    (padded,), _ = pad_rows_to_block((pts,), block_n)
    sums, counts = kmeans_update_stats(jnp.asarray(padded),
                                       jnp.asarray(cents), block_n=block_n,
                                       tie_policy=tie_policy)
    counts = pad_correction(counts, jnp.asarray(cents),
                            padded.shape[0] - n, tie_policy=tie_policy)
    _, _, want_sums, want_counts = _lloyd_oracle(pts, cents)
    # counts are the exact-parity guard: any flipped assignment shows up
    # as a whole unit.  sums pass through one default-precision MXU dot
    # (inputs truncated to bf16, ~2^-8 relative), so their tolerance is
    # bf16-scaled: a genuine misassignment would move a sum by >= the
    # 16-unit cluster separation, far past it.
    np.testing.assert_allclose(np.asarray(counts, np.float64), want_counts,
                               atol=1e-3)
    np.testing.assert_allclose(np.asarray(sums, np.float64), want_sums,
                               rtol=2e-3, atol=0.5)


def _grey_images(rng, n, dcol, k):
    """Whole grey levels 0-255 (exact in bfloat16): 2 k sparse shapes
    (more than centroids, or every row would lie between near-twins)
    under a gain and noise on their inked pixels, and k of the rows as
    centroids, with a duplicate a whole tile away and one far off."""
    protos = ((rng.random((2 * k, dcol)) < 0.19)
              * rng.integers(60, 256, size=(2 * k, dcol))).astype(np.float32)
    pts = protos[rng.integers(0, 2 * k, size=n)]
    pts = np.clip(np.rint(pts * rng.uniform(0.6, 1.0, size=(n, 1))
                          + (pts > 0) * rng.integers(-16, 17, size=pts.shape)),
                  0, 255).astype(np.float32)
    cents = pts[rng.permutation(n)[:k]].copy()
    cents[k - 2] = cents[1]
    cents[k - 1] = 4096.0
    return pts, cents


@pytest.mark.parametrize("n,dcol,k", [(1 << 17, 784, 4096),
                                      (1 << 15, 128, 16384),
                                      (20_000, 200, 5000)],
                         ids=["mnist8m", "row-major-16k", "ragged"])
def test_kmeans_kernel_tiled_over_k_parity(tpu, rng, n, dcol, k):
    """kmeans_update_stats tiled over k, at the tiles the plan picks,
    against float64 sums of the bfloat16-rounded points under the
    first-index assignment: ``kmeans_mnist8m``'s shapes (rows on lanes),
    16 K centroids of whole lane tiles (row-major blocks), and a k, a d
    and an n that divide by nothing.  The points are whole grey levels,
    so the sums are exact; a row whose two best scores lie within what
    float32 accumulation can move (8 of about 3e6) may go either way, and
    only the clusters such a row could touch are let off."""
    import jax.numpy as jnp

    from flink_ml_tpu.ops.kmeans_pallas import (
        kmeans_update_stats, pad_correction, stats_tiles)
    from flink_ml_tpu.utils.padding import pad_rows_to_block

    block_n, k_tile = stats_tiles(dcol, k)
    assert k_tile is not None
    pts, cents = _grey_images(rng, n, dcol, k)
    (padded,), _ = pad_rows_to_block((pts,), block_n)
    sums, counts = kmeans_update_stats(
        jnp.asarray(padded), jnp.asarray(cents), block_n=block_n,
        k_tile=k_tile, tie_policy="first")
    counts = pad_correction(counts, jnp.asarray(cents),
                            padded.shape[0] - n, tie_policy="first")
    sums, counts = np.asarray(sums, np.float64), np.asarray(counts)

    cb = np.asarray(jnp.asarray(cents).astype(jnp.bfloat16)
                    .astype(jnp.float32), np.float64)
    c2 = (cents.astype(np.float64) ** 2).sum(1)
    assign = np.empty(n, np.int64)
    loose = np.zeros(k, bool)
    for lo in range(0, n, 4096):
        sc = c2[None] - 2.0 * pts[lo:lo + 4096].astype(np.float64) @ cb.T
        assign[lo:lo + 4096] = sc.argmin(1)
        two = np.argpartition(sc, 1, axis=1)[:, :2]
        near = np.abs(np.take_along_axis(sc, two, 1) @ [1.0, -1.0]) < 8.0
        # an exact tie is the first index's, in float32 as in float64
        near &= sc[np.arange(len(sc)), two[:, 0]] != sc[
            np.arange(len(sc)), two[:, 1]]
        loose[two[near].reshape(-1)] = True
    want_counts = np.bincount(assign, minlength=k)
    want_sums = np.zeros((k, dcol))
    np.add.at(want_sums, assign, pts.astype(np.float64))
    assert loose.mean() < 0.2
    assert counts.sum() == n and want_counts[k - 2] == 0
    np.testing.assert_array_equal(counts[~loose], want_counts[~loose])
    np.testing.assert_array_equal(sums[~loose], want_sums[~loose])
    np.testing.assert_array_equal(sums.sum(0), pts.sum(0, dtype=np.float64))


@_KMEANS_SHAPES
def test_kmeans_workset_kernel_parity(tpu, rng, n, dcol, k):
    """kmeans_workset_update (fused Hamerly scoring + stats) vs the numpy
    oracle and its registered XLA twin: half the points active, the rest
    keeping a cached assignment, a tail of masked padding rows."""
    import jax.numpy as jnp

    from flink_ml_tpu.distance import DistanceMeasure
    from flink_ml_tpu.kernels.registry import lookup
    from flink_ml_tpu.ops.kmeans_pallas import pick_block_n_workset

    pts, cents = _separated_clusters(rng, n, dcol, k)
    prev = rng.integers(0, k, size=n).astype(np.int32)
    active = (rng.random(n) < 0.5).astype(np.float32)
    pad_mask = np.ones(n, np.float32)
    pad_mask[-9:] = 0.0
    args = [jnp.asarray(a) for a in (pts, cents, prev, active, pad_mask)]
    # full width: the block the fit plans (4096 — at 8192 the compiler
    # wants 20.05 MB of its 16 MB scoped VMEM).  The small case takes a
    # 1024-row block only to keep this tier short: Mosaic's compile time
    # for this kernel grows with the block (measured PR 21: 63 s at 4096,
    # 220 s at 8192 — its per-point vectors are 1-D blocks).
    block_n = pick_block_n_workset(None, dcol, k) if k == 256 else 1024
    got = lookup("kmeans_workset_update", backend="pallas").fn(
        *args, block_n=block_n)
    twin = lookup("kmeans_workset_update", backend="xla").fn(
        DistanceMeasure.get_instance("euclidean"), k, *args)
    assign, d_best, d_second, sums, counts = (np.asarray(a) for a in got)

    fresh, d2, _, _ = _lloyd_oracle(pts, cents)
    want_assign = np.where(active > 0, fresh, prev)
    np.testing.assert_array_equal(assign, want_assign)
    np.testing.assert_array_equal(assign, np.asarray(twin[0]))
    # the distances are |p|^2 - 2 p.c + |c|^2 with p.c from a default-
    # precision MXU pass (operands truncated to bf16): the SQUARED distance
    # carries an absolute error of ~2^-8 (|p|^2 + |c|^2) however near the
    # centroid is, so that — not a relative bound on the root — is what a
    # correct kernel can be held to.  A wrong centroid is >= 256 away.
    want = np.sort(d2, axis=1)[:, :2]
    p2c2 = (pts.astype(np.float64) ** 2).sum(1) + (cents ** 2).sum(1).max()
    for got_root, col in ((d_best, 0), (d_second, 1)):
        err = np.abs(got_root.astype(np.float64) ** 2 - want[:, col])
        assert (err <= 2.0 ** -6 * p2c2).all(), (col, err.max(), p2c2.max())
    want_counts = np.bincount(want_assign, weights=pad_mask, minlength=k)
    want_sums = np.zeros((k, dcol))
    np.add.at(want_sums, want_assign, pts * pad_mask[:, None])
    np.testing.assert_allclose(counts, want_counts, atol=1e-3)
    # cached assignments put points on FAR centers, so a sum holds terms
    # up to 16 * sqrt(dcol) from its mean: the same bf16-scaled bound
    np.testing.assert_allclose(sums, want_sums, rtol=2e-3, atol=0.5)
    np.testing.assert_allclose(sums, np.asarray(twin[3]), rtol=2e-3,
                               atol=0.5)


def test_ell_fused_gather_kernel_parity(tpu, rng):
    """Mosaic compile + parity for the EXPERIMENTAL fused-gather kernel
    (per-row one-hot MXU contraction + transpose — the riskiest Mosaic
    surface in the repo; a compile failure here names it cheaply)."""
    import jax.numpy as jnp

    from flink_ml_tpu.ops.ell_scatter import (
        ell_layout,
        ell_scatter_apply_fused,
        ell_scatter_apply_xla,
    )

    d, batch, nnz = 128 * 128, 96, 7
    cat = rng.integers(0, d, size=(1, batch, nnz)).astype(np.int32)
    lay = ell_layout(cat, d)
    r = rng.normal(size=batch).astype(np.float32)
    r_ext = np.concatenate([r, np.zeros(256 - batch % 256, np.float32)])
    w0 = rng.normal(size=d).astype(np.float32)
    u = (-0.35) * jnp.asarray(r_ext)[lay.src[0]]
    want = np.asarray(ell_scatter_apply_xla(
        jnp.asarray(w0), u, lay.pos[0], lay.mask[0]))
    # default precision: the in-kernel one-hot contraction truncates the
    # gathered residuals to bf16 (~2^-8 relative) — bf16-scaled tolerance
    got = np.asarray(ell_scatter_apply_fused(
        jnp.asarray(w0), jnp.asarray(r_ext), lay.src[0], lay.pos[0],
        lay.mask[0], lr=0.35))
    np.testing.assert_allclose(got, want, atol=6e-3)
    # highest precision: exact parity with the XLA gather
    got = np.asarray(ell_scatter_apply_fused(
        jnp.asarray(w0), jnp.asarray(r_ext), lay.src[0], lay.pos[0],
        lay.mask[0], lr=0.35, precision="highest"))
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_ell_margin_kernel_parity(tpu, rng):
    """Mosaic compile + parity for the fused margin kernel (r4: forward
    half of the ELL plan) against the direct gather, both layouts."""
    import jax.numpy as jnp

    from flink_ml_tpu.ops.ell_scatter import ell_layout, ell_margin_fused

    d, batch, nnz, m_len = 128 * 128, 96, 7, 256
    cat = rng.integers(0, d, size=(1, batch, nnz)).astype(np.int32)
    w = rng.normal(size=d).astype(np.float32)
    lay = ell_layout(cat, d)
    want = w[cat[0]].sum(axis=1)
    # default-precision tolerance: nnz=7 bf16-truncated terms of |w|<~4
    # each carry up to ~|w|*2^-8 — worst-case sum ~0.1.  "default" IS the
    # production setting (SGDConfig.ell_precision): exactness there is
    # epoch-level (the residuals are batch-normalized, see sgd.py), while
    # this per-call check sees raw weights
    for prec, tol in (("highest", 1e-4), ("default", 0.1)):
        got = np.asarray(ell_margin_fused(
            jnp.asarray(w), lay.src[0], lay.pos[0], lay.mask[0],
            m_len=m_len, precision=prec))
        np.testing.assert_allclose(got[:batch], want, atol=tol)
    vals = rng.normal(size=(1, batch, nnz)).astype(np.float32)
    layv = ell_layout(cat, d, values=vals)
    wantv = (vals[0] * w[cat[0]]).sum(axis=1)
    got = np.asarray(ell_margin_fused(
        jnp.asarray(w), layv.src[0], layv.pos[0], layv.mask[0],
        m_len=m_len, val=layv.val[0], precision="highest"))
    np.testing.assert_allclose(got[:batch], wantv, atol=1e-4)


def test_routed_table_grad_both_placements_on_device(tpu, rng):
    """The r5 routed table gradients (ops/emb_grad.py): both placements
    must compile and match the scatter-add oracle on the real chip
    (pure-XLA paths, but the sorted-unique scatter flags and the big
    row-gather are exactly what a backend change could break)."""
    import jax.numpy as jnp

    from flink_ml_tpu.ops.emb_grad import emb_grad_route

    vocab, emb = 4096, 8
    cat = rng.integers(0, vocab, size=(2, 64, 4)).astype(np.int64)
    g = rng.normal(size=(256, emb)).astype(np.float32)
    want = np.zeros((vocab, emb), np.float64)
    np.add.at(want, cat[0].reshape(-1), g)
    for placement in ("gather", "scatter"):
        route = emb_grad_route(cat, vocab, placement=placement)
        got = np.asarray(route.apply(
            jnp.asarray(g), *(jnp.asarray(np.asarray(a))
                              for a in route.step_slice(0))))
        np.testing.assert_allclose(got, want.astype(np.float32),
                                   rtol=1e-4, atol=1e-4, err_msg=placement)


def test_routed_scatter_placement_under_heavy_skew_on_device(tpu, rng):
    """The scatter placement as ``widedeep_criteo.fit`` runs it (PR 29:
    past the inverse map's budget ``auto`` takes it, and it is the fit's
    primary path there): a table of 2^22 rows, 2^16 slots a step of
    which one id fills more than half (15 fold passes), ids of two steps
    so that the per-step slices differ.  Both tables' payloads, against
    a float64 sum on the host."""
    import jax.numpy as jnp

    from flink_ml_tpu.ops.emb_grad import emb_grad_route

    vocab, slots, emb = 1 << 22, 1 << 16, 16
    cat = rng.integers(0, vocab, size=(2, slots // 4, 4)).astype(np.int64)
    cat[:, :, 0] = np.where(rng.random((2, slots // 4)) < 0.6, 3,
                            cat[:, :, 0])
    cat[:, ::7, 1] = vocab - 1
    route = emb_grad_route(cat, vocab, placement="scatter")
    assert route.fold_passes >= 13 and route.placement == "scatter"
    for step in (0, 1):
        for shape in ((slots, emb), (slots,)):
            g = rng.normal(size=shape).astype(np.float32)
            want = np.zeros((vocab,) + shape[1:], np.float64)
            np.add.at(want, cat[step].reshape(-1), g)
            got = np.asarray(route.apply(
                jnp.asarray(g), *(jnp.asarray(np.asarray(a))
                                  for a in route.step_slice(step))))
            assert got.shape == want.shape
            touched = np.unique(cat[step])
            np.testing.assert_allclose(got[touched], want[touched],
                                       rtol=1e-5, atol=2e-4)
            idle = np.ones(vocab, bool)
            idle[touched] = False
            assert not got[idle].any()


def test_routed_adam_update_under_heavy_skew_on_device(tpu, rng):
    """The fused Adam pass the registry plans for an embedding table on
    the chip, fed by the scatter placement's run sums under the skew of
    the test above (a table of 2^22 rows and one more, so that the last
    block is ragged; 2^16 slots a step, one id in more than half of
    them), two steps from a state with history, against a float64 Adam
    step on the host.  A row with no history that neither step touches
    is its start, bit for bit."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    from flink_ml_tpu.kernels.registry import lookup
    from flink_ml_tpu.ops.adam_table_pallas import _bias_corrections
    from flink_ml_tpu.ops.emb_grad import emb_grad_route, routed_run_sums

    vocab, slots, emb = (1 << 22) + 1, 1 << 16, 16
    lr, b1, b2, eps = 1e-3, 0.9, 0.999, 1e-8
    cat = rng.integers(0, vocab, size=(2, slots // 4, 4)).astype(np.int64)
    cat[:, :, 0] = np.where(rng.random((2, slots // 4)) < 0.6, 3,
                            cat[:, :, 0])
    cat[:, ::7, 1] = vocab - 1
    cat[1, :, 2] = np.arange(slots // 4) + 8192  # blocks touched in every row
    route = emb_grad_route(cat, vocab, placement="scatter")
    p0 = (0.05 * rng.normal(size=(vocab, emb))).astype(np.float32)
    m0 = np.zeros_like(p0)
    hist = rng.choice(vocab, vocab // 8, replace=False)
    m0[hist] = (1e-3 * rng.normal(size=(hist.size, emb))).astype(np.float32)
    v0 = np.square(m0)
    entry = lookup("routed_adam_update", sig=(vocab, emb))
    assert entry.backend == "pallas", entry.backend
    state = tuple(map(jnp.asarray, (p0, m0, v0)))
    want = tuple(x.astype(np.float64) for x in (p0, m0, v0))
    corrections = jax.jit(partial(_bias_corrections, b1=b1, b2=b2))
    for step in (0, 1):
        g = (1e-2 * rng.normal(size=(slots, emb))).astype(np.float32)
        order, sorted_ids, out_pos, out_ids = (
            jnp.asarray(np.asarray(a)) for a in route.step_slice(step))
        sums = routed_run_sums(jnp.asarray(g), order, sorted_ids, out_pos,
                               fold_passes=route.fold_passes)
        state = entry.fn(*state, sums, out_ids, jnp.int32(step + 1), lr=lr,
                         b1=b1, b2=b2, eps=eps)
        dense = np.zeros((vocab, emb), np.float64)
        np.add.at(dense, cat[step].reshape(-1), g)
        p, m, v = want
        m = (1 - b1) * dense + b1 * m
        v = (1 - b2) * dense * dense + b2 * v
        # the bias corrections as the device computes them: its float32
        # ``b ** count`` is off in the seventh digit, which 1 - 0.999 ** t
        # turns into the fourth (optax's own expression, on either backend)
        c1, c2 = (float(c) for c in corrections(jnp.int32(step + 1)))
        p = p - lr * (m / c1) / (np.sqrt(v / c2) + eps)
        want = (p, m, v)
    got = [np.asarray(x) for x in state]
    touched = np.unique(cat)
    # the heaviest id's run sum is near 2 and carries the fold's float32
    # rounding (2e-4 in the test above): m holds a tenth of it
    for a, b, tol, name in zip(got, want, (3e-7, 2e-5, 1e-6), "pmv"):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=tol, err_msg=name)
    assert not np.array_equal(got[0][touched], p0[touched])
    idle = np.ones(vocab, bool)
    idle[touched] = False
    idle[hist] = False
    assert idle.sum() > vocab // 2
    for a, start in zip(got, (p0, m0, v0)):
        np.testing.assert_array_equal(a[idle], start[idle])


def test_widedeep_scatter_fit_takes_the_fused_update_on_device(
        tpu, rng, monkeypatch):
    """``WideDeep.fit`` past the gather budget on the chip's one-device
    mesh: the route span and the model say ``fused``, a table smaller
    than the kernel's block (5300 rows: one ragged block, id windows
    rounded up to 1024) compiles under Mosaic, and the fit equals the
    autodiff dense-Adam fit up to the order of summation; under the
    ``gather`` placement the same fit says ``dense_grad``."""
    from flink_ml_tpu import Table
    from flink_ml_tpu.models.recommendation.widedeep import WideDeep
    from flink_ml_tpu.obs.trace import tracer
    from flink_ml_tpu.ops import emb_grad

    n, vocab = 4096, [5000, 300]
    table = Table({
        "denseFeatures": rng.normal(size=(n, 4)).astype(np.float32),
        "catFeatures": np.stack([rng.integers(0, v, n) for v in vocab],
                                1).astype(np.int32),
        "label": rng.integers(0, 2, n).astype(np.float32)})

    def fit(mode="auto"):
        est = (WideDeep().set_vocab_sizes(vocab).set_embedding_dim(16)
               .set_hidden_units([32, 16]).set_max_iter(2)
               .set_global_batch_size(1024).set_seed(3)
               .set(WideDeep.ROUTED_EMB_GRAD, mode))
        tracer.enable()
        try:
            model = est.fit(table)
            notes = [s.ids for s in tracer.find("fit.arrange.route")]
        finally:
            tracer.disable()
            tracer.clear()
        return model, notes

    gather, notes = fit()
    assert (gather.route_placement, gather.table_update,
            notes[0]["table_update"]) == ("gather", "dense_grad",
                                          "dense_grad")
    monkeypatch.setattr(emb_grad, "_POS_MAP_BUDGET_BYTES", 0)
    fused, notes = fit()
    assert (fused.route_placement, fused.table_update,
            notes[0]["table_update"]) == ("scatter", "fused", "fused")
    oracle, _ = fit("off")
    for name in ("emb", "wide_cat", "mlp"):
        for a, b in zip(jax.tree_util.tree_leaves(fused._params[name]),
                        jax.tree_util.tree_leaves(oracle._params[name])):
            np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-5,
                                       err_msg=name)
    np.testing.assert_allclose(fused.loss_log, oracle.loss_log, rtol=1e-4)


def test_als_grouped_neq_on_device(tpu, rng):
    """The grouped normal equations of a skewed rating set on the chip
    (gather, batched contractions over each group's slots at 'highest',
    blocks of several shapes, one group split into parts) against the
    per-group sums in float64."""
    import jax
    import jax.numpy as jnp

    from flink_ml_tpu.models.recommendation.als import (
        GroupedPlan, grouped_normal_equations)

    n_groups, n_other, nnz, rank = 300, 64, 20000, 24
    g = np.minimum((n_groups * rng.random(nnz) ** 3).astype(np.int64),
                   n_groups - 2)               # skewed; the last group empty
    o = rng.integers(0, n_other, size=nnz).astype(np.int32)
    r = rng.normal(size=nnz).astype(np.float32)
    w = np.where(rng.random(nnz) < 0.1, 0.0, 1.0).astype(np.float32)
    factors = rng.normal(size=(n_other, rank)).astype(np.float32)
    plan = GroupedPlan(g, n_groups, rank, block_groups=64, block_slots=1024)
    assert plan.blocks > 1 and plan.parts > 1
    with jax.default_matmul_precision("highest"):
        A1, b1, c1 = grouped_normal_equations(
            jnp.asarray(factors), plan, plan.arrays(o, r, w))
    y = factors.astype(np.float64)[o]
    A0 = np.zeros((n_groups, rank, rank))
    b0 = np.zeros((n_groups, rank))
    np.add.at(A0, g, w[:, None, None] * y[:, :, None] * y[:, None, :])
    np.add.at(b0, g, (w * r)[:, None] * y)
    scale = np.abs(A0).max()
    assert np.abs(np.asarray(A1) - A0).max() < 1e-5 * scale
    assert np.abs(np.asarray(b1) - b0).max() < 1e-5 * np.abs(b0).max()
    np.testing.assert_array_equal(np.asarray(c1),
                                  np.bincount(g, w, minlength=n_groups))


@pytest.mark.parametrize("rank,groups", [(100, 1100), (32, 4001), (10, 300)],
                         ids=["netflix-rank", "chip-smoke-rank",
                              "default-rank"])
def test_als_cholesky_solve_on_device(tpu, rng, rank, groups):
    """Op ``als_cholesky_solve`` as the registry picks it on the chip
    (the kernel that keeps a tile of groups' matrices in VMEM) at a
    ragged group count, against ``np.linalg.solve`` in float64 and
    against its XLA twin; one matrix that is not positive definite gives
    NaN in its own lane and leaves its neighbours alone."""
    import jax.numpy as jnp

    from flink_ml_tpu.kernels.registry import lookup

    y = rng.normal(size=(groups, rank + 3, rank)).astype(np.float32)
    A = np.einsum("gls,glt->gst", y, y) + np.float32(0.5) * np.eye(
        rank, dtype=np.float32)
    b = rng.normal(size=(groups, rank)).astype(np.float32)
    bad = groups - 5                          # in the last, partial tile
    A[bad] = -A[bad]
    entry = lookup("als_cholesky_solve", sig=(rank, groups))
    assert entry.backend == "pallas"
    At, bt = jnp.transpose(jnp.asarray(A), (2, 1, 0)), jnp.asarray(b).T
    got = np.asarray(jax.jit(entry.fn)(At, bt)).T
    twin = np.asarray(jax.jit(
        lookup("als_cholesky_solve", backend="xla").fn)(At, bt)).T
    assert got.shape == (groups, rank)
    assert np.isnan(got[bad]).all() and np.isnan(twin[bad]).all()
    sound = np.arange(groups) != bad
    exact = np.linalg.solve(A[sound].astype(np.float64),
                            b[sound].astype(np.float64)[..., None])[..., 0]
    scale = np.abs(exact).max(axis=1, keepdims=True)
    assert np.max(np.abs(got[sound] - exact) / scale) < 2e-5
    assert np.max(np.abs(got[sound] - twin[sound]) / scale) < 2e-5


def test_als_fit_plans_grouped_on_device(tpu, rng):
    """``ALS.fit`` under ``'sorted'`` on the chip: the model says
    ``grouped``, its users' block of 500 groups is solved inside VMEM and
    its items' 120 by the XLA loop, and its factors are the scatter
    fit's (one batched ``cho_solve`` over dense operands) to float32
    rounding."""
    from flink_ml_tpu import Table
    from flink_ml_tpu.models.recommendation.als import ALS

    n = 20000
    users = np.minimum((500 * rng.random(n) ** 2).astype(np.int64), 499)
    items = rng.integers(0, 120, size=n).astype(np.int64)
    pairs = np.unique(users * 120 + items)
    table = Table({"user": pairs // 120, "item": pairs % 120,
                   "rating": rng.integers(1, 6, size=len(pairs)).astype(
                       np.float32)})

    def fit(impl):
        return (ALS().set_rank(16).set_reg_param(0.05).set_max_iter(3)
                .set_seed(1).set(ALS.NEQ_IMPL, impl).fit(table))

    grouped, scatter = fit("sorted"), fit("scatter")
    assert (grouped.neq_plan, scatter.neq_plan) == ("grouped", "scatter")
    assert (grouped.solve_plan, scatter.solve_plan) == ("vmem/xla", "xla")
    (a,), (b,) = grouped.get_model_data(), scatter.get_model_data()
    for col in ("userFactors", "itemFactors"):
        np.testing.assert_allclose(np.asarray(a[col]), np.asarray(b[col]),
                                   rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("n,d,bins,n_nodes", [(256, 4, 16, 4),
                                              (40_000, 13, 32, 16)])
def test_gbt_mxu_hist_on_device(tpu, rng, n, d, bins, n_nodes):
    """gbt_level_histograms/pallas (the exact one-hot contraction "auto"
    plans on a TPU: gradients carried as three bfloat16 parts) against
    the segment_sum twin, to float32 summation order.  The MXU form this
    replaced summed bf16-truncated addends: max |diff| 0.0079 on sums of
    ~3 on a v5e."""
    import jax.numpy as jnp

    from flink_ml_tpu.kernels.registry import lookup
    from flink_ml_tpu.models.common import gbt

    assert gbt.resolve_hist_impl("auto", (d, bins, n_nodes)) == "pallas"
    cols = tuple(jnp.asarray(rng.integers(0, bins, size=n), jnp.int32)
                 for _ in range(d))
    ids = jnp.asarray(rng.integers(-1, n_nodes, size=n), jnp.int32)
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    h = jnp.asarray(rng.random(n) + 0.1, jnp.float32)
    gs, hs = lookup("gbt_level_histograms", backend="xla").fn(
        cols, ids, g, h, n_nodes, d, bins)
    gm, hm = lookup("gbt_level_histograms").fn(cols, ids, g, h, n_nodes, d,
                                               bins)
    np.testing.assert_allclose(np.asarray(gm), np.asarray(gs),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(hm), np.asarray(hs),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("emb", [8, 64])
@pytest.mark.parametrize("fold_passes", [1, 2, 3, 4])
def test_fold_runs_fused_parity(tpu, rng, emb, fold_passes):
    """routed_table_grad/pallas: the fused segmented fold (shifts by 1, 2,
    4, 8 sublanes inside a VMEM tile, halo from the next block) against
    the XLA fold it mirrors pass for pass — adds only, so bit-exact.

    PARKED (ops/emb_grad_pallas.py::_register): Mosaic refuses the kernel,
    so today this xfails on the refusal.  The day it compiles it must
    match, and then it fails loudly: un-park the entry."""
    import jax.numpy as jnp

    from flink_ml_tpu.ops.emb_grad import _folded_ext
    from flink_ml_tpu.ops.emb_grad_pallas import fold_runs_fused

    slots, block_n = 1024, 256
    # runs up to 2^fold_passes long, some crossing a block boundary
    ids = np.sort(rng.integers(0, slots >> fold_passes, size=slots))
    g = rng.normal(size=(slots, emb)).astype(np.float32)
    try:
        got = np.asarray(fold_runs_fused(
            jnp.asarray(g), jnp.asarray(ids, jnp.int32),
            fold_passes=fold_passes, block_n=block_n))
    except Exception as exc:   # noqa: BLE001 — the refusal is the point
        assert "Mosaic failed to compile TPU kernel" in str(exc), exc
        pytest.xfail(str(exc).splitlines()[0][:200])
    want, _ = _folded_ext(jnp.asarray(g), jnp.arange(slots),
                          jnp.asarray(ids, jnp.int32), fold_passes)
    np.testing.assert_array_equal(got, np.asarray(want)[:-1])
    pytest.fail("fold_runs_fused compiles and matches on this chip now: "
                "un-park routed_table_grad/pallas")


def test_routed_table_grad_plans_the_xla_fold(tpu, rng):
    """With the Pallas fold parked, a gather route with a heavy run plans
    the XLA fold on the chip, and the routed gradient it computes matches
    the scatter-add oracle."""
    import jax.numpy as jnp

    from flink_ml_tpu.kernels.registry import lookup
    from flink_ml_tpu.ops.emb_grad import emb_grad_route

    vocab, emb = 4096, 8
    cat = rng.integers(0, vocab, size=(1, 64, 4)).astype(np.int64)
    cat[0, :40, 0] = 5                    # heavy run -> fold_passes > 0
    route = emb_grad_route(cat, vocab, placement="gather")
    assert route.fold_passes > 0
    entry = lookup("routed_table_grad", sig=route.kernel_sig())
    assert entry.backend == "xla", entry.backend
    g = rng.normal(size=(256, emb)).astype(np.float32)
    got = np.asarray(entry.fn(route, jnp.asarray(g), *route.step_slice(0)))
    want = np.zeros((vocab, emb), np.float64)
    np.add.at(want, cat[0].reshape(-1), g)
    np.testing.assert_allclose(got, want.astype(np.float32), rtol=1e-4,
                               atol=1e-4)


def _small_int_vectors(rng, n, dim):
    """Vectors of small integers: exact in bf16, so every distance is the
    same exact integer at any MXU precision and on any backend."""
    return rng.integers(-8, 9, size=(n, dim)).astype(np.float32)


@pytest.mark.parametrize("rows", [1, 8])
def test_retrieve_flat_kernel_parity(tpu, rng, rows):
    """retrieve/pallas, flat scan: the registry plans it on the chip, and
    at nprobe == nlist (every list scanned, so the probe order cannot
    matter) its distances equal the exact ones bit for bit and every id it
    returns sits at the distance it reports."""
    from flink_ml_tpu.retrieval import IVFIndex

    # dim and block at the kernel's DMA alignment (multiples of 128)
    X = _small_int_vectors(rng, 600, 128)
    index = IVFIndex.build(X, nlist=8, k=10, nprobe=8, seed=1, block=256)
    assert index.search_plan().backend == "pallas"
    q = _small_int_vectors(rng, rows, 128)
    nbrs, dists = index.search(q)

    exact = ((q[:, None, :].astype(np.float64) - X[None]) ** 2).sum(-1)
    np.testing.assert_array_equal(dists, np.sort(exact, axis=1)[:, :10])
    assert (nbrs >= 0).all()
    np.testing.assert_array_equal(
        np.take_along_axis(exact, nbrs, axis=1), dists)
    for row in nbrs:
        assert len(set(row.tolist())) == 10, row


def test_retrieve_flat_matches_xla_stage_on_real_data(tpu, rng):
    """retrieve/pallas on real-valued vectors at nprobe < nlist — what
    serving runs: the MXU passes round their operands to bf16 and the
    probed lists decide the answer (eight natural clusters split over 32
    lists; one probe finds under half the neighbours, eight find all).
    Held against the registered XLA stage on the same index and against
    the exact float64 scan, 64 queries = eight grid steps."""
    import jax
    import jax.numpy as jnp

    from flink_ml_tpu.kernels.registry import lookup
    from flink_ml_tpu.retrieval import IVFIndex
    from flink_ml_tpu.retrieval.ivf import _DIST_STAGE, _NN_STAGE

    n, dim, k = 4096, 128, 10
    centers = (2.0 * rng.normal(size=(8, dim))).astype(np.float32)
    X = (centers[rng.integers(0, 8, size=n)]
         + rng.normal(size=(n, dim))).astype(np.float32)
    q = (X[rng.choice(n, size=64, replace=False)]
         + 0.25 * rng.normal(size=(64, dim))).astype(np.float32)
    index = IVFIndex.build(X, nlist=32, k=k, nprobe=8, seed=1, block=256)
    assert index.search_plan().backend == "pallas"
    nbrs, dists = index.search(q)

    static, sig = index._static(), index.sig()
    twin = jax.jit(lambda params, cols: lookup(
        "retrieve", sig=sig, backend="xla").fn(static, params, cols))(
        {name: jnp.asarray(v) for name, v in index.params.items()},
        {index.query_col: jnp.asarray(q)})
    twin_nbrs = np.asarray(twin[_NN_STAGE])

    ids, stored = index.stored_vectors()
    assert np.array_equal(ids, np.arange(n))
    q64, x64 = q.astype(np.float64), stored.astype(np.float64)
    exact = ((q64[:, None, :] - x64[None]) ** 2).sum(-1)
    # q^2 + x^2 - 2 q.x with q.x from one bf16 pass: both operands carry
    # up to 2^-8 relative error, so the squared distance is off by at most
    # 2^-6 |q||x| <= 2^-7 (|q|^2 + |x|^2); twice that is allowed
    bound = 2.0 ** -6 * ((q64 ** 2).sum(1)[:, None]
                         + (x64 ** 2).sum(1)[None])
    for who, got_n, got_d in (("pallas", nbrs, dists),
                              ("xla", twin_nbrs,
                               np.asarray(twin[_DIST_STAGE]))):
        assert (got_n >= 0).all(), who
        assert all(len(set(row.tolist())) == k for row in got_n), who
        assert (np.diff(got_d, axis=1) >= 0).all(), who
        err = np.abs(got_d - np.take_along_axis(exact, got_n, axis=1))
        assert (err <= np.take_along_axis(bound, got_n, axis=1)).all(), (
            who, float(err.max()))

    def overlap(a, b):
        return np.array([len(set(x.tolist()) & set(y.tolist())) / k
                         for x, y in zip(a, b)])

    both = overlap(nbrs, twin_nbrs)
    assert both.mean() >= 0.9 and both.min() >= 0.8, (both.mean(),
                                                      both.min())
    truth = np.argsort(exact, axis=1)[:, :k]
    recall, twin_recall = (overlap(nbrs, truth).mean(),
                           overlap(twin_nbrs, truth).mean())
    assert recall >= 0.9 and recall >= twin_recall - 0.05, (recall,
                                                           twin_recall)
    print(f"retrieve/pallas vs xla: mean overlap {both.mean():.4f}, min "
          f"{both.min():.1f}; recall@{k} {recall:.4f} vs {twin_recall:.4f}")


@pytest.mark.parametrize("rows", [1, 8])
def test_retrieve_pq_serves_through_xla(tpu, rng, rows):
    """The Pallas PQ scan does not lower for TPU and is forced-lookup only
    (retrieve/pallas-pq, ops/retrieve_pallas.py::_register): a PQ index
    must plan the XLA stage on the chip, warm up as a servable and answer
    requests."""
    from flink_ml_tpu import Table
    from flink_ml_tpu.retrieval import IVFIndex, PQConfig
    from flink_ml_tpu.serving import make_servable

    X = rng.normal(size=(600, 32)).astype(np.float32)
    index = IVFIndex.build(X, nlist=8, k=10, nprobe=4, seed=1,
                           pq=PQConfig(m=8, ksub=16))
    assert index.search_plan().backend == "xla"
    queries = Table({"query": rng.normal(size=(8, 32)).astype(np.float32)})
    servable = make_servable(index, queries.take(2), max_batch_rows=8)
    servable.warm_up()
    served = servable.predict(queries.take(rows))
    offline = index.transform(queries)[0]
    np.testing.assert_array_equal(served["neighbors"],
                                  offline["neighbors"][:rows])
    assert np.isfinite(np.asarray(served["distances"])).all()


def _int8_cases(rng):
    """(op, static, f32 params, cols, agreement(got, ref)) for the three
    forced-lookup int8 entries — the fixtures of tests/test_kernels.py's
    accuracy-envelope harnesses."""
    from flink_ml_tpu.distance import DistanceMeasure
    from flink_ml_tpu.models.recommendation.widedeep import (
        _field_offsets,
        init_params,
    )

    X = rng.normal(size=(512, 16)).astype(np.float32)
    yield ("linear_margins", ("f", "m"),
           {"w": rng.normal(size=(16,)).astype(np.float32),
            "b": np.float32(0.1)},
           {"f": X}, "m", lambda got, ref: np.mean((got > 0) == (ref > 0)))
    yield ("kmeans_assign",
           ("f", "a", DistanceMeasure.get_instance("euclidean")),
           {"centroids": rng.normal(size=(7, 16)).astype(np.float32)},
           {"f": X}, "a", lambda got, ref: np.mean(got == ref))
    vocab = (17, 23)
    net = init_params(rng, 4, vocab, 8, (16,))
    for name in ("wide_cat", "wide_dense"):
        net[name] = (rng.normal(size=net[name].shape) * 0.1
                     ).astype(np.float32)
    cat = np.stack([rng.integers(0, v, size=512) for v in vocab],
                   axis=1).astype(np.int32)
    yield ("widedeep_scores", ("d", "c", "s"),
           {"net": net, "offsets": _field_offsets(vocab)},
           {"d": X[:, :4], "c": cat}, "s",
           lambda got, ref: np.mean((got > 0.5) == (ref > 0.5)))


def test_int8_entries_on_device(tpu, rng):
    """The three "int8" registry entries (weight-only quantized serving
    kernels, forced lookup only) through the shared DONATING dispatch
    surface: decisions agree with the f32 entry within the 99% envelope
    the CPU parity matrix holds them to."""
    from flink_ml_tpu.kernels.quantize import quantize_stage_params
    from flink_ml_tpu.kernels.registry import dispatch, lookup

    for op, static, params, cols, out_col, agreement in _int8_cases(rng):
        outs = {}
        for backend in ("xla", "int8"):
            p = (quantize_stage_params(op, params) if backend == "int8"
                 else params)
            plan = ((lookup(op, backend=backend).fn, static),)
            # a fresh column dict per call: the plan jit donates it
            outs[backend] = np.asarray(dispatch(
                plan, (p,), {k: np.array(v) for k, v in cols.items()},
                op=f"{op}-{backend}")[out_col])
        agree = float(agreement(outs["int8"], outs["xla"]))
        assert agree >= 0.99, f"{op}: int8 agreement {agree} vs f32"
