"""Pallas kernel tests — run in interpret mode on the CPU mesh (the kernels
compile natively on TPU; interpret mode is the portable correctness oracle)."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ml_tpu.ops.kmeans_pallas import (
    kmeans_assign_reduce,
    kmeans_update_stats,
    pad_correction,
    pick_block_n,
    supported,
    update_stats_sharded,
)


def _problem(n=512, d=16, k=8, n_pad=17, seed=0):
    """Points with ``n_pad`` trailing all-zero padding rows (the maskless
    kernel contract)."""
    rng = np.random.default_rng(seed)
    pts = rng.normal(size=(n, d)).astype(np.float32)
    pts[-n_pad:] = 0.0
    cents = pts[:k].copy()
    return jnp.asarray(pts), jnp.asarray(cents), n_pad


def _oracle(pts, cents, n_pad):
    """Numpy Lloyd's statistics over the real (non-padding) rows only."""
    pts = np.asarray(pts)[: pts.shape[0] - n_pad]
    cents = np.asarray(cents)
    d2 = ((pts[:, None, :] - cents[None, :, :]) ** 2).sum(-1)
    assign = d2.argmin(1)
    oh = np.zeros((pts.shape[0], cents.shape[0]), np.float32)
    oh[np.arange(pts.shape[0]), assign] = 1
    return assign, oh.T @ pts, oh.sum(0)


def _corrected_stats(pts, cents, n_pad, **kw):
    sums, counts = kmeans_update_stats(pts, cents, interpret=True, **kw)
    counts = pad_correction(counts, cents, n_pad)
    return sums, counts


def _hibench_problem(n_blocks, block_n, n_pad, seed=0, d=20, k=10):
    """HiBench's shape class: d 20, k 10, ``n_blocks`` blocks of rows of
    which the last ``n_pad`` are zero fill.  Clusters as ``tests_tpu``
    separates them (centres the bit codes of their index times 16, unit
    noise), so no margin is within reach of a rounding."""
    rng = np.random.default_rng(seed)
    n = n_blocks * block_n
    bits = (np.arange(k)[:, None] >> np.arange(d)[None, :]) & 1
    true_c = (16.0 * bits).astype(np.float32)
    pts = (true_c[rng.integers(0, k, size=n)]
           + rng.normal(size=(n, d))).astype(np.float32)
    pts[n - n_pad:] = 0.0
    cents = (true_c + 0.5 * rng.normal(size=(k, d))).astype(np.float32)
    return jnp.asarray(pts), jnp.asarray(cents), n_pad


#: (problem, block_n): the module's small problem in one-lane-tile
#: blocks, HiBench's shape class over several blocks of two sizes
_PARITY_CASES = {
    "d16-k8": (_problem, 128),
    "hibench-5x128": (partial(_hibench_problem, 5, 128, 37), 128),
    "hibench-3x512": (partial(_hibench_problem, 3, 512, 300, seed=1), 512),
}


@pytest.mark.parametrize("tie_policy", ["first", "fast", "split"])
@pytest.mark.parametrize("case", list(_PARITY_CASES))
def test_update_stats_matches_oracle(case, tie_policy):
    problem, block_n = _PARITY_CASES[case]
    pts, cents, n_pad = problem()
    _, exp_sums, exp_counts = _oracle(pts, cents, n_pad)
    sums, counts = kmeans_update_stats(pts, cents, block_n=block_n,
                                       tie_policy=tie_policy, interpret=True)
    counts = pad_correction(counts, cents, n_pad, tie_policy=tie_policy)
    np.testing.assert_allclose(np.asarray(sums), exp_sums, atol=1e-3)
    np.testing.assert_allclose(np.asarray(counts), exp_counts, atol=1e-5)


def test_update_stats_first_takes_exact_ties_to_the_first_centroid():
    """Centroids 3 and 7 are the same row, bit for bit, so every point of
    their cluster ties exactly: ``first`` counts all of them on 3, as the
    oracle's argmin does, and 7 gets nothing.  Counts to the unit, sums
    to the bf16-scaled tolerance ``tests_tpu`` uses on the chip."""
    pts, cents, n_pad = _hibench_problem(4, 256, 100, seed=2)
    cents = cents.at[7].set(cents[3])
    _, exp_sums, exp_counts = _oracle(pts, cents, n_pad)
    assert exp_counts[3] > 0 and exp_counts[7] == 0
    sums, counts = kmeans_update_stats(pts, cents, block_n=256,
                                       tie_policy="first", interpret=True)
    counts = pad_correction(counts, cents, n_pad, tie_policy="first")
    np.testing.assert_array_equal(np.asarray(counts), exp_counts)
    np.testing.assert_allclose(np.asarray(sums), exp_sums, rtol=2e-3,
                               atol=0.5)
    assert not np.asarray(sums)[7].any()


def test_update_stats_bf16_dots_conserve_mass():
    # bf16 scores may flip boundary assignments vs the f32 oracle, so check
    # the invariants instead: with "split" ties every real row contributes
    # exactly once, so counts and coordinate mass are conserved.
    pts, cents, n_pad = _problem()
    sums, counts = _corrected_stats(pts, cents, n_pad, block_n=128,
                                    tie_policy="split",
                                    compute_dtype=jnp.bfloat16)
    np.testing.assert_allclose(np.asarray(counts).sum(), 512 - n_pad,
                               atol=1e-3)
    np.testing.assert_allclose(
        np.asarray(sums).sum(0),
        np.asarray(pts)[: 512 - n_pad].sum(0), atol=0.3)


def test_assign_reduce_matches_oracle():
    pts, cents, n_pad = _problem()
    assign, sums, counts = kmeans_assign_reduce(pts, cents, block_n=128,
                                                interpret=True)
    counts = pad_correction(counts, cents, n_pad, tie_policy="argmin")
    exp_assign, exp_sums, exp_counts = _oracle(pts, cents, n_pad)
    np.testing.assert_array_equal(np.asarray(assign)[: 512 - n_pad],
                                  exp_assign)
    np.testing.assert_allclose(np.asarray(sums), exp_sums, atol=1e-3)
    np.testing.assert_allclose(np.asarray(counts), exp_counts)


def test_split_ties_fractional():
    # Two identical centroids: "split" halves each point between them,
    # "fast" double-counts — both leave the centroid *means* identical.
    rng = np.random.default_rng(1)
    pts = rng.normal(size=(128, 8)).astype(np.float32)
    cents = np.stack([pts[0], pts[0]])  # exact duplicates -> every row ties
    split_sums, split_counts = kmeans_update_stats(
        jnp.asarray(pts), jnp.asarray(cents), block_n=128,
        tie_policy="split", interpret=True)
    fast_sums, fast_counts = kmeans_update_stats(
        jnp.asarray(pts), jnp.asarray(cents), block_n=128,
        tie_policy="fast", interpret=True)
    np.testing.assert_allclose(np.asarray(split_counts).sum(), 128)
    np.testing.assert_allclose(np.asarray(fast_counts).sum(), 256)
    for sums, counts in ((split_sums, split_counts), (fast_sums, fast_counts)):
        means = np.asarray(sums) / np.asarray(counts)[:, None]
        np.testing.assert_allclose(means[0], means[1], rtol=1e-5)
        np.testing.assert_allclose(means[0], pts.mean(0), rtol=1e-4)


def test_update_stats_sharded_matches_single(cpu_mesh_8):
    pts, cents, n_pad = _problem(n=1024, d=16, k=8)
    sharded_sums, sharded_counts = update_stats_sharded(
        pts, cents, cpu_mesh_8, block_n=128, interpret=True)
    sums, counts = kmeans_update_stats(pts, cents, block_n=128,
                                       interpret=True)
    np.testing.assert_allclose(np.asarray(sharded_sums), np.asarray(sums),
                               atol=1e-3)
    np.testing.assert_allclose(np.asarray(sharded_counts), np.asarray(counts),
                               atol=1e-5)


def test_pad_correction_only_touches_nearest_to_origin():
    cents = jnp.asarray(np.array([[3.0, 0.0], [0.5, 0.5], [2.0, 2.0]],
                                 np.float32))
    counts = jnp.asarray(np.array([10.0, 20.0, 30.0], np.float32))
    out = np.asarray(pad_correction(counts, cents, 7))
    np.testing.assert_allclose(out, [10.0, 13.0, 30.0])


def test_pad_correction_exact_under_min_norm_ties():
    # Two centroids tie for minimal norm (duplicated init): the kernel counts
    # padding on BOTH under "fast" and half-each under "split"; the
    # correction must mirror that, not subtract from the first only.
    rng = np.random.default_rng(5)
    n, n_pad = 128, 32
    pts = rng.normal(loc=5.0, size=(n, 8)).astype(np.float32)
    pts[-n_pad:] = 0.0
    dup = pts[0] * 0.01  # small-norm duplicate pair
    cents = jnp.asarray(np.stack([dup, dup, pts[1], pts[2]]))
    exp_counts = _oracle(jnp.asarray(pts), cents, n_pad)[2]
    for tie_policy, scale in (("fast", 2.0), ("split", 1.0)):
        _, counts = kmeans_update_stats(jnp.asarray(pts), cents, block_n=128,
                                        tie_policy=tie_policy, interpret=True)
        counts = np.asarray(pad_correction(counts, cents, n_pad,
                                           tie_policy=tie_policy))
        # real rows tie on the duplicate pair too, under the same policy
        np.testing.assert_allclose(counts[2:], exp_counts[2:], atol=1e-4)
        np.testing.assert_allclose(counts[:2].sum(),
                                   scale * exp_counts[:2].sum(), atol=1e-3)
        assert (counts >= -1e-4).all()
    # argmin kernel under the same min-norm tie: correction must subtract
    # from the FIRST tied index only (regression: 'fast' correction after
    # the argmin kernel drove counts negative)
    _, _, counts = kmeans_assign_reduce(jnp.asarray(pts), cents, block_n=128,
                                        interpret=True)
    counts = np.asarray(pad_correction(counts, cents, n_pad,
                                       tie_policy="argmin"))
    np.testing.assert_allclose(counts[2:], exp_counts[2:], atol=1e-4)
    assert (counts >= -1e-4).all()


def test_block_divisibility_enforced():
    pts, cents, _ = _problem(n=500, n_pad=3)
    with pytest.raises(ValueError):
        kmeans_update_stats(pts, cents, block_n=128, interpret=True)


def test_bad_tie_policy_rejected():
    pts, cents, _ = _problem()
    with pytest.raises(ValueError):
        kmeans_update_stats(pts, cents, block_n=128, tie_policy="nope",
                            interpret=True)


def test_supported_budget_and_block_pick():
    assert supported(64, 256)
    assert not supported(4096, 8192)
    assert pick_block_n(1_048_576, 64, 256) == 8192
    assert pick_block_n(640, 16, 8) == 128
    assert pick_block_n(100, 16, 8) is None


@pytest.mark.parametrize("d,k,block_n", [
    (20, 10, 32768),    # HiBench: 24 sublanes of points twice, 16 of scores
    (64, 256, 8192),    # chip_smoke.py's fit
    (784, 256, 1024),   # MNIST's width: the points block alone is 6.1 MiB
    (8, 4, 65536),      # the largest block on offer
])
def test_block_pick_follows_the_feature_major_tile(d, k, block_n):
    """The picks the compiler was asked about for a described v5e (PR 30:
    each compiles under all three tie policies, and at d 20, k 10 the
    next power of two is refused at 16.45 MiB of the 16 it may take)."""
    assert pick_block_n(None, d, k) == block_n
    assert block_n == 65536 or not supported(d, k, 2 * block_n)


def test_pad_correction_exact_under_min_norm_ties_first():
    """'first' (the r4 fit default) with duplicated min-norm centroids:
    the kernel counts ALL padding on the first tied column, and
    pad_correction's argmin(c2) must name that same column — real rows
    tying on the duplicate pair land on its first index too, so counts
    match the single-assignment oracle exactly."""
    rng = np.random.default_rng(5)
    n, n_pad = 128, 32
    pts = rng.normal(loc=5.0, size=(n, 8)).astype(np.float32)
    pts[-n_pad:] = 0.0
    dup = pts[0] * 0.01  # small-norm duplicate pair -> tied c2
    cents = jnp.asarray(np.stack([dup, dup, pts[1], pts[2]]))
    exp_counts = _oracle(jnp.asarray(pts), cents, n_pad)[2]
    _, counts = kmeans_update_stats(jnp.asarray(pts), cents, block_n=128,
                                    tie_policy="first", interpret=True)
    counts = np.asarray(pad_correction(counts, cents, n_pad,
                                       tie_policy="first"))
    # single assignment: the whole tied mass sits on column 0
    np.testing.assert_allclose(counts[2:], exp_counts[2:], atol=1e-4)
    np.testing.assert_allclose(counts[0], exp_counts[:2].sum(), atol=1e-3)
    np.testing.assert_allclose(counts[1], 0.0, atol=1e-4)
    assert (counts >= -1e-4).all()


# -- the stats kernel tiled over k ------------------------------------------

def _grey_problem(n, d, k, n_pad, seed):
    """Whole grey levels 0-255 (exact in bfloat16, as the benchmark's
    images are), ``n_pad`` zero rows last, and centroids that are rows of
    the set with the faults a fit meets planted: centroid ``k - 2`` is
    centroid 1 again, bit for bit (every row of theirs ties exactly, the
    two a whole tile apart), centroid 2 is centroid 0 again (a tie inside
    one tile), and the last centroid lies where no row is."""
    rng = np.random.default_rng(seed)
    pts = rng.integers(0, 256, size=(n, d)).astype(np.float32)
    pts[n - n_pad:] = 0.0
    cents = pts[rng.permutation(n - n_pad)[:k]].copy()
    cents[k - 2] = cents[1]
    cents[2] = cents[0]
    cents[k - 1] = 4096.0
    return pts, cents


def _lloyd_stats(pts, cents, n_pad):
    """Plain ``jax.numpy`` Lloyd statistics in the kernel's stated
    arithmetic: operands rounded to bfloat16, products exact, ``c2`` of
    the float32 centroids, first index on a tie, real rows only."""
    real = jnp.asarray(pts[:len(pts) - n_pad])
    c = jnp.asarray(cents)
    with jax.default_matmul_precision("highest"):
        rounded = real.astype(jnp.bfloat16).astype(jnp.float32)
        scores = jnp.sum(c * c, axis=1)[None] - 2.0 * (
            rounded @ c.astype(jnp.bfloat16).astype(jnp.float32).T)
        assign = jnp.argmin(scores, axis=1)
        onehot = jax.nn.one_hot(assign, len(cents), dtype=jnp.float32)
        return (np.asarray(assign), np.asarray(onehot.T @ rounded),
                np.asarray(onehot.sum(0)))


@pytest.mark.parametrize("d,k,block_n,k_tile", [
    (200, 70, 128, 16),     # rows on lanes; k 70 on five tiles of 16
    (784, 100, 256, 64),    # the benchmark's width; a last tile of 36
    (40, 24, 128, 8),       # one group of eight a tile
    (256, 70, 128, 32),     # whole lane tiles: row-major blocks
    (128, 40, 256, 128),    # row-major, one tile wider than k
    (40, 1030, 512, 512),   # three tiles, the last of six centroids
], ids=["d200-k70", "d784-k100", "d40-k24", "d256-k70", "d128-k40",
        "d40-k1030"])
def test_ktiled_stats_match_plain_lloyd(d, k, block_n, k_tile):
    """Tiles that do not divide k, a width off the lane tile and on it,
    exact ties inside a tile and across tiles, an empty cluster and zero
    rows under ``pad_correction``: counts to the unit, sums exact (whole
    grey levels add exactly in float32 in any order)."""
    n, n_pad = 3 * block_n, 37
    pts, cents = _grey_problem(n, d, k, n_pad, seed=d + k)
    assign, exp_sums, exp_counts = _lloyd_stats(pts, cents, n_pad)
    sums, counts = kmeans_update_stats(
        jnp.asarray(pts), jnp.asarray(cents), block_n=block_n,
        k_tile=k_tile, tie_policy="first", interpret=True)
    counts = pad_correction(counts, jnp.asarray(cents), n_pad,
                            tie_policy="first")
    np.testing.assert_array_equal(np.asarray(counts), exp_counts)
    np.testing.assert_array_equal(np.asarray(sums), exp_sums)
    # the planted faults did what they were planted for
    assert exp_counts[1] > 0 and exp_counts[k - 2] == 0
    assert exp_counts[0] > 0 and exp_counts[2] == 0
    assert exp_counts[k - 1] == 0 and not np.asarray(sums)[k - 1].any()


def test_ktiled_stats_take_a_tie_across_tiles_to_the_first_index():
    """Every centroid the same row: all of k ties for every point, over
    all the tiles, and centroid 0 takes everything (every row of every
    block adds to the same row of the sums)."""
    rng = np.random.default_rng(3)
    pts = rng.integers(0, 256, size=(256, 24)).astype(np.float32)
    cents = np.repeat(pts[:1], 40, axis=0)
    sums, counts = kmeans_update_stats(
        jnp.asarray(pts), jnp.asarray(cents), block_n=128, k_tile=16,
        tie_policy="first", interpret=True)
    assert np.asarray(counts).tolist() == [256.0] + [0.0] * 39
    np.testing.assert_array_equal(np.asarray(sums)[0], pts.sum(0))


@pytest.mark.parametrize("d,winners", [
    (200, "one"),     # rows on lanes: every row of a block, one winner
    (128, "ends"),    # row-major: a block's rows to centroids 0 and k - 1
], ids=["one-winner", "both-ends"])
def test_ktiled_stats_by_row_chains_and_ends(d, winners):
    """Every row of a block adds to one row of the sums, one after the
    other (the read-modify-write chain: a lost update shows in the count),
    or a block's rows alternate between the first and the last centroid,
    on the first and the last tile; counts to the unit, sums exact."""
    k, block_n, k_tile, n, n_pad = 70, 128, 16, 384, 5
    rng = np.random.default_rng(d)
    cents = np.full((k, d), 4096.0, np.float32)
    pts = rng.integers(0, 256, size=(n, d)).astype(np.float32)
    if winners == "one":
        ends = [k_tile + 3]
        cents[ends[0]] = 128.0
    else:
        ends = [0, k - 1]
        cents[0], cents[k - 1] = 60.0, 200.0
        pts = np.clip(np.where(np.arange(n)[:, None] % 2, 200.0, 60.0)
                      + rng.integers(-40, 41, size=(n, d)), 0, 255
                      ).astype(np.float32)
    pts[n - n_pad:] = 0.0
    assign, exp_sums, exp_counts = _lloyd_stats(pts, cents, n_pad)
    assert set(assign.tolist()) == set(ends)
    if winners == "ends":
        assert (assign[::2] == 0).all() and (assign[1::2] == k - 1).all()
    sums, counts = kmeans_update_stats(
        jnp.asarray(pts), jnp.asarray(cents), block_n=block_n,
        k_tile=k_tile, tie_policy="first", interpret=True)
    counts = pad_correction(counts, jnp.asarray(cents), n_pad,
                            tie_policy="first")
    np.testing.assert_array_equal(np.asarray(counts), exp_counts)
    np.testing.assert_array_equal(np.asarray(sums), exp_sums)
    assert np.asarray(counts)[ends].sum() == n - n_pad


def test_ktiled_stats_know_the_first_policy_alone():
    pts, cents, _ = _problem()
    with pytest.raises(ValueError, match="'first' policy alone"):
        kmeans_update_stats(pts, cents, block_n=128, k_tile=8,
                            tie_policy="fast", interpret=True)


def test_ktiled_stats_sharded_matches_single(cpu_mesh_8):
    pts, cents = _grey_problem(1024, 40, 24, 0, seed=9)
    sharded = update_stats_sharded(
        jnp.asarray(pts), jnp.asarray(cents), cpu_mesh_8, block_n=128,
        k_tile=8, tie_policy="first", interpret=True)
    single = kmeans_update_stats(
        jnp.asarray(pts), jnp.asarray(cents), block_n=128, k_tile=8,
        tie_policy="first", interpret=True)
    for got, want in zip(sharded, single):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("d,k,tiles", [
    (20, 10, (32768, None)),      # HiBench: all of k resident, as before
    (64, 256, (8192, None)),      # chip_smoke.py's
    (784, 256, (1024, None)),     # the source's other k still fits whole
    (784, 4096, (512, 512)),      # kmeans_mnist8m: tiled over k
    (128, 16384, (512, 512)),     # row-major, 16 K centroids
    (784, 65536, None),           # 400 MB of resident sums: XLA
], ids=["hibench", "chip-smoke", "mnist-k256", "mnist-k4096", "d128-k16384",
        "too-wide"])
def test_stats_tiles_follow_the_shapes(d, k, tiles):
    from flink_ml_tpu.ops.kmeans_pallas import stats_tiles

    assert stats_tiles(d, k) == tiles
