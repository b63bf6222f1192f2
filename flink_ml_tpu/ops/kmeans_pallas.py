"""Pallas KMeans kernels — the fit/transform hot path, fused in VMEM.

The XLA expansion of one Lloyd's iteration (pairwise matmul -> argmin ->
one_hot -> einsum, ``models/clustering/kmeans.py``) materialises two
``(n, k)`` intermediates in HBM (scores and the one-hot matrix).  These
kernels tile the points over a sequential TPU grid and keep the
score/one-hot tiles in VMEM, so an iteration reads the points from HBM
once and writes the ``(k, d)`` / ``(k,)`` results.

**The stats kernel's tiles are feature-major** (PR 30): the call takes
``points.T``, shape ``(d, n)``, in blocks ``(d, block_n)``: ``d`` and ``k``
lie on sublanes, the block's rows on lanes.

- The chip keeps a narrow ``(n, d)`` array column-major
  (``f32[n,d]{0,1:T(8,128)}``: ``d`` on sublanes in groups of 8, 128 rows a
  lane tile), which IS ``(d, n)`` row-major: the transposition compiles
  to a ``bitcast`` (d 20 and d 64, compiled for a described v5e), where
  the row-major kernel before it made XLA copy the points into rows of
  128 lanes, 512 B each: 10.24 GB reserved beside 20 M rows of d 20.
- ``scores (k, bn) = -2 C (k, d) @ Xt (d, bn) + c2 (k, 1)``; the minimum
  over the ``k`` axis, ``is_min``, the first-index rule and the one-hot are
  sublane-axis work on ceil(k/8) vregs per 128 rows, every lane a row
  (row-major, each of these was a ``(bn, k)`` tile with k of 128 lanes in
  use: 8 vregs per 128 rows at k 10 where 2 hold the data);
  ``sums (k, d) += onehot (k, bn) . Xt (d, bn)`` contracts both operands
  over their last axis, the MXU's transposed-operand form, so no tile is
  transposed in VMEM.
- Both contractions are one default-precision MXU pass (operands rounded
  to bfloat16, float32 accumulation) unless ``compute_dtype`` says
  otherwise: the arithmetic of the row-major kernel, in another order of
  accumulation over a block.

Measured on one v5e, the kernel alone in a jitted scan that feeds the
centroids back, best of five calls, ms an iteration (my chip runs, PR 30,
``TPU v5 lite``; "row-major" is the kernel this one replaced, run from the
parent commit in the same process on the same points):

    n 20,021,248 = 611 x 32768, d 20, k 10 (HiBench's; seed 2147488001),
    1.6 GB of points (1.92 GB as laid out: 20 features on 24 sublanes)
      feature-major  first  block 32768   2.692   (595 GB/s of the 1.6 GB)
                            block 16384   2.696
                            block  8192   2.802
                            block  4096   3.437
                     fast   block 32768   2.674
                     split  block 32768   2.679
      row-major      first  block  8192 115.86    (its lane-padded copy of
                                                   the points, once a call,
                                                   shared by 5 iterations)
    n 2^20, d 64, k 256 (chip_smoke.py's; seeds 2147488002 / 3000000303)
      feature-major  first  block  8192   1.056 / 1.052
                            block  4096   1.009 / 1.009
                            block 16384   1.091 / 1.085
                     fast   block  8192   0.943 / 0.938
                     split  block  8192   1.043 / 1.036
      row-major      first  block  8192   1.527 / 1.528
                            block  4096   1.362 / 1.362
                     fast   block  8192   0.883 / 0.879
                     split  block  8192   1.219 / 1.227

At k 256 both layouts give the MXU the same work (by their shapes, 512
streamed rows per 128 points) and differ in the VPU/XLU passes around
it: feature-major is 31% faster under
``first`` (the fit's policy) and 15% under ``split``, 7% slower under
``fast``, whose chain has no pass that a lane reduction made dear.  One
kernel serves every shape.  Counts agree to the unit and sums to 2.3e-6
relative between the layouts on the same points; against float64 sums on
separated clusters (seed 3000000301) both are 5e-5 from the sums of the
points rounded to bfloat16 and 10.9 from the sums of the points as they
are: one bfloat16 MXU pass, as stated above.

The VMEM model (:func:`_stats_tile_bytes`) was held against the compiler
for a described v5e (no chip): over (d, k) in {(1, 1), (3, 2), (8, 4),
(20, 10), (33, 65), (64, 256), (100, 100), (128, 16), (256, 256),
(512, 8), (784, 256), (16, 2048), (64, 1024), (20, 1000)} the block it
picks compiles under all three tie policies, and the first power of two
the compiler refuses (16 MiB of scoped VMEM; looked for at nine of them)
is two to eight times the pick; at d 20, k 10 it refuses 65536 at
16.45 MiB.  At k 256, d 64 it
still compiles 16384 (points twice 8 MiB, a float32 score tile 16 MiB),
so it never holds a whole score-shaped tile: hence the half.

**Tiled over k** (PR 35).  All of the above keeps every centroid, its
sums, ``c2`` and the counts resident and works through a score tile of
``(k, block_n)``: at k 4096, d 784 the model above says 67 MB of resident
blocks against 12.  There the same call (``k_tile`` given:
:func:`stats_tiles` decides from ``(d, k)`` alone) runs
:func:`_ktiled_stats_kernel`: the centroids (as ``-2 C`` in bfloat16)
and the float32 sums stay resident under a raised VMEM limit, and a
block of rows meets them a tile of ``k_tile`` centroids at a time, twice:
a scoring pass that carries every row's running minimum and the first
index that reached it across the tiles, then a pass that adds each
tile's one-hot contraction to that tile's rows of the sums.  Every row is
scored against every centroid; the points are read from HBM once an
iteration.  The block's orientation follows the layout the chip keeps the
points in, which follows from ``d`` (:func:`_update_stats_ktiled`).
Measured on one v5e, the kernel alone in a jitted scan that feeds the
centroids back (my chip runs, PR 35): 2^18 x 784 grey-level images, k 4096,
tiles (512, 512): 20.98 ms an iteration, 80.2 TFLOP/s of the scoring
contraction's 2 n k d alone and as much again for the sums, with 784 on 896
lanes 93% of the bf16 peak; counts and sums equal to float64's under the
first-index rule, to the unit; the XLA expansion of the same step, which
the compiler fuses without ever holding an ``(n, k)`` array, 25.21 ms.

Design notes:

- **No mask input.**  Padding rows must be exact zeros — the MASKLESS
  kernel padding contract of ``utils/padding.py`` (``pad_rows_to_block``
  zero-fill + :func:`require_block_rows` divisibility; the shared rule
  every registered kernel pads by, not a module-local convention).  A
  zero row scores ``||c||^2`` against every centroid, so all padding
  lands on the centroid nearest the origin and contributes nothing to
  ``sums``; the caller subtracts the padding count from that one cluster
  (:func:`pad_correction`) — an exact fix that saves one HBM read + one
  (k, block_n) VPU pass over keeping a mask.  (The workset kernel below
  instead uses the MASKED contract: it needs the pad mask anyway to
  merge cached assignments, see :func:`kmeans_workset_update`.)
- **tie_policy="fast"** assigns a point to *every* centroid at exactly the
  minimum distance (``scores <= min``).  For continuous f32 data exact ties
  are measure-zero; the known benign case is duplicated centroids, which
  receive identical (double-counted) updates and therefore stay identical —
  the same fixed point Lloyd's has.  **"split"** divides tied points
  fractionally among the minimisers (exact expected-assignment semantics).
  **"first"** (the fit default since r4) keeps the reference's exact
  first-index-argmin semantics: the smallest tied centroid index via
  where/min/compare over an iota tile — no argmin loop, no division.
- a true ``argmin`` inside a Mosaic kernel lowers to a slow
  index-tracking loop (~6 ms/it measured), so the fit kernels compute
  assignment one-hots directly (see the policies above) rather than
  indices; the transform kernel (:func:`kmeans_assign_reduce`) does use
  argmin, because prediction needs indices and runs once, not
  ``max_iter`` times.
- ``||p||^2`` is omitted everywhere: it shifts each score row uniformly and
  cannot change which centroids attain the row minimum.

The reference computes the same statistics as a keyed network shuffle +
window reduce (``flink-ml-lib/.../clustering/kmeans/KMeans.java:172-196``);
here the whole reduction happens on-chip.
"""

from __future__ import annotations

import functools

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.padding import require_block_rows

__all__ = [
    "kmeans_assign_reduce",
    "kmeans_update_stats",
    "kmeans_workset_update",
    "update_stats_sharded",
    "pad_correction",
    "pick_block_n",
    "pick_block_n_measured",
    "pick_block_n_workset",
    "pick_block_n_workset_measured",
    "stats_tiles",
    "mxu_padded_share",
    "supported",
    "workset_supported",
]

_VMEM_BUDGET = 12 * 1024 * 1024  # leave headroom below the ~16 MB/core VMEM
#: the k-tiled layout keeps (k, d) blocks resident and raises Mosaic's
#: limit to what its model says plus this headroom, inside the chip's
#: 128 MiB of VMEM
_KTILED_VMEM_BUDGET = 96 * 1024 * 1024
_KTILED_VMEM_HEADROOM = 8 * 1024 * 1024


def _up(x: int, multiple: int) -> int:
    return -(-x // multiple) * multiple


def _stats_tile_bytes(d: int, k: int, block_n: int,
                      k_tile: Optional[int] = None) -> int:
    """THE per-tile VMEM model of the stats kernel, padding counted.
    Every supported()/pick_block_n variant of the stats kernel derives
    from this ONE formula.

    Feature-major (``k_tile`` None): the points block ``(d, block_n)``
    lies on ceil(d/8)*8 sublanes and the pipeline holds two of it; HALF a
    score-shaped ``(k, block_n)`` float32 tile on ceil(k/8)*8 sublanes
    for the compare/one-hot chain (the compiler works through it by lane
    columns and never holds a whole one: see the module docstring for
    the probe that says so); and the resident ``(k, d)`` / ``(k, 1)``
    blocks (centroids, sums, c2, counts), lane-padded to 128, two
    buffers each.

    Tiled over k (``k_tile`` centroids a tile): the float32 points block
    of ``block_n`` rows, reckoned on ceil(d/128)*128 lanes (row-major; the
    feature-major block pads less), two of it, and its bfloat16 copy;
    resident for the whole call, two buffers each, the bfloat16 centroids
    and the float32 sums ``(k, d)`` with k rounded up to the tile, and
    ``c2`` and the counts ``(k, 1)`` on 128 lanes; a score tile and a
    one-hot ``(k_tile, block_n)`` and one contraction's result
    ``(k_tile, d)``, float32.  58 MB at d 784, k 4096 and tiles of 512,
    which Mosaic compiles under the limit this number sets."""
    if k_tile is None:
        d8, k8 = _up(d, 8), _up(k, 8)
        return ((2 * d8 * 4 + k8 * 2) * block_n
                + 4 * k8 * (_up(d, 128) + 128) * 4)
    dp, kp = _up(d, 128), _up(k, k_tile)
    return ((2 * 4 + 2) * block_n * dp
            + 2 * kp * (dp * (2 + 4) + 2 * 128 * 4)
            + k_tile * (2 * block_n + dp) * 4)


def supported(d: int, k: int, block_n: int = 8192,
              k_tile: Optional[int] = None) -> bool:
    """True if the stats-kernel tile (:func:`_stats_tile_bytes`) fits the
    VMEM budget of its layout."""
    budget = _VMEM_BUDGET if k_tile is None else _KTILED_VMEM_BUDGET
    return _stats_tile_bytes(d, k, block_n, k_tile) <= budget


#: ``(block_n, k_tile)`` the k-tiled layout is offered, widest first.  The
#: tiles hardly matter once both are a few MXU passes wide: at 2^18 x 784,
#: k 4096 an iteration takes 20.98 ms at (512, 512), 20.68 at (1024, 512),
#: 20.53 at (512, 1024), 20.47 at (1024, 1024), 20.24 at (2048, 512) and
#: 21.92 at (256, 512) (my chip runs, PR 35, one v5e: the MXU passes of
#: both contractions, padding included, at 93% of the bf16 peak), so the
#: first pick is the one that holds least
_KTILED_TILES = ((512, 512), (512, 256), (256, 256), (256, 128), (128, 128))


def stats_tiles(d: int, k: int) -> Optional[Tuple[int, Optional[int]]]:
    """``(block_n, k_tile)`` of the stats kernel at ``(d, k)``, from the
    shapes alone: the feature-major layout with everything of ``k``
    resident (``k_tile`` None) wherever its tile fits, else the layout
    tiled over k, else None (the caller falls back to XLA)."""
    block_n = pick_block_n(None, d, k)
    if block_n is not None:
        return block_n, None
    for block_n, k_tile in _KTILED_TILES:
        if supported(d, k, block_n, k_tile):
            return block_n, k_tile
    return None


def mxu_padded_share(d: int, k: int, k_tile: Optional[int]) -> float:
    """The share of both contractions' centroid-shaped operand that is
    padding as the kernel lays it out: ``(k, d)`` on ceil(k/8)*8 x
    ceil(d/8)*8 feature-major, on whole k tiles x ceil(d/128)*128 lanes
    tiled over k.  The MXU passes over the padding as over data."""
    kp, dp = ((_up(k, 8), _up(d, 8)) if k_tile is None
              else (_up(k, k_tile), _up(d, 128)))
    return 1.0 - (k * d) / (kp * dp)


# Largest block each kernel is offered.  The stats kernel's blocks are
# lane counts of a feature-major tile (about 256 B a row at d 20, k 10);
# the workset kernel's are rows of a row-major one.
_MAX_STATS_BLOCK = 65536
_MAX_WORKSET_BLOCK = 8192


def _blocks_down(largest: int) -> list:
    """The power-of-two blocks ``largest``, ``largest / 2``, ..., 128."""
    return [largest >> s for s in range(largest.bit_length() - 7)]


def _pick_block(n: Optional[int], fits, largest: int) -> Optional[int]:
    """Largest power-of-two block (<= ``largest``, >= 128) satisfying
    ``fits`` and — when ``n`` is given — dividing ``n``; None if nothing
    works (caller falls back to XLA)."""
    for bn in _blocks_down(largest):
        if (n is None or n % bn == 0) and fits(bn):
            return bn
    return None


def pick_block_n(n: Optional[int], d: int, k: int) -> Optional[int]:
    """Largest viable stats-kernel block.  Pass ``n=None`` when the
    caller zero-pads to the block anyway (the estimator does)."""
    return _pick_block(n, lambda bn: supported(d, k, bn), _MAX_STATS_BLOCK)


def _viable_blocks(fits, largest: int) -> list:
    """Every power-of-two block (``largest`` down to 128) passing
    ``fits`` — the candidate set the measured search ranks (the analytic
    descent only ever took the largest)."""
    return [bn for bn in _blocks_down(largest) if fits(bn)]


def _measured_block(op: str, d: int, k: int, candidates: list,
                    runner_factory, *, analytic: int,
                    layout: tuple = ()) -> int:
    """Resolve a block size by measurement through the registry
    autotuner (``kernels/autotune.py``): ``choose`` honors a recorded
    decision for ``(op, ("block_n", *layout, d, k))`` without running
    anything; a first encounter times every candidate on a synthetic
    probe of the kernel's real entry point and persists the winner.
    ``layout`` tags the key of a kernel whose tiles changed, so that no
    decision measured on the old ones is reused.  With autotuning
    disabled (no cache root) the analytic pick stands — exactly the
    pre-autotune behavior."""
    from ..kernels import autotune

    if len(candidates) == 1 or not autotune.enabled():
        return analytic
    choice, _ = autotune.choose(
        op, ("block_n", *layout, d, k),
        {str(bn): runner_factory(bn) for bn in candidates},
        kind="block", probe=f"synthetic n={max(candidates)} d={d} k={k}")
    return int(choice)


def _probe_operands(n: int, d: int, k: int):
    # centroids drawn SEPARATELY from the points: the probe's k must be
    # the real fit's k even when k exceeds the largest candidate block,
    # or the persisted winner would be measured on the wrong problem
    rng = np.random.default_rng(1234)
    points = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    cents = jnp.asarray(rng.normal(size=(k, d)).astype(np.float32))
    return points, cents


def pick_block_n_measured(d: int, k: int, *, interpret: bool = False,
                          candidates: Optional[list] = None
                          ) -> Optional[int]:
    """The measured form of :func:`pick_block_n` (ISSUE 12): instead of
    trusting the VMEM model to rank blocks, time the stats kernel at
    every viable block size once per (d, k, device kind) and persist the
    winner in the autotune cache — every later process reuses the
    decision without re-searching.  Falls back to the analytic pick when
    autotuning is disabled; returns None exactly when the analytic
    descent would (no viable block -> XLA fallback)."""
    cands = (candidates if candidates is not None
             else _viable_blocks(lambda bn: supported(d, k, bn),
                                 _MAX_STATS_BLOCK))
    if not cands:
        return None
    # probe operands are lazy AND shared across candidates: a recorded
    # decision allocates nothing, a fresh search allocates one set
    probe: list = []

    def runner(bn):
        def thunk():
            if not probe:
                probe.append(_probe_operands(max(cands), d, k))
            points, cents = probe[0]
            return kmeans_update_stats(points, cents, block_n=bn,
                                       interpret=interpret)
        return thunk

    # decisions recorded under ("block_n", d, k) were measured on the
    # row-major kernel this one replaced
    return _measured_block("kmeans_update_stats", d, k, cands, runner,
                           analytic=max(cands), layout=("feature_major",))


def pick_block_n_workset_measured(d: int, k: int, *,
                                  interpret: bool = False,
                                  candidates: Optional[list] = None
                                  ) -> Optional[int]:
    """Measured twin of :func:`pick_block_n_workset` for the fused
    workset kernel (same decision protocol, its own op key — the two
    kernels have different VPU/VMEM profiles, so one winner must never
    be assumed to transfer to the other)."""
    cands = (candidates if candidates is not None
             else _viable_blocks(lambda bn: workset_supported(d, k, bn),
                                 _MAX_WORKSET_BLOCK))
    if not cands:
        return None
    probe: list = []

    def runner(bn):
        def thunk():
            if not probe:
                n = max(cands)
                points, cents = _probe_operands(n, d, k)
                probe.append((points, cents, jnp.zeros((n,), jnp.int32),
                              jnp.ones((n,), jnp.float32)))
            points, cents, prev, ones = probe[0]
            return kmeans_workset_update(points, cents, prev, ones,
                                         ones, block_n=bn,
                                         interpret=interpret)
        return thunk

    return _measured_block("kmeans_workset_update", d, k, cands, runner,
                           analytic=max(cands))


def _stats_kernel(tie_policy: str, compute_dtype):
    # contract both operands over their LAST axis (the block's rows, on
    # lanes): the MXU's transposed-operand form, nothing is transposed in
    # VMEM
    over_rows = (((1,), (1,)), ((), ()))

    def kern(xt_ref, cent_ref, c2_ref, sums_ref, counts_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            sums_ref[:] = jnp.zeros_like(sums_ref)
            counts_ref[:] = jnp.zeros_like(counts_ref)

        xt = xt_ref[:].astype(compute_dtype)                      # (d, bn)
        scores = (-2.0 * jnp.dot(cent_ref[:].astype(compute_dtype), xt,
                                 preferred_element_type=jnp.float32)
                  + c2_ref[:])                                    # (k, bn)
        mins = jnp.min(scores, axis=0, keepdims=True)
        is_min = scores <= mins
        if tie_policy == "first":
            # exact first-index-argmin semantics WITHOUT an argmin loop
            # (which lowers to a ~6 ms index-tracking scan in Mosaic):
            # the first minimiser is the smallest centroid index among
            # the tied minima -- one where + min over the k axis +
            # compare, all sublane-axis VPU passes (no division like
            # "split").
            k = scores.shape[0]
            iota = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
            first = jnp.min(jnp.where(is_min, iota, k), axis=0,
                            keepdims=True)
            onehot = (iota == first).astype(jnp.float32)
        else:
            onehot = is_min.astype(jnp.float32)
            if tie_policy == "split":
                onehot = onehot / jnp.sum(onehot, axis=0, keepdims=True)
        sums_ref[:] += jax.lax.dot_general(
            onehot.astype(compute_dtype), xt, over_rows,
            preferred_element_type=jnp.float32)                   # (k, d)
        counts_ref[:] += jnp.sum(onehot, axis=1, keepdims=True)   # (k, 1)

    return kern


def _ktiled_stats_kernel(k_tile: int, n_tiles: int, rows_on_lanes: bool):
    """The stats of one block of rows against ALL centroids, a tile of
    ``k_tile`` of them at a time.  The score tile is ``(k_tile, block_n)``
    with k on sublanes and the block's rows on lanes, as in the kernel
    with all of k resident; the points block is ``(d, block_n)`` of
    ``points.T`` (``rows_on_lanes``) or ``(block_n, d)`` of ``points``,
    whichever the chip holds (:func:`_update_stats_ktiled`), and the two
    contractions swap their forms with it: ``C_j Xt`` / ``onehot_j . Xt``
    over the lanes of both, or ``C_j . X`` over the lanes of both /
    ``onehot_j X``.  Nothing is transposed in VMEM either way.

    Pass 1 carries, for every row, the minimum so far and where it was
    first seen, as ``(8, block_n)`` vregs: slot ``s`` of a lane holds the
    best of the centroids ``8 g + s`` and the first group ``g`` that
    reached it (a strict ``<`` keeps the earlier one, across tile borders
    too), so a tile costs three VPU passes over its scores and no
    reduction; the eight slots meet once a block: the minimum over
    sublanes, then the smallest centroid index among the slots that hold
    it: the first index of the whole row.  Pass 2 forms each tile's
    one-hot from that index and adds its contraction with the block to
    the tile's rows of the resident sums."""
    over_lanes = (((1,), (1,)), ((), ()))
    groups = k_tile // 8

    def contract(lhs, rhs, lanes_of_both: bool):
        if lanes_of_both:
            return jax.lax.dot_general(lhs, rhs, over_lanes,
                                       preferred_element_type=jnp.float32)
        return jnp.dot(lhs, rhs, preferred_element_type=jnp.float32)

    def kern(x_ref, cb_ref, c2_ref, sums_ref, counts_ref):
        block_n = x_ref.shape[1 if rows_on_lanes else 0]

        @pl.when(pl.program_id(0) == 0)
        def _init():
            sums_ref[:] = jnp.zeros_like(sums_ref)
            counts_ref[:] = jnp.zeros_like(counts_ref)

        xb = x_ref[:].astype(jnp.bfloat16)

        def tile(j):
            return pl.ds(pl.multiple_of(j * k_tile, k_tile), k_tile)

        def score(j, carry):
            best, seen = carry
            # cb holds -2 C rounded to bfloat16: c2 - 2 C.x, float32
            scores = (contract(cb_ref[tile(j), :], xb, not rows_on_lanes)
                      + c2_ref[tile(j), :])               # (k_tile, bn)
            for g in range(groups):
                part = scores[8 * g:8 * (g + 1), :]
                less = part < best
                best = jnp.where(less, part, best)
                seen = jnp.where(less, j * groups + g, seen)
            return best, seen

        best, seen = jax.lax.fori_loop(
            0, n_tiles, score,
            (jnp.full((8, block_n), jnp.inf, jnp.float32),
             jnp.zeros((8, block_n), jnp.int32)))
        index = 8 * seen + jax.lax.broadcasted_iota(jnp.int32, seen.shape, 0)
        lowest = jnp.min(best, axis=0, keepdims=True)
        first = jnp.min(jnp.where(best == lowest, index,
                                  jnp.iinfo(jnp.int32).max),
                        axis=0, keepdims=True)            # (1, bn)

        def accumulate(j, carry):
            ids = j * k_tile + jax.lax.broadcasted_iota(
                jnp.int32, (k_tile, block_n), 0)
            onehot = (ids == first).astype(jnp.float32)
            sums_ref[tile(j), :] += contract(
                onehot.astype(jnp.bfloat16), xb, rows_on_lanes)
            counts_ref[tile(j), :] += jnp.sum(onehot, axis=1, keepdims=True)
            return carry

        jax.lax.fori_loop(0, n_tiles, accumulate, None)

    return kern


def _update_stats_ktiled(points, centroids, block_n: int, k_tile: int,
                         interpret: bool):
    """The call of :func:`_ktiled_stats_kernel`.  The blocks follow the
    layout the chip keeps ``points`` in, which follows from ``d``: rows
    whose width is no multiple of 128 lanes lie column-major (no lane is
    padded: compiled for a described v5e, an ``f32[n,784]`` parameter is
    ``{0,1:T(8,128)}``), so the call takes ``points.T``, a bitcast, in
    blocks ``(d, block_n)``; rows of whole lane tiles lie row-major and go
    in as they are, ``(block_n, d)``.  The other orientation would make
    XLA copy the points (7.26 GB beside 6.35 at 2,025,000 x 784).  The
    centroids go in as ``-2 C`` rounded to bfloat16 on whole tiles (zero
    rows, whose ``c2`` is infinite, so that no row picks one); the sums
    come back on the same padded shape and are cut to ``k`` rows."""
    n, d = points.shape
    k = centroids.shape[0]
    k_pad = _up(k, k_tile)
    rows_on_lanes = d % 128 != 0
    c2 = jnp.pad(jnp.sum(centroids * centroids, axis=1, keepdims=True),
                 ((0, k_pad - k), (0, 0)), constant_values=jnp.inf)
    cb = jnp.pad((-2.0 * centroids).astype(jnp.bfloat16),
                 ((0, k_pad - k), (0, 0)))

    def resident(shape):
        return pl.BlockSpec(shape, lambda i: (0, 0), memory_space=pltpu.VMEM)

    if rows_on_lanes:
        x, x_spec = points.T, pl.BlockSpec(
            (d, block_n), lambda i: (0, i), memory_space=pltpu.VMEM)
    else:
        x, x_spec = points, pl.BlockSpec(
            (block_n, d), lambda i: (i, 0), memory_space=pltpu.VMEM)
    sums, counts = pl.pallas_call(
        _ktiled_stats_kernel(k_tile, k_pad // k_tile, rows_on_lanes),
        grid=(n // block_n,),
        in_specs=[x_spec, resident((k_pad, d)), resident((k_pad, 1))],
        out_specs=[resident((k_pad, d)), resident((k_pad, 1))],
        out_shape=[
            jax.ShapeDtypeStruct((k_pad, d), jnp.float32),
            jax.ShapeDtypeStruct((k_pad, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=(_stats_tile_bytes(d, k, block_n, k_tile)
                              + _KTILED_VMEM_HEADROOM)),
        interpret=interpret,
    )(x, cb, c2)
    return sums[:k], counts[:k, 0]


def _assign_kernel(points_ref, cent_ref, c2_ref,
                   assign_ref, sums_ref, counts_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        sums_ref[:] = jnp.zeros_like(sums_ref)
        counts_ref[:] = jnp.zeros_like(counts_ref)

    pts = points_ref[:]
    scores = (-2.0 * jnp.dot(pts, cent_ref[:].T,
                             preferred_element_type=jnp.float32)
              + c2_ref[:])
    assign = jnp.argmin(scores, axis=1)
    assign_ref[:] = assign.astype(jnp.int32)

    k = sums_ref.shape[0]
    onehot = (assign[:, None]
              == jax.lax.broadcasted_iota(jnp.int32, (pts.shape[0], k), 1))
    onehot = onehot.astype(jnp.float32)
    sums_ref[:] += jnp.dot(onehot.T, pts,
                           preferred_element_type=jnp.float32)
    counts_ref[:] += jnp.sum(onehot, axis=0)


def _check_block(n: int, block_n: int, op: str = "kmeans_pallas") -> None:
    # the shared registered-kernel invariant (utils/padding.py), not a
    # module-local rule: every blocked kernel raises the same message
    require_block_rows(n, block_n, op=op)


@functools.partial(jax.jit,
                   static_argnames=("block_n", "k_tile", "tie_policy",
                                    "compute_dtype", "interpret"))
def kmeans_update_stats(points: jnp.ndarray, centroids: jnp.ndarray, *,
                        block_n: int = 8192, k_tile: Optional[int] = None,
                        tie_policy: str = "fast",
                        compute_dtype=jnp.float32, interpret: bool = False
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fit hot path: ``(points (n, d), centroids (k, d)) ->
    (sums (k, d) f32, counts (k,) f32)``.

    ``n`` must be a multiple of ``block_n``; pad with all-zero rows and
    correct the counts with :func:`pad_correction`.  A block is
    ``block_n`` rows on lanes: a multiple of 128 for the chip (the
    interpreter takes any).  ``(block_n, k_tile)`` come from
    :func:`stats_tiles`: ``k_tile`` None is the feature-major kernel with
    all of ``k`` resident, a number the kernel tiled over k, which knows
    the ``"first"`` policy alone and rounds both contractions' operands
    to bfloat16 itself (``compute_dtype`` does not apply).
    """
    if tie_policy not in ("first", "fast", "split"):
        raise ValueError(f"tie_policy must be 'first', 'fast' or 'split', "
                         f"got {tie_policy!r}")
    n, d = points.shape
    k = centroids.shape[0]
    _check_block(n, block_n)
    if k_tile is not None:
        if tie_policy != "first":
            raise ValueError("the stats kernel tiled over k assigns by the "
                             f"'first' policy alone, got {tie_policy!r}")
        return _update_stats_ktiled(points, centroids, block_n, k_tile,
                                    interpret)
    c2 = jnp.sum(centroids * centroids, axis=1, keepdims=True)

    # (n, d) -> (d, n): the chip keeps narrow rows column-major, so for
    # them this is the array it already holds under another name
    sums, counts = pl.pallas_call(
        _stats_kernel(tie_policy, compute_dtype),
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((d, block_n), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((k, 1), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((k, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((k, 1), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, d), jnp.float32),
            jax.ShapeDtypeStruct((k, 1), jnp.float32),
        ],
        interpret=interpret,
    )(points.T, centroids, c2)
    return sums, counts[:, 0]


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def kmeans_assign_reduce(points: jnp.ndarray, centroids: jnp.ndarray, *,
                         block_n: int = 2048, interpret: bool = False
                         ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Transform path: also emits per-point assignments (first-index argmin).
    ``(points (n, d), centroids (k, d)) ->
    (assignments (n,) int32, sums (k, d), counts (k,))``.
    Padding rows get a garbage (but in-range) assignment — slice them off."""
    n, d = points.shape
    k = centroids.shape[0]
    _check_block(n, block_n)
    c2 = jnp.sum(centroids * centroids, axis=1)[None, :]

    return pl.pallas_call(
        _assign_kernel,
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, k), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((block_n,), lambda i: (i,),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((k,), lambda i: (0,), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((k, d), jnp.float32),
            jax.ShapeDtypeStruct((k,), jnp.float32),
        ],
        interpret=interpret,
    )(points, centroids, c2)


def pad_correction(counts: jnp.ndarray, centroids: jnp.ndarray,
                   n_pad, tie_policy: str = "fast") -> jnp.ndarray:
    """Remove the contribution of ``n_pad`` all-zero padding rows: they all
    landed on the centroid(s) with the smallest norm, added nothing to
    ``sums``, and ``n_pad`` to those clusters' counts.

    ``tie_policy`` must name the policy of the kernel that produced
    ``counts``, so the fix stays exact even when several centroids tie for
    minimal norm (e.g. duplicated init centroids):

    - ``"fast"``   — :func:`kmeans_update_stats` counted padding fully on
      *every* tied centroid
    - ``"split"``  — fractionally across the tied centroids
    - ``"argmin"`` / ``"first"`` — :func:`kmeans_assign_reduce` /
      :func:`kmeans_update_stats` with ``tie_policy="first"`` counted it
      on the first tied index only (first-index argmin semantics)
    """
    c2 = jnp.sum(centroids * centroids, axis=1)
    if tie_policy in ("argmin", "first"):
        tied = jax.nn.one_hot(jnp.argmin(c2), counts.shape[0],
                              dtype=counts.dtype)
    elif tie_policy in ("fast", "split"):
        tied = (c2 <= jnp.min(c2)).astype(counts.dtype)
        if tie_policy == "split":
            tied = tied / jnp.sum(tied)
    else:
        raise ValueError(
            f"tie_policy must be 'first', 'fast', 'split' or 'argmin', "
            f"got {tie_policy!r}")
    return counts - n_pad * tied


def update_stats_sharded(points: jnp.ndarray, centroids: jnp.ndarray,
                         mesh, *, block_n: int = 8192,
                         k_tile: Optional[int] = None,
                         tie_policy: str = "fast",
                         compute_dtype=jnp.float32,
                         interpret: bool = False
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mesh-parallel stats: each device runs the kernel on its row shard,
    partial (k, d)/(k,) results are summed with one ``psum`` over the
    ``data`` axis (the ICI allreduce replacing the reference's keyed network
    shuffle).  Per-shard row count must be a multiple of ``block_n``.  The
    ``psum`` and nothing else lies under the ``jax.named_scope``
    ``kmeans.reduce``, so that a device trace says what the all-reduce
    costs a step (the kernel stays under the caller's scope)."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.collectives import shard_map_fn

    def shard_fn(pts, cents):
        sums, counts = kmeans_update_stats(
            pts, cents, block_n=block_n, k_tile=k_tile,
            tie_policy=tie_policy, compute_dtype=compute_dtype,
            interpret=interpret)
        with jax.named_scope("kmeans.reduce"):
            return (jax.lax.psum(sums, "data"),
                    jax.lax.psum(counts, "data"))

    # shard_map_fn turns the varying-axes check off (pallas_call
    # out_shapes carry no varying-mesh-axes annotation)
    return shard_map_fn(shard_fn, mesh=mesh,
                        in_specs=(P("data", None), P(None, None)),
                        out_specs=(P(None, None), P(None)))(points, centroids)


# ---------------------------------------------------------------------------
# Fused workset assign+update (PR 10 hot path): one VMEM pass per tile
# computes the Hamerly scoring (distances, first-index argmin, best and
# second-best distances), merges with the cached assignment under the
# active mask, AND accumulates the Lloyd's statistics — the (n, k)
# distance matrix, the is_min compare tiles, and the (n, k) one-hot all
# live and die in VMEM instead of round-tripping HBM between the scoring
# expression and the stats einsum of the XLA workset body
# (``models/clustering/kmeans.py::kmeans_workset_epoch_step``).
# ---------------------------------------------------------------------------

def workset_supported(d: int, k: int, block_n: int = 8192) -> bool:
    """VMEM model of :func:`kmeans_workset_update`: TWO live (block_n, k)
    f32 tiles (distances and the merged one-hot — the second-best pass
    keeps the distances alive past the one-hot), the double-buffered
    (block_n, d) points tile, the accumulators, and the per-tile
    assign/bound/mask vectors (~6 lane vectors of block_n f32/i32).
    Calibrated on the chip (PR 21, v5 lite): at block_n=8192, k=256, d=64
    the compiler reports a 20.05 MB scoped allocation against its 16 MB
    limit; this model says 21.2 MB there and 10.6 MB at 4096."""
    tiles = 2 * block_n * k * 4 + 2 * block_n * d * 4 + k * d * 4 + k * 4
    return tiles + 6 * block_n * 4 <= _VMEM_BUDGET


def pick_block_n_workset(n: Optional[int], d: int, k: int) -> Optional[int]:
    """Largest viable workset-kernel block (``n=None`` when the caller
    pads to the block — the estimator does)."""
    return _pick_block(n, lambda bn: workset_supported(d, k, bn),
                       _MAX_WORKSET_BLOCK)


def _workset_kernel(k: int):
    def kern(points_ref, cent_ref, c2_ref, prev_ref, active_ref, padm_ref,
             assign_ref, dbest_ref, dsec_ref, sums_ref, counts_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            sums_ref[:] = jnp.zeros_like(sums_ref)
            counts_ref[:] = jnp.zeros_like(counts_ref)

        pts = points_ref[:]
        # EXPRESSION-identical to EuclideanDistanceMeasure.pairwise (the
        # XLA workset body's scoring): the bound cache decays in TRUE
        # distance space, so the kernel must emit root distances, and
        # matching the expression keeps the per-row results bit-identical
        # to the XLA body in interpret mode (the parity oracle).
        p2 = jnp.sum(pts * pts, axis=-1, keepdims=True)          # (bn, 1)
        cross = jnp.dot(pts, cent_ref[:].T,
                        preferred_element_type=jnp.float32)      # (bn, k)
        dists = jnp.sqrt(jnp.maximum(p2 - 2.0 * cross + c2_ref[:], 0.0))
        mins = jnp.min(dists, axis=1, keepdims=True)
        is_min = dists <= mins
        # first-index argmin WITHOUT an argmin loop (the stats-kernel
        # trick): smallest tied column index via iota + row-min
        iota = jax.lax.broadcasted_iota(jnp.int32, dists.shape, 1)
        fresh = jnp.min(jnp.where(is_min, iota, k), axis=1)      # (bn,)
        d_sec = jnp.min(jnp.where(iota == fresh[:, None],
                                  jnp.inf, dists), axis=1)

        # merge: active points take the fresh score, settled points keep
        # their cached assignment (provably identical, see the body doc)
        on = active_ref[:] > 0
        assign = jnp.where(on, fresh, prev_ref[:]).astype(jnp.int32)
        assign_ref[:] = assign
        dbest_ref[:] = mins[:, 0]
        dsec_ref[:] = d_sec

        onehot = (iota == assign[:, None]).astype(jnp.float32)
        onehot = onehot * padm_ref[:][:, None]        # masked contract
        sums_ref[:] += jnp.dot(onehot.T, pts,
                               preferred_element_type=jnp.float32)
        counts_ref[:] += jnp.sum(onehot, axis=0)

    return kern


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def kmeans_workset_update(points: jnp.ndarray, centroids: jnp.ndarray,
                          prev_assign: jnp.ndarray, active: jnp.ndarray,
                          pad_mask: jnp.ndarray, *, block_n: int = 2048,
                          interpret: bool = False):
    """Fused bound-filtered scoring + stats for one workset Lloyd's round:
    ``(points (n, d), centroids (k, d), prev_assign (n,) i32,
    active (n,) f32 0/1, pad_mask (n,) f32 0/1) ->
    (assign (n,) i32, d_best (n,), d_second (n,), sums (k, d),
    counts (k,))``.

    ``assign`` is already MERGED (fresh first-index argmin where active,
    the cached assignment elsewhere); ``d_best``/``d_second`` are the
    FRESH per-point best/second-best root distances — the caller keeps
    its old bounds where the point was settled, then applies the drift
    decay exactly as the XLA body does.  Stats are masked by
    ``pad_mask`` (the MASKED padding contract,
    ``utils/padding.py::pad_rows_with_mask(multiple=block_n)``) — no
    pad-correction step, unlike the maskless BSP stats kernel.

    Parity: per-row outputs are expression-identical to the XLA workset
    body; ``sums`` accumulate tile-sequentially, so they match the XLA
    einsum to f32 summation order (allclose, not bitwise — asserted in
    the cross-backend matrix of ``tests/test_kernels.py``).  Euclidean
    only (the bounds need root distances)."""
    n, d = points.shape
    k = centroids.shape[0]
    _check_block(n, block_n, op="kmeans_workset_update")
    c2 = jnp.sum(centroids * centroids, axis=1)[None, :]

    return pl.pallas_call(
        _workset_kernel(k),
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, k), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((block_n,), lambda i: (i,),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_n,), lambda i: (i,),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_n,), lambda i: (i,),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((block_n,), lambda i: (i,),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_n,), lambda i: (i,),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_n,), lambda i: (i,),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((k,), lambda i: (0,), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.float32),
            jax.ShapeDtypeStruct((n,), jnp.float32),
            jax.ShapeDtypeStruct((k, d), jnp.float32),
            jax.ShapeDtypeStruct((k,), jnp.float32),
        ],
        interpret=interpret,
    )(points, centroids, c2, prev_assign.astype(jnp.int32),
      active.astype(jnp.float32), pad_mask.astype(jnp.float32))
