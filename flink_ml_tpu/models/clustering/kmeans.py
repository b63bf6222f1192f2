"""KMeans — the framework's vertical slice, TPU-native.

Capability mirror of ``flink-ml-lib/.../clustering/kmeans/KMeans.java:79-337``
+ ``KMeansModel.java:62-214`` + ``KMeansParams.java``/``KMeansModelParams``.

The reference implements one Lloyd's iteration as a dataflow subgraph:
broadcast centroids → two-input cache-and-assign operator
(``KMeans.java:238-315``) → keyed window reduce (``CentroidAccumulator``) →
parallelism-1 window average (``KMeans.java:172-196``) → feedback edge.  On
TPU the same epoch is three fused XLA ops on sharded arrays:

- assign   = pairwise-distance argmin (one MXU matmul via the
             ||x||^2 - 2xc + ||c||^2 expansion)
- reduce   = one-hot^T @ points matmul (MXU) — replaces the keyed shuffle +
             reduce; XLA inserts the psum over the data axis of the mesh
- feedback = centroids stay in HBM between epochs (donated buffers)

and the whole ``maxIter`` loop compiles into a single XLA program
(``iterate`` fused mode) — zero host round-trips, zero network shuffles
inside the iteration body.
"""

from __future__ import annotations

import ctypes
from dataclasses import dataclass
from functools import lru_cache, partial
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...api.stage import Estimator, Model
from ...data.table import Table
from ...distance import DistanceMeasure
from ...iteration import (
    IterationBodyResult,
    IterationConfig,
    Workset,
    iterate,
    with_program_key,
)
from ...linalg import float32_rows, stack_vectors
from ...obs.trace import tracer
from ...params.param import (
    BoolParam,
    IntParam,
    ParamValidators,
    StringParam,
)
from ...params.shared import (
    HasDistanceMeasure,
    HasFeaturesCol,
    HasMaxIter,
    HasPredictionCol,
    HasSeed,
)
from ...parallel.mesh import (
    default_mesh,
    local_axis_multiple,
    fetch_replicated,
    mesh_process_count,
    put_sharded,
    put_sharded_in_pieces,
    replicate,
    rows_a_put,
    shard_devices,
)
from ...utils import persist
from ...utils.padding import pad_rows_to_bucket, pad_rows_with_mask

__all__ = ["KMeans", "KMeansModel", "KMeansParams", "KMeansModelParams"]


class KMeansModelParams(HasDistanceMeasure, HasFeaturesCol, HasPredictionCol):
    """``KMeansModelParams.java`` mixin set."""


class KMeansParams(KMeansModelParams, HasSeed, HasMaxIter):
    """``KMeansParams.java``: adds K (>= 2) and the training-only params.

    ``tiePolicy`` (beyond-reference, TPU-specific) picks the Pallas fit
    kernel's handling of EXACTLY-tied point-to-centroid distances:

    - ``"first"`` (default): first-index argmin — EXACTLY the
      reference's and the XLA body's single-assignment Lloyd's
      semantics, ties included, computed without Mosaic's slow argmin
      loop (smallest tied column index via where/min/compare — cheaper
      than "split"'s division).
    - ``"split"``: fractional assignment across the tied minimisers
      (exact expected-assignment semantics: total cluster mass always
      sums to n).
    - ``"fast"`` (opt-in via ``setTiePolicy``; ``kmeans_hibench.fit``
      measures what ``fit`` plans, i.e. the "first" default): a tied point
      counts toward EVERY minimizing centroid — its mass is
      double-counted, biasing the tied centroids' means toward it.  On
      continuous features exact f32 ties are measure-zero, so this is
      free; on DISCRETE/quantized features (integer grids, one-hot),
      distinct equidistant centroids are common and "fast" measurably
      changes the fit.  Its time against "split" and "first" is not
      measured on the chip.

    The XLA fallback path (non-TPU, small n, non-euclidean) always uses
    first-index argmin and ignores this param."""

    K = IntParam("k", "Number of clusters.", default=2,
                 validator=ParamValidators.gt_eq(2))
    INIT_MODE = StringParam(
        "initMode",
        "Initial centroid selection: 'random' (the reference's "
        "shuffle-take-k) or 'k-means++' (distance-weighted seeding, one "
        "fused device program).",
        default="random",
        validator=ParamValidators.in_array(["random", "k-means++"]))
    TIE_POLICY = StringParam(
        "tiePolicy",
        "Pallas-kernel handling of exactly-tied distances: 'first' "
        "(reference argmin semantics), 'fast', or 'split'.",
        default="first",
        validator=ParamValidators.in_array(["first", "fast", "split"]))
    WORKSET = BoolParam(
        "workset",
        "Delta/workset iteration mode: thread Hamerly center-movement "
        "bounds through the fused fit loop and exit the while_loop at "
        "Lloyd's fixed point instead of always running maxIter rounds.  "
        "Settled points keep cached assignments, shrinking the points "
        "SCORED per round (the fit report's accounting; the fused "
        "program still evaluates dense shapes, so the wall-clock win "
        "today is the early exit).  Off TPU the body is XLA — final "
        "centroids bit-identical to the XLA BSP fit (first-index "
        "argmin; tiePolicy does not apply).  On TPU the registry plans "
        "the fused scoring+stats kernel (op kmeans_workset_update) "
        "above the Pallas row threshold: same assignments, stats equal "
        "to f32 summation order.  The fit records a per-round "
        "convergence report in estimator.last_workset_report.",
        default=False)

    def get_workset(self) -> bool:
        return self.get(KMeansParams.WORKSET)

    def set_workset(self, value: bool):
        return self.set(KMeansParams.WORKSET, value)

    def get_k(self) -> int:
        return self.get(KMeansParams.K)

    def set_k(self, value: int):
        return self.set(KMeansParams.K, value)

    def get_tie_policy(self) -> str:
        return self.get(KMeansParams.TIE_POLICY)

    def set_tie_policy(self, value: str):
        return self.set(KMeansParams.TIE_POLICY, value)

    def get_init_mode(self) -> str:
        return self.get(KMeansParams.INIT_MODE)

    def set_init_mode(self, value: str):
        return self.set(KMeansParams.INIT_MODE, value)


def _pad_points(points: np.ndarray, mesh, row_multiple: int = 1,
                fill: str = "first_row",
                cross_host_checked: bool = False) -> tuple:
    """The host half of host -> device on a mesh of SEVERAL PROCESSES (the
    only one left since PR 39; a one-process mesh pads on its devices,
    :func:`_put_and_lay_out` and :func:`_put_and_lay_out_sharded`, and the
    tests keep this as the statement of what they must make): pad rows to
    a multiple of the data-axis size (and of ``row_multiple`` per shard;
    mask marks real rows).  The caller shards the batch dim of both
    (``put_sharded(.., P("data"))``).  With a remainder this is a whole
    copy of the points (``pad_rows_with_mask``), without one it is the
    array it was given.

    On a process-spanning mesh ``points`` is THIS process's shard; each
    host pads to its local device multiple and the global array assembles
    over processes.  Equal padded counts are required — validated here
    unless the caller already allgathered row counts
    (``cross_host_checked``)."""
    multiple = local_axis_multiple(mesh, row_multiple=row_multiple)
    padded, mask = pad_rows_with_mask(points, multiple, fill=fill)
    if mesh_process_count(mesh) > 1 and not cross_host_checked:
        from jax.experimental import multihost_utils

        rows = np.asarray(multihost_utils.process_allgather(
            np.asarray([padded.shape[0]], np.int64))).reshape(-1)
        if not np.all(rows == rows[0]):
            raise ValueError(
                "multi-host KMeans requires equal padded row counts per "
                f"process; got {rows.tolist()}")
    return padded, mask


#: Rows ``_rows_on_device`` gives their layout at a time: a reshape to
#: rows of ``d`` < 128 floats is lane-padded to 512 B a row on the chip, so
#: done whole it would reserve 10 GB beside 20 M rows; done this many at a
#: time the padded piece stays out of HBM (compiled for a v5e: no
#: temporary at all).
_RELAYOUT_ROWS = 1 << 16


@lru_cache(maxsize=None)
def _rows_on_device(shape: tuple, pad: int, fill: str, sharding):
    """The jitted program that makes ``pad_rows_with_mask``'s two arrays
    where the rows already are.  Its argument is the C-order buffer of
    the ``shape`` = (n, d) rows as it was put: one dimension, so the
    runtime transposes nothing on the host on the way (the chip keeps rows
    whose width is no multiple of 128 column-major, and a put of the 2-D
    array makes that layout tile by tile on the host's threads).  It gives
    the rows in that layout, ``_RELAYOUT_ROWS`` at a time (the last piece
    overlaps the one before it, so that every piece has one shape), with
    ``pad`` rows of ``fill`` after the last one, and the float32 mask of
    the real rows, both under ``sharding``.  One program per shapes, kept
    for the process (as ``_predict``'s jit keeps its own): a refit neither
    traces nor compiles."""
    n, d = shape

    def rows_and_mask(flat):
        points = _placed(jnp.zeros((n + pad, d), flat.dtype), flat, 0, n)
        return _filled(points, n, pad, fill), _row_mask(n, pad)

    return jax.jit(rows_and_mask, out_shardings=(sharding, sharding))


def _placed(points, flat, first, rows: int):
    """``points`` with the ``rows`` rows of the C-order buffer ``flat`` at
    row ``first``, given their layout ``_RELAYOUT_ROWS`` at a time."""
    d = points.shape[1]
    step = min(rows, _RELAYOUT_ROWS)

    def place(i, points):
        start = jnp.minimum(i * step, rows - step)
        piece = jax.lax.dynamic_slice(flat, (start * d,), (step * d,))
        return jax.lax.dynamic_update_slice(
            points, piece.reshape(step, d), (first + start, 0))

    return jax.lax.fori_loop(0, -(-rows // step) if rows else 0, place,
                             points)


def _filled(points, n: int, pad: int, fill: str, first_row=None):
    """``points`` with its ``pad`` last rows filled: with ``first_row``
    (``(1, d)``; the array's own first row unless given: a shard other
    than the first is filled with the TABLE's first row), or left zero."""
    if pad and fill == "first_row":
        row = points[:1] if first_row is None else first_row
        return jax.lax.dynamic_update_slice(
            points, jnp.broadcast_to(row, (pad, points.shape[1])), (n, 0))
    return points


def _row_mask(n: int, pad: int):
    return (jnp.arange(n + pad) < n).astype(jnp.float32)


def _put_rows(n: int, d: int, devices: int = 1) -> int:
    """Rows of ``(n, d)`` float32 points a put hands a device at a time,
    where ``devices`` get as many each in one round: all of them while the
    round stays under ``parallel/mesh.py: PUT_BYTES`` (the cap and why it
    is 2 GiB a process are there), else whole ``_RELAYOUT_ROWS``."""
    return rows_a_put(n, 4 * d * devices, _RELAYOUT_ROWS)


@lru_cache(maxsize=None)
def _rows_from_pieces(shape: tuple, pad: int, fill: str, sharding):
    """:func:`_rows_on_device` for a buffer that was put in pieces, as
    three jitted programs: ``empty() -> (points, mask)``, ``place(points,
    flat, first) -> points`` for the piece whose first row is ``first``
    (``points`` donated: the rows are laid out in place, a piece's buffer
    is free once it is placed; one program a piece length, so two a
    table), and ``finish(points)`` for the ``fill`` rows
    (``finish(points, first_row)`` fills with that row instead of the
    array's own first: a shard's program, :func:`_put_and_lay_out_sharded`)."""
    n, d = shape

    def empty():
        return jnp.zeros((n + pad, d), jnp.float32), _row_mask(n, pad)

    def place(points, flat, first):
        return _placed(points, flat, first, flat.shape[0] // d)

    return (jax.jit(empty, out_shardings=(sharding, sharding)),
            jax.jit(place, donate_argnums=0, out_shardings=sharding),
            jax.jit(lambda points, first_row=None: _filled(
                points, n, pad, fill, first_row),
                    donate_argnums=0, out_shardings=sharding))


def _put_and_lay_out(host_points: np.ndarray, plan, mesh, spec) -> tuple:
    """Host -> device on a mesh of one process whose ``data`` axis is one
    device: the rows' buffer put flat and laid out, padded and masked on
    the device, under the spans ``fit.upload`` and ``fit.arrange.pad``.
    Up to ``PUT_BYTES`` that is one asynchronous put (the transfer runs
    while the host draws the start) and :func:`_rows_on_device`; a larger
    table goes up a piece at a time, each waited for, and the device lays
    a piece out (:func:`_rows_from_pieces`) while the next one arrives."""
    from jax.sharding import NamedSharding

    n = len(host_points)
    put_rows = _put_rows(*host_points.shape)
    laid_out = (host_points.shape, -n % plan.local_multiple(mesh),
                plan.fill, NamedSharding(mesh, spec))

    def put(first):
        return put_sharded(host_points[first:first + put_rows].reshape(-1),
                           mesh, spec)

    def arranging():
        return tracer.span("fit.arrange.pad", "fit")

    if put_rows == n:
        with tracer.span("fit.upload", "fit") as upload:
            upload.note(pieces=1)
            flat = put(0)
        with tracer.span("fit.arrange", "fit"), arranging():
            return _rows_on_device(*laid_out)(flat)
    empty, place, finish = _rows_from_pieces(*laid_out)
    with tracer.span("fit.arrange", "fit"), arranging():
        points, mask = empty()
    for first in range(0, n, put_rows):
        with tracer.span("fit.upload", "fit") as upload:
            if not first:
                upload.note(pieces=-(-n // put_rows))
            flat = put(first)
            flat.block_until_ready()
        with tracer.span("fit.arrange", "fit"), arranging():
            points = place(points, flat, first)
            if first + put_rows >= n:
                points = finish(points)
    return points, mask


def _put_and_lay_out_sharded(host_points: np.ndarray, plan, mesh,
                             spec) -> tuple:
    """:func:`_put_and_lay_out` on a mesh of one process whose ``data``
    axis has SEVERAL devices: what ``_pad_points`` + ``put_sharded`` make
    (the rows padded at the table's end to the mesh's multiple and divided
    in contiguous runs, the mask beside them), bit for bit, with no copy
    of the table on the host.  Each device gets its run of rows flat, in
    pieces that are views of the column (``parallel/mesh.py:
    put_sharded_in_pieces``: the devices' pieces of a round in flight
    together, a round of at most ``PUT_BYTES`` in all, waited for before
    the next: the runtime's bound is on what the process has in flight), and
    lays it out, pads and masks it ITSELF with the one-device route's
    three programs (:func:`_rows_from_pieces` under that device's own
    sharding: the runs' lengths differ where the table does not divide,
    and arrays of different lengths make no one sharded array without a
    copy; a program a device is also what lets a device start on its piece
    when that piece has arrived).  The finished per-device arrays ARE the
    global arrays' shards (``jax.make_array_from_single_device_arrays``:
    nothing moves).  Same spans as the one-device route; ``fit.upload``
    notes ``pieces``, the puts the fullest device received."""
    from jax.sharding import NamedSharding, SingleDeviceSharding

    n, d = host_points.shape
    lines = shard_devices(mesh)
    shard_rows = (n + -n % plan.local_multiple(mesh)) // len(lines)
    put_rows = _put_rows(shard_rows, d, mesh.size)
    programs, laid_out, short = {}, {}, []
    for i, line in enumerate(lines):
        rows = min(max(n - i * shard_rows, 0), shard_rows)
        for device in line:
            programs[device] = _rows_from_pieces(
                (rows, d), shard_rows - rows, plan.fill,
                SingleDeviceSharding(device))
            if rows < shard_rows and plan.fill == "first_row":
                short.append((device, i > 0))

    def arranging():
        return tracer.span("fit.arrange.pad", "fit")

    with tracer.span("fit.arrange", "fit"), arranging():
        for device, (empty, _, _) in programs.items():
            laid_out[device] = empty()
    rounds = put_sharded_in_pieces(host_points, mesh, shard_rows, put_rows)
    pieces = -(-max(min(shard_rows, n), 1) // put_rows)
    for piece in range(pieces):
        with tracer.span("fit.upload", "fit") as upload:
            if not piece:
                upload.note(pieces=pieces)
            first, put = next(rounds)
        with tracer.span("fit.arrange", "fit"), arranging():
            for line, flats in zip(lines, put):
                for device, flat in zip(line, flats):
                    if flat.shape[0]:
                        points, mask = laid_out[device]
                        laid_out[device] = (programs[device][1](
                            points, flat, first), mask)
    with tracer.span("fit.arrange", "fit"), arranging():
        for device, of_the_table in short:
            # the fill rows are the TABLE's first row on every shard
            points, mask = laid_out[device]
            row = (jax.device_put(host_points[:1], device) if of_the_table
                   else None)
            laid_out[device] = programs[device][2](points, row), mask
        sharding = NamedSharding(mesh, spec)
        total = shard_rows * len(lines)
        return tuple(
            jax.make_array_from_single_device_arrays(
                shape, sharding, [pair[j] for pair in laid_out.values()])
            for j, shape in enumerate(((total, d), (total,))))


@partial(jax.jit, static_argnums=0)
def _predict(measure: DistanceMeasure, pts, centroids):
    """Module-level jit (cache hit on every transform after the first;
    DistanceMeasure instances are registry singletons, hashable by id)."""
    return jnp.argmin(measure.pairwise(pts, centroids), axis=1)


def _kmeans_chain_kernel(static, params, cols):
    """Chain-fused nearest-centroid assign (same expression as
    ``_predict``; the measure singleton rides the plan-static tuple)."""
    from ...api.chain import as_matrix

    (fcol, acol, measure) = static
    pts = as_matrix(cols[fcol])
    dists = measure.pairwise(pts.astype(jnp.float32), params["centroids"])
    return {acol: jnp.argmin(dists, axis=1)}


def _draw_prefix(lib, rng: np.random.Generator, n: int,
                 k: int) -> Optional[np.ndarray]:
    """``rng.permutation(n)[:k]`` by ``native/kmeans_start.cpp``, drawn
    through ``rng``'s own bit generator, which ends where the permutation
    leaves it; ``None`` where the pass declines (then before its first
    draw)."""
    out = np.empty(k, np.int64)
    bits = rng.bit_generator
    with bits.lock:
        iface = bits.ctypes
        status = lib.perm_prefix(
            ctypes.cast(iface.next_uint32, ctypes.c_void_p), iface.state,
            n, k, out.ctypes.data)
    return out if status == 0 else None


@lru_cache(maxsize=None)
def _native_start():
    """``native/kmeans_start.cpp`` built and loaded, or ``None`` on a
    machine with no ``make`` and no built library, or where the pass does
    not give this NumPy's permutation (a NumPy whose shuffle changed then
    costs speed, not the answer): once a process, on a small case."""
    from ...utils.native_lib import load_native_lib

    lib = load_native_lib("kmeans_start")
    if lib is None:
        return None
    lib.perm_prefix.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                ctypes.c_int64, ctypes.c_int64,
                                ctypes.c_void_p]
    lib.perm_prefix.restype = ctypes.c_int
    native, numpy_ = np.random.default_rng(7), np.random.default_rng(7)
    prefix = _draw_prefix(lib, native, 1000, 10)
    if (prefix is None
            or not np.array_equal(prefix, numpy_.permutation(1000)[:10])
            or native.bit_generator.state != numpy_.bit_generator.state):
        return None
    return lib


def random_start(n: int, k: int, seed: int) -> tuple[np.ndarray, bool]:
    """``np.random.default_rng(seed).permutation(n)[:k]``, and whether the
    native pass drew it: the k indices come out of every draw of the
    shuffle without a permutation of n being built (160 MB of random
    swaps at 20 M rows).  NumPy draws them where there is no library or
    the pass declines: ``n - 1`` needs more than 32 bits (NumPy's shuffle
    draws 64 there) or its buffer of n draws cannot be had."""
    rng = np.random.default_rng(seed)
    lib = _native_start()
    idx = None if lib is None else _draw_prefix(lib, rng, n, k)
    if idx is not None:
        return idx, True
    return rng.permutation(n)[:k], False


def _random_centroids(points: np.ndarray, k: int,
                      seed: int) -> tuple[np.ndarray, bool]:
    n = points.shape[0]
    if n < k:
        raise ValueError(f"Need at least k={k} points, got {n}")
    idx, native = random_start(n, k, seed)
    return points[idx], native


def select_random_centroids(points: np.ndarray, k: int, seed: int) -> np.ndarray:
    """Semantics of ``KMeans.selectRandomCentroids`` (``KMeans.java:317-336``):
    shuffle all points with the seed, take k."""
    return _random_centroids(points, k, seed)[0]


def select_kmeanspp_centroids(points: np.ndarray, k: int,
                              seed: int) -> np.ndarray:
    """k-means++ seeding (Arthur & Vassilvitskii 2007) as ONE fused device
    program: a ``fori_loop`` of k-1 rounds, each doing one (n, d) pass —
    the squared-distance-to-nearest-chosen vector updates incrementally
    (``d2 = min(d2, ||x - c||^2)``) and the next center draws
    categorically with probability proportional to ``d2``.  No
    per-round host round trip (a host-looped version would pay one
    dispatch and one fetch per center); beyond-reference init quality
    knob (the reference only has shuffle-take-k)."""
    n = points.shape[0]
    if n < k:
        raise ValueError(f"Need at least k={k} points, got {n}")
    out = _kmeanspp_run(jnp.asarray(points, jnp.float32),
                        jax.random.PRNGKey(seed), k)
    return np.asarray(out)


@partial(jax.jit, static_argnames=("k",))
def _kmeanspp_run(pts, key, k: int):
    key, sub = jax.random.split(key)
    first = jax.random.randint(sub, (), 0, pts.shape[0])
    chosen = jnp.zeros((k, pts.shape[1]), pts.dtype).at[0].set(pts[first])
    d2 = jnp.sum(jnp.square(pts - pts[first]), axis=1)

    def round_(i, carry):
        chosen, d2, key = carry
        key, sub = jax.random.split(key)
        # log-prob of d2 with zeros mapped to -inf (already-chosen
        # points can never repeat while any unchosen mass remains)
        logits = jnp.where(d2 > 0, jnp.log(d2), -jnp.inf)
        idx = jax.random.categorical(sub, logits)
        c = pts[idx]
        chosen = chosen.at[i].set(c)
        d2 = jnp.minimum(d2, jnp.sum(jnp.square(pts - c), axis=1))
        return chosen, d2, key

    chosen, _, _ = jax.lax.fori_loop(1, k, round_, (chosen, d2, key))
    return chosen


# mode -> (points, k, seed) -> (centroids, whether the native pass drew them)
_INIT_MODES = {"random": _random_centroids,
               "k-means++": lambda points, k, seed: (
                   select_kmeanspp_centroids(points, k, seed), False)}


def _stats_from_assign(k: int, points, mask, assign):
    """(sums, counts) from a per-point assignment vector — the reduce half
    of :func:`_assign_stats`, split out so the workset body (which merges
    cached and fresh assignments) runs the EXPRESSION-IDENTICAL einsum over
    all n points: identical assignments => bit-identical sums, which is
    what makes bound-filtered KMeans exact."""
    onehot = jax.nn.one_hot(assign, k, dtype=points.dtype) # (n, k)
    onehot = onehot * mask[:, None]                        # drop padding
    sums = jnp.einsum("nk,nd->kd", onehot, points)         # MXU reduce
    return sums, jnp.sum(onehot, axis=0)


def _assign_stats(measure: DistanceMeasure, k: int, points, mask,
                  centroids):
    """THE Lloyd's statistics: (sums (k, d), counts (k,)) of the masked
    points by nearest centroid — shared by the in-core epoch body and the
    out-of-core per-batch accumulation so the two can never diverge."""
    dists = measure.pairwise(points, centroids)            # (n, k)
    assign = jnp.argmin(dists, axis=1)                     # (n,)
    return _stats_from_assign(k, points, mask, assign)


def _update_centroids(centroids, sums, counts, xp=jnp):
    """Empty clusters keep their previous centroid (the reference's
    keyed-reduce would silently drop them; keeping is strictly better and
    identical when all clusters are non-empty, as in KMeansTest).
    ``xp`` lets the out-of-core path apply the identical policy on its
    host float64 accumulators (jnp would silently downcast to f32)."""
    counts = counts[:, None]
    return xp.where(counts > 0, sums / xp.maximum(counts, 1.0), centroids)


def kmeans_epoch_step(measure: DistanceMeasure, k: int):
    """One Lloyd's iteration as a pure jnp function (points, mask are closed
    over by ``iterate``'s static data).  The body states its program key
    (``iteration/body.py: with_program_key``): the measure, ``k`` and the
    module's functions the trace calls (by what their names hold now: a
    test that patches one gets its own program)."""

    def body(centroids, epoch, data):
        points, mask = data
        with jax.named_scope("kmeans.stats"):
            sums, counts = _assign_stats(measure, k, points, mask, centroids)
        with jax.named_scope("kmeans.update"):
            new_centroids = _update_centroids(centroids, sums, counts)
        return IterationBodyResult(feedback=new_centroids)

    return with_program_key(body, kmeans_epoch_step, measure.name,
                            type(measure), k, _assign_stats,
                            _stats_from_assign, _update_centroids)


def workset_points_scored(active_fraction, n_real: int,
                          n_padded: int) -> np.ndarray:
    """Points scored per round, derived from the POST-round
    active-fraction trace: round 0 rescored every real point (BSP round
    0), round ``e`` scores round ``e-1``'s survivors (the fraction is
    over padded rows).  THE one copy of this convention: the fit report
    reads it."""
    frac = np.asarray(active_fraction, np.float64)
    if not frac.size:
        return np.zeros((0,))
    return np.concatenate([[float(n_real)], frac[:-1] * n_padded])


#: relative slack on the Hamerly bound decay: f32 rounding of
#: ``upper + drift`` / ``lower - drift`` may land BELOW the true bound, so
#: every decayed bound is nudged conservatively outward — a too-loose
#: bound only keeps a settled point active one more round (wasted score),
#: never freezes a point that could still flip (wrong centroids).
_WS_BOUND_SLACK = 1e-5


def kmeans_workset_update_xla(measure: DistanceMeasure, k: int, points,
                              centroids, prev_assign, active, pad_mask):
    """XLA backend of registry op ``kmeans_workset_update`` — the
    bound-filtered scoring + stats of one workset round, and the parity
    oracle the fused Pallas kernel is matrix-tested against.  Returns
    ``(assign, d_best, d_second, sums, counts)`` with ``assign`` already
    merged under the active mask (the settled points' cached
    assignments); ``d_best``/``d_second`` are the FRESH per-point
    distances — the caller keeps its old bounds where settled."""
    dists = measure.pairwise(points, centroids)             # (n, k)
    fresh = jnp.argmin(dists, axis=1).astype(jnp.int32)
    is_min = jnp.arange(k, dtype=jnp.int32)[None, :] == fresh[:, None]
    d_best = jnp.min(dists, axis=1)
    d_second = jnp.min(jnp.where(is_min, jnp.inf, dists), axis=1)
    assign = jnp.where(active > 0, fresh, prev_assign).astype(jnp.int32)
    sums, counts = _stats_from_assign(k, points, pad_mask, assign)
    return assign, d_best, d_second, sums, counts


def kmeans_workset_epoch_step(measure: DistanceMeasure, k: int, *,
                              block_n: Optional[int] = None,
                              interpret: bool = False):
    """One bound-filtered Lloyd's iteration as an ``iterate`` workset body
    (Hamerly 2010 adapted to the device-resident mask).

    ``block_n`` switches the scoring+stats block onto the fused Pallas
    kernel (``ops/kmeans_pallas.py::kmeans_workset_update`` — registry
    op ``kmeans_workset_update``): distances, first-index argmin, the
    second-best pass, the cached-assignment merge, AND the stats reduce
    run as one VMEM kernel, so the (n, k) intermediates never touch HBM.
    Per-point outputs are expression-identical to the XLA block below;
    the stats accumulate tile-sequentially (f32-summation-order
    equivalent, not bitwise — the registry plans it only on TPU, so the
    CPU tier's bit-exactness contract vs BSP is untouched).  The bound
    decay, settle detection, and centroid update are shared verbatim.

    Per-point bound state rides ``workset.bounds``: the cached assignment,
    an UPPER bound on the distance to the assigned centroid, and a LOWER
    bound on the distance to every other centroid.  A masked-out point is
    one whose ``upper < lower`` after decaying both by the centroids'
    movement — the triangle inequality then proves its argmin cannot have
    flipped, so its CACHED assignment feeds the stats reduce and the
    result is bit-identical to the BSP body (the reduce itself still runs
    the same einsum over all n points — identical assignments, identical
    f32 summation order).  What shrinks is the LOGICAL scoring work: the
    number of points whose (n, k) distance rows a round must re-score
    (``points_scored`` in the fit report) — the fused
    fixed-shape program still evaluates densely, so that count is what a
    compacting backend banks, while the early exit below is the physical
    saving available today.

    The body drives the workset to empty at Lloyd's fixed point: a round
    with zero assignment flips produces bit-identical sums, hence zero
    centroid drift, hence no point left to rescore — the driver's
    active-fraction criterion then exits the ``lax.while_loop`` strictly
    before ``max_epochs`` whenever the fit converges early.

    Euclidean only: the bound decay leans on the triangle inequality in
    TRUE distance space (``EuclideanDistanceMeasure.pairwise`` returns
    root distances, not squares)."""
    if measure.name != "euclidean":
        raise ValueError(
            "workset KMeans requires the euclidean measure (Hamerly "
            f"bounds need the triangle inequality), got {measure.name!r}")

    def body(centroids, ws, epoch, data):
        points, pad_mask = data
        active = ws.mask                                    # (n,) f32 0/1
        prev_assign = ws.bounds["assign"]
        if block_n is not None:
            from ...ops.kmeans_pallas import kmeans_workset_update

            assign, d_best, d_second, sums, counts = kmeans_workset_update(
                points, centroids, prev_assign, active, pad_mask,
                block_n=block_n, interpret=interpret)
        else:
            assign, d_best, d_second, sums, counts = \
                kmeans_workset_update_xla(measure, k, points, centroids,
                                          prev_assign, active, pad_mask)
        on = active > 0
        # merge: active points take the fresh score, settled points keep
        # their cached assignment/bounds (provably identical); assign is
        # already merged by the scoring fn, so the flip count over it
        # equals the fresh-vs-cached count (inactive terms are masked)
        upper = jnp.where(on, d_best, ws.bounds["upper"])
        lower = jnp.where(on, d_second, ws.bounds["lower"])
        changed = jnp.sum(active * (assign != prev_assign))
        new_centroids = _update_centroids(centroids, sums, counts)

        drift = jnp.sqrt(jnp.maximum(
            jnp.sum(jnp.square(new_centroids - centroids), axis=1), 0.0))
        drift_max = jnp.max(drift)
        # conservative f32 decay (see _WS_BOUND_SLACK)
        upper = upper + drift[assign]
        upper = upper + jnp.abs(upper) * _WS_BOUND_SLACK
        lower = lower - drift_max
        lower = lower - jnp.abs(lower) * _WS_BOUND_SLACK
        # fixed point: nothing moved and nothing flipped => every future
        # BSP round is a bit-identical no-op — drain the workset entirely
        settled = jnp.logical_and(drift_max == 0.0, changed == 0.0)
        next_active = jnp.logical_and(upper >= lower,
                                      jnp.logical_not(settled))
        new_mask = jnp.where(pad_mask > 0,
                             next_active.astype(jnp.float32), 0.0)
        new_ws = Workset(new_mask, {"assign": assign, "upper": upper,
                                    "lower": lower})
        return IterationBodyResult(feedback=(new_centroids, new_ws))

    return body


def kmeans_epoch_step_pallas(k: int, mesh=None, *, block_n: int = 8192,
                             k_tile: Optional[int] = None,
                             tie_policy: str = "first",
                             interpret: bool = False):
    """One Lloyd's iteration on the fused Pallas kernel
    (``ops/kmeans_pallas.py``): score/one-hot tiles stay in VMEM, so the
    points are read from HBM once an iteration and nothing else is.
    ``(block_n, k_tile)`` are the plan's (``kmeans_pallas.stats_tiles``):
    ``k_tile`` None is the feature-major kernel with all of k resident,
    a number the kernel tiled over k.

    ``tie_policy="first"`` (the default, what ``KMeans.fit`` plans via
    its ``tiePolicy`` param) keeps the XLA body's exact first-index
    argmin semantics; ``"split"`` gives fractional expected-assignment
    ties, ``"fast"`` assigns exactly-tied points to every minimizing
    centroid — see ``KMeansParams.TIE_POLICY``.

    Requires zero-filled padding (``fill="zero"``) with the per-shard row
    count a multiple of ``block_n``; euclidean metric only.  With a
    multi-device ``mesh``, per-shard partial sums meet in one ICI psum.

    ``jax.named_scope`` s say what a device operation is for:
    ``kmeans.stats`` (points and centroids to sums and counts: the kernel
    and the XLA around it), on a multi-device mesh ``kmeans.reduce`` (the
    ``psum`` of the shards' sums and counts, and nothing else:
    ``update_stats_sharded``) and ``kmeans.update`` (the padding's
    correction, the division, the empty clusters).

    The body states its program key (``iteration/body.py:
    with_program_key``): every argument here, the mesh among them, and
    the kernel module's functions the trace calls; the metric is the
    kernel's one, euclidean."""
    from ...ops import kmeans_pallas as kp

    sharded = mesh is not None and int(mesh.shape.get("data", 1)) > 1

    def body(centroids, epoch, data):
        points, mask = data
        with jax.named_scope("kmeans.stats"):
            if sharded:
                sums, counts = kp.update_stats_sharded(
                    points, centroids, mesh, block_n=block_n, k_tile=k_tile,
                    tie_policy=tie_policy, interpret=interpret)
            else:
                sums, counts = kp.kmeans_update_stats(
                    points, centroids, block_n=block_n, k_tile=k_tile,
                    tie_policy=tie_policy, interpret=interpret)
        with jax.named_scope("kmeans.update"):
            n_pad = points.shape[0] - jnp.sum(mask)
            counts = kp.pad_correction(counts, centroids, n_pad,
                                       tie_policy=tie_policy)[:, None]
            # No clamp-to-1 here: "split" ties legally produce fractional
            # counts in (0, 1), which must divide as-is.
            safe = jnp.where(counts > 0, counts, 1.0)
            new_centroids = jnp.where(counts > 0, sums / safe, centroids)
        return IterationBodyResult(feedback=new_centroids)

    return with_program_key(body, kmeans_epoch_step_pallas, k, mesh, block_n,
                            k_tile, tie_policy, interpret,
                            kp.kmeans_update_stats, kp.update_stats_sharded,
                            kp.pad_correction)


# Pallas engages only above this row count — below it the XLA path is within
# noise and avoids kernel constraints (zero-fill, block divisibility).
_PALLAS_MIN_ROWS = 65536
_MIN_BLOCKS = 8


def _plan_fit_impl(n: int, d: int, k: int, measure: DistanceMeasure,
                   mesh, tie_policy: str = "first") -> tuple:
    """Pick (impl, block_n, k_tile) for the BSP fit loop via registry op
    ``kmeans_update_stats`` (the Pallas entry's availability gate is the
    TPU backend; its supports predicate is the euclidean metric, the
    row-count threshold, and a viable VMEM tile).  Which of the kernel's
    two layouts, and its tiles, follow from ``(d, k)`` alone
    (``kmeans_pallas.stats_tiles``): feature-major with all of k resident
    (``k_tile`` None) wherever that fits VMEM, else tiled over k.  Padding
    rounds the per-shard row count up to the block, so any supported block
    size works: the largest that leaves a shard ``_MIN_BLOCKS`` blocks.
    The kernel tiled over k assigns by the ``"first"`` policy alone, so
    another ``tiePolicy`` at its shapes takes the XLA body."""
    from ...kernels.registry import lookup
    from ...ops import kmeans_pallas as kp

    entry = lookup("kmeans_update_stats", sig=(n, d, k, measure.name))
    if entry.backend == "pallas":
        block_n, k_tile = kp.stats_tiles(d, k)
        if k_tile is not None and tie_policy != "first":
            return "xla", None, None
        if k_tile is None:
            # measured-not-analytic when the autotune cache is configured
            # (ISSUE 12): the winner is persisted per (d, k, device kind),
            # so only the fleet's first process pays the search
            block_n = kp.pick_block_n_measured(d, k)
        # narrow rows admit blocks of 2^15-2^16 rows: keep a shard at
        # _MIN_BLOCKS of them, so that its fill rows stay a small share
        # and the kernel's pipeline has steps to overlap
        shard_rows = -(-n // int(mesh.shape.get("data", 1)))
        while block_n > 128 and block_n * _MIN_BLOCKS > shard_rows:
            block_n //= 2
        return "pallas", block_n, k_tile
    return "xla", None, None


@dataclass(frozen=True)
class FitPlan:
    """THE per-fit shape/impl contract, derived once and shared by every
    KMeans fit path (in-core BSP, workset, out-of-core streaming) instead
    of each re-deriving k/d padding independently — the workset port must
    not fork a third copy of the padding rules."""

    impl: str                  # "xla" | "pallas"
    block_n: Optional[int]     # Pallas tile rows (None for xla)
    row_multiple: int          # per-shard row-count multiple for padding
    fill: str                  # pad_rows_with_mask fill policy
    k: int
    d: int
    k_tile: Optional[int] = None   # Pallas stats kernel tiled over k

    def notes(self) -> dict:
        """What ``fit.arrange`` notes of the plan: which stats kernel the
        fit took, its tiles, and the share of the MXU passes' operand
        area that is padding at them."""
        from ...ops import kmeans_pallas as kp

        if self.impl != "pallas":
            return {"stats_plan": self.impl}
        return {"stats_plan": ("k_tiled" if self.k_tile else "feature_major"),
                "block_n": self.block_n, "k_tile": self.k_tile or self.k,
                "mxu_padded_share": kp.mxu_padded_share(
                    self.d, self.k, self.k_tile)}

    def local_multiple(self, mesh) -> int:
        """Per-process padded-row multiple on ``mesh`` under this plan."""
        return local_axis_multiple(mesh, row_multiple=self.row_multiple)

    def init_workset(self, pad_mask) -> Workset:
        """The workset bound-state initializer: everything real starts
        active with vacuous bounds (+inf upper / -inf lower forces a full
        first-round rescore, exactly BSP round 0); padding rows are born
        settled so they are never scored OR counted active.  Every bound
        array derives elementwise from ``pad_mask`` so it inherits the
        mask's sharding — the while_loop carry stays consistently sharded
        on a multi-device mesh with no GSPMD resharding."""
        mask = pad_mask.astype(jnp.float32)
        zero = mask * 0.0
        return Workset(
            mask=mask,
            bounds={"assign": zero.astype(jnp.int32),
                    "upper": zero + jnp.asarray(jnp.inf, jnp.float32),
                    "lower": zero - jnp.asarray(jnp.inf, jnp.float32)})


def _fit_plan(n: int, d: int, k: int, measure: DistanceMeasure, mesh, *,
              workset: bool = False, tie_policy: str = "first") -> FitPlan:
    """Build the shared :class:`FitPlan`.  The workset path plans via
    registry op ``kmeans_workset_update``: the fused scoring+stats
    Pallas kernel (PR 10) where available — TPU, euclidean, a viable
    VMEM block, and a single-device data axis (the sharded composition
    is future work) — else the XLA body, which is what every CPU tier
    runs (impl ``"pallas_ws"`` pads by the MASKED contract: the kernel
    takes the pad mask, so first-row fill stays safe).  The BSP path
    falls out of :func:`_plan_fit_impl` exactly as before."""
    if workset:
        from ...kernels.registry import lookup
        from ...ops import kmeans_pallas as kp

        data_devs = int(mesh.shape.get("data", 1)) if mesh else 1
        entry = lookup("kmeans_workset_update",
                       sig=(n, d, k, measure.name, data_devs))
        if entry.backend == "pallas":
            block_n = kp.pick_block_n_workset_measured(d, k)
            return FitPlan("pallas_ws", block_n, block_n, "first_row", k, d)
        return FitPlan("xla", None, 1, "first_row", k, d)
    impl, block_n, k_tile = _plan_fit_impl(n, d, k, measure, mesh,
                                           tie_policy)
    row_multiple, fill = ((block_n, "zero") if impl == "pallas"
                          else (1, "first_row"))
    return FitPlan(impl, block_n, row_multiple, fill, k, d, k_tile)


def kmeans_fit_outofcore(make_reader, k: int, *,
                         measure_name: str = "euclidean",
                         max_iter: int = 20, seed: int = 0, mesh=None,
                         features_key: str = "features",
                         prefetch_depth: int = 2) -> np.ndarray:
    """Out-of-core Lloyd's: the dataset streams from ``make_reader()``
    (a fresh per-epoch iterator of host batch dicts — the same protocol as
    ``sgd_fit_outofcore``) instead of living in HBM; this is the
    replay-per-epoch semantics of the reference's ReplayOperator
    (``operator/ReplayOperator.java:62-311``) at beyond-memory scale.

    Each epoch accumulates per-batch (sums, counts) partial statistics on
    device — batch N+1's host read and transfer overlap batch N's compute
    via ``prefetch_to_device`` — and the centroid update applies once per
    epoch (exact Lloyd's: identical result to the in-core fit on the same
    concatenated rows, asserted in tests).  Initial centroids are a
    seeded shuffle-take-k of the FIRST batch.

    Returns the final (k, d) centroids (host float32)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ...data.prefetch import prefetch_to_device

    mesh = mesh or default_mesh()
    if mesh_process_count(mesh) > 1:
        raise ValueError(
            "kmeans_fit_outofcore is single-host (the prefetch transfer "
            "and init read are per-process); run the reader on each host "
            "and use KMeans.fit with per-process shards for multi-host")
    measure = DistanceMeasure.get_instance(measure_name)

    from ...utils.padding import FixedRowBatcher

    # The shared FitPlan owns the padding rules (n=0: per-batch streaming
    # accumulation is below any Pallas residency threshold by
    # construction, so the plan always lands on the XLA impl) — no
    # independent re-derivation of the row multiple here.
    plan = _fit_plan(0, 1, k, measure, mesh)
    multiple = plan.local_multiple(mesh)
    sharding = NamedSharding(mesh, P("data"))
    # shared fixed-row protocol (first padded batch pins; ragged tail
    # zero-pads with mask 0)
    batcher = FixedRowBatcher(1)

    def to_host_batch(batch):
        pts = np.asarray(batch[features_key], np.float32)
        padded, mask = pad_rows_with_mask(pts, multiple, fill="zero")
        return batcher.pad((padded, mask), have=padded.shape[0])

    batch_stats = jax.jit(lambda c, pts, mask:
                          _assign_stats(measure, k, pts, mask, c))
    add2 = jax.jit(lambda a, b, c, d: (a + c, b + d))

    from ..common.sgd import _reader_for_epoch

    centroids = None
    for iteration in range(max_iter):
        # Two-level accumulation: f32 on device within a window sized so
        # counts stay in f32's exact-integer range (2^24), folded into a
        # host float64 total — billions of rows per epoch cannot silently
        # round away per-batch contributions.
        host_sums = host_counts = None
        sums = counts = None
        window_used = 0
        window = None

        def fold():
            nonlocal host_sums, host_counts, sums, counts, window_used
            if sums is None:
                return
            s64 = np.asarray(jax.device_get(sums), np.float64)
            c64 = np.asarray(jax.device_get(counts), np.float64)
            host_sums = s64 if host_sums is None else host_sums + s64
            host_counts = c64 if host_counts is None else host_counts + c64
            sums = counts = None
            window_used = 0

        # epoch-aware factories (the sgd_fit_outofcore protocol) receive
        # the Lloyd iteration number; Lloyd statistics are order-invariant
        # so per-epoch reshuffled readers change IO pattern only.  NOTE:
        # init below samples the FIRST batch — epoch-varying readers
        # change which rows that is, deterministically in (seed, epoch=0)
        for pts, mask in prefetch_to_device(
                _reader_for_epoch(make_reader, iteration),
                depth=prefetch_depth,
                transform=to_host_batch,
                sharding=(sharding, sharding)):
            if centroids is None:
                # init: seeded shuffle-take-k of the first batch's rows
                first = np.asarray(pts)[np.asarray(mask) > 0]
                centroids = jnp.asarray(
                    select_random_centroids(first, k, seed))
            if window is None:
                window = max(1, (1 << 23) // batcher.rows)
            s, c = batch_stats(centroids, pts, mask)
            if sums is None:
                sums, counts = s, c
            else:
                sums, counts = add2(sums, counts, s, c)
            window_used += 1
            if window_used >= window:
                fold()
        fold()
        if host_sums is None:
            raise ValueError("make_reader() returned an empty epoch")
        centroids = jnp.asarray(_update_centroids(
            np.asarray(jax.device_get(centroids), np.float64),
            host_sums, host_counts, xp=np).astype(np.float32))
    return np.asarray(jax.device_get(centroids), np.float32)


class KMeans(KMeansParams, Estimator["KMeansModel"]):
    """Estimator: Lloyd's algorithm for ``maxIter`` rounds
    (termination parity with ``TerminateOnMaxIterationNum``,
    ``common/iteration/TerminateOnMaxIterationNum.java:34-55``)."""

    def fit(self, *inputs) -> "KMeansModel":
        (table,) = inputs
        with tracer.fit_span(type(self).__name__):
            return self._fit(table)

    def _fit(self, table: Table) -> "KMeansModel":
        """``fit`` under its root span.  The phase spans (``fit.gather``,
        ``fit.arrange``, ``fit.upload``, then ``iterate.dispatch`` inside
        ``iterate``, ``fit.fetch``) follow each other without a gap and
        add no fence: each covers what the host does in it.

        How the points reach the device follows what the column and the
        mesh are; no parameter chooses.  The column takes
        :func:`~flink_ml_tpu.linalg.float32_rows`' route: a C-contiguous
        float32 array is read IN PLACE (``fit`` never writes into it, and
        the table must not be mutated from another thread while ``fit``
        runs, which was never allowed), any other numeric array is
        converted once, an object column of vectors is stacked first.
        Which mesh takes which route:

        - one process, one device on ``data`` (:func:`_put_and_lay_out`):
          the rows are put as they are (their buffer, flat, whole up to
          ``parallel/mesh.py: PUT_BYTES``, else a piece at a time), BEFORE
          the start is drawn, so that the transfer runs under the start's
          draw (:func:`random_start`), and the device gives them their
          layout, the mask, and the fill rows of a remainder against the
          plan's row multiple (:func:`_rows_on_device`; no remainder, no
          row added);
        - one process, several devices on ``data``
          (:func:`_put_and_lay_out_sharded`, PR 39): the same, a device a
          contiguous run of the rows: every device's run flat, in pieces,
          the devices' pieces in flight together, laid out, padded and
          masked on its own device; no copy of the table on the host;
        - several processes: every process passed its own shard, which is
          padded on the host (:func:`_pad_points`) and put after the
          start, as before.

        ``fit.arrange`` notes ``shards``, the devices on ``data`` the rows
        were divided over, and ``fit.upload`` ``pieces``, the puts the
        fullest device received."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        # report describes THIS fit only — a reused estimator must not
        # serve a stale report from an earlier workset fit
        self.last_workset_report = None
        mesh = default_mesh()
        k = self.get_k()
        measure = DistanceMeasure.get_instance(self.get_distance_measure())

        with tracer.span("fit.gather", "fit"):
            column = table[self.get_features_col()]
            with tracer.span("fit.gather.stack", "fit"):
                if column.dtype == object:
                    column = stack_vectors(column)
            with tracer.span("fit.gather.cast", "fit"):
                host_points = float32_rows(column)
        with tracer.span("fit.arrange", "fit") as arrange:
            n_for_plan = host_points.shape[0]
            multi_host = mesh_process_count(mesh) > 1
            if multi_host:
                # Every process passed its own shard.  ONE allgather of the
                # raw row counts runs before any other collective so every
                # host takes identical branches from identical facts: the
                # impl plan uses the GLOBAL row count (per-host planning
                # straddling the Pallas threshold would compile mismatched
                # collective programs -> deadlock), the
                # host-0-shard-too-small error raises on ALL hosts (raising
                # on one strands the rest in the init broadcast), and
                # padded-count equality is validated here rather than
                # re-gathered downstream.
                from jax.experimental import multihost_utils

                rows = np.asarray(multihost_utils.process_allgather(
                    np.asarray([host_points.shape[0]], np.int64))).reshape(-1)
                n_for_plan = int(rows.sum())
                if rows[0] < k:
                    raise ValueError(
                        f"multi-host KMeans selects initial centroids from "
                        f"host 0's shard, which holds {int(rows[0])} rows "
                        f"< k={k}; give host 0 at least k rows")

            workset_mode = self.get_workset()
            plan = _fit_plan(n_for_plan, host_points.shape[1], k, measure,
                             mesh, workset=workset_mode,
                             tie_policy=self.get_tie_policy())
            arrange.note(shards=int(mesh.shape["data"]), **plan.notes())
            impl, block_n = plan.impl, plan.block_n
            select_init = _INIT_MODES[self.get_init_mode()]

        def draw_start():
            with tracer.span("fit.arrange.init", "fit") as span:
                def draw():
                    init, native = select_init(host_points, k,
                                               self.get_seed())
                    span.note(native=int(native))
                    return init

                if not multi_host:
                    return draw()
                from ...parallel.distributed import broadcast_from_host0

                multiple = plan.local_multiple(mesh)
                padded_rows = -(-rows // multiple) * multiple
                if not np.all(padded_rows == padded_rows[0]):
                    raise ValueError(
                        "multi-host KMeans requires equal padded row "
                        f"counts per process; got {padded_rows.tolist()}")
                init = (draw() if jax.process_index() == 0
                        else np.zeros((k, host_points.shape[1]), np.float32))
                return np.asarray(broadcast_from_host0(init))

        spec = P("data")
        if not multi_host:
            route = (_put_and_lay_out if int(mesh.shape["data"]) == 1
                     else _put_and_lay_out_sharded)
            points, mask = route(host_points, plan, mesh, spec)
            with tracer.span("fit.arrange", "fit"):
                init = draw_start()
        else:
            with tracer.span("fit.arrange", "fit"):
                init = draw_start()
                with tracer.span("fit.arrange.pad", "fit"):
                    padded, mask = _pad_points(
                        host_points, mesh, row_multiple=plan.row_multiple,
                        fill=plan.fill, cross_host_checked=True)
            with tracer.span("fit.upload", "fit"):
                points = put_sharded(padded, mesh, spec)
                mask = put_sharded(mask, mesh, spec)
            del padded  # the runtime holds it while the transfer needs it
        with tracer.span("fit.upload", "fit"):
            init_dev = replicate(init, mesh)

        if workset_mode:
            result = iterate(
                kmeans_workset_epoch_step(
                    measure, k,
                    block_n=block_n if impl == "pallas_ws" else None),
                init_dev,
                (points, mask),
                max_epochs=self.get_max_iter(),
                workset=plan.init_workset(mask),
                config=IterationConfig(mode="fused"),
            )
            self.last_workset_report = self._workset_report(
                result, n_real=n_for_plan, n_padded=int(points.shape[0]))
        else:
            body = (kmeans_epoch_step_pallas(k, mesh, block_n=block_n,
                                             k_tile=plan.k_tile,
                                             tie_policy=self.get_tie_policy())
                    if impl == "pallas" else kmeans_epoch_step(measure, k))
            result = iterate(
                body,
                init_dev,
                (points, mask),
                max_epochs=self.get_max_iter(),
                config=IterationConfig(mode="fused"),
            )
        with tracer.span("fit.fetch", "fit"):
            centroids = np.asarray(fetch_replicated(result.state))

        model = KMeansModel()
        model.copy_params_from(self)
        model.set_model_data(
            Table({"centroids": centroids[None, :, :]}))  # 1 row of (k, d)
        return model

    def _workset_report(self, result, *, n_real: int, n_padded: int) -> dict:
        """Convergence report of a workset fit: ``active_fraction[e]`` is
        the fraction left active AFTER round ``e`` (over padded rows), so
        the points actually SCORED in round ``e`` are the previous round's
        survivors — round 0 scores every real point (BSP round 0)."""
        trace = result.side.get("epoch_trace", {})
        frac = np.asarray(trace.get("active_fraction", ()), np.float64)
        scored = workset_points_scored(frac, n_real, n_padded)
        return {
            "rounds": result.num_epochs,
            "max_epochs": self.get_max_iter(),
            "n_points": int(n_real),
            "active_fraction": frac,
            "points_scored": scored,
        }

    def fit_outofcore(self, make_reader, *, mesh=None,
                      features_key: str = None) -> "KMeansModel":
        """Out-of-core ``fit`` (see :func:`kmeans_fit_outofcore`): the
        dataset streams from ``make_reader()`` — a fresh per-epoch
        iterator of host batch dicts (e.g. a re-seeked ``DataCacheReader``)
        — instead of living in RAM/HBM."""
        centroids = kmeans_fit_outofcore(
            make_reader, self.get_k(),
            measure_name=self.get_distance_measure(),
            max_iter=self.get_max_iter(), seed=self.get_seed(), mesh=mesh,
            features_key=features_key or self.get_features_col())
        model = KMeansModel()
        model.copy_params_from(self)
        model.set_model_data(Table({"centroids": centroids[None, :, :]}))
        return model

    def save(self, path: str) -> None:
        persist.save_metadata(self, path)

    @classmethod
    def load(cls, path: str) -> "KMeans":
        return persist.load_stage_param(path)


class KMeansModel(KMeansModelParams, Model):
    """Batch prediction: one pairwise-distance matmul + argmin appended as the
    prediction column (the reference buffers rows until ``finish()`` then
    loops — ``KMeansModel.java:109-176``; here it's a single jitted call)."""

    def __init__(self):
        super().__init__()
        self._centroids: np.ndarray | None = None

    # -- model data ---------------------------------------------------------
    def set_model_data(self, *inputs) -> "KMeansModel":
        (table,) = inputs
        self._centroids = np.asarray(table["centroids"][0], dtype=np.float32)
        return self

    def get_model_data(self) -> List[Table]:
        self._require_model()
        return [Table({"centroids": self._centroids[None, :, :]})]

    def _require_model(self):
        if self._centroids is None:
            raise RuntimeError(
                "KMeansModel has no model data; fit a KMeans or call "
                "set_model_data first")

    def transform_kernel(self, schema):
        """Chain TERMINAL: the in-segment assign is expression-identical
        to ``_predict`` (pairwise + per-row argmin — pad rows inert), the
        host ``post`` applies the same int64 cast; bit-exact with the
        stagewise transform."""
        from ...api.chain import StageKernel, numeric_entry

        self._require_model()
        fcol = self.get_features_col()
        if numeric_entry(schema, fcol) is None:
            return None
        measure = DistanceMeasure.get_instance(self.get_distance_measure())
        pred_col = self.get_prediction_col()
        assign_col = f"__chain_assign__{pred_col}"

        def post(host):
            return {pred_col: host[assign_col].astype(np.int64)}

        return StageKernel(
            fn=_kmeans_chain_kernel,
            static=(fcol, assign_col, measure),
            params={"centroids": np.asarray(self._centroids, np.float32)},
            consumes=(fcol,), produces=(assign_col,), post=post)

    # -- inference ----------------------------------------------------------
    def transform(self, *inputs) -> List[Table]:
        (table,) = inputs
        self._require_model()
        # numeric feature columns assign through the kernel registry's
        # shared dispatch surface — the SAME (fn, static) plan the chain
        # terminal and the serving executor run, so offline transform,
        # fused pipelines, and serving share one compiled executable per
        # (schema, bucket); object-dtype vector columns keep the legacy
        # stack_vectors entry point below
        from ...api.chain import apply_kernel_or_none

        kernel = self.transform_kernel(table.schema())
        cols = apply_kernel_or_none(kernel, table)
        if cols is not None:
            return [table.with_column(self.get_prediction_col(),
                                      cols[self.get_prediction_col()])]
        measure = DistanceMeasure.get_instance(self.get_distance_measure())
        points = stack_vectors(table[self.get_features_col()]).astype(
            np.float32)
        # bucketed batch shape: mixed request sizes share one compiled
        # assign program per power-of-two bucket (utils/padding.py); the
        # per-row argmin makes pad rows inert, sliced off below
        (padded,), n = pad_rows_to_bucket((points,))
        assign = np.asarray(
            _predict(measure, padded, jnp.asarray(self._centroids)))[:n]
        return [table.with_column(self.get_prediction_col(),
                                  assign.astype(np.int64))]

    # -- persistence --------------------------------------------------------
    def save(self, path: str) -> None:
        self._require_model()
        persist.save_metadata(self, path)
        persist.save_model_arrays(path, "model", {"centroids": self._centroids})

    @classmethod
    def load(cls, path: str) -> "KMeansModel":
        model = persist.load_stage_param(path)
        data = persist.load_model_arrays(path, "model")
        model._centroids = data["centroids"].astype(np.float32)
        return model


# ---------------------------------------------------------------------------
# kernel-registry entries.  ``kmeans_assign`` (stage convention) is the
# transform/serving/chain dispatch op; ``kmeans_update_stats`` and
# ``kmeans_workset_update`` are the fit-planning ops whose supports
# predicates carry THIS model's planning policy (euclidean metric, the
# Pallas row-count threshold, viable VMEM blocks; the workset kernel
# additionally requires a single-device data axis — its sharded
# composition is future work).
# ---------------------------------------------------------------------------

def _pallas_stats_supported(sig: tuple) -> bool:
    from ...ops import kmeans_pallas as kp

    if len(sig) != 4:       # no/foreign sig: never auto-select pallas
        return False
    n, d, k, measure_name = sig
    return (measure_name == "euclidean" and n >= _PALLAS_MIN_ROWS
            and kp.stats_tiles(d, k) is not None)


def _pallas_workset_supported(sig: tuple) -> bool:
    from ...ops import kmeans_pallas as kp

    if len(sig) != 5:       # no/foreign sig: never auto-select pallas
        return False
    n, d, k, measure_name, data_devs = sig
    return (measure_name == "euclidean" and n >= _PALLAS_MIN_ROWS
            and data_devs == 1
            and kp.pick_block_n_workset(None, d, k) is not None)


def _register_kmeans_kernels() -> None:
    from ...kernels.registry import register_kernel, tpu_only
    from ...ops import kmeans_pallas as kp

    register_kernel("kmeans_assign", "xla", _kmeans_chain_kernel,
                    convention="stage")
    register_kernel("kmeans_update_stats", "pallas", kp.kmeans_update_stats,
                    priority=10, supports=_pallas_stats_supported,
                    available=tpu_only)
    register_kernel("kmeans_update_stats", "xla", _assign_stats)
    register_kernel("kmeans_workset_update", "pallas",
                    kp.kmeans_workset_update, priority=10,
                    supports=_pallas_workset_supported, available=tpu_only)
    register_kernel("kmeans_workset_update", "xla",
                    kmeans_workset_update_xla)


_register_kmeans_kernels()
