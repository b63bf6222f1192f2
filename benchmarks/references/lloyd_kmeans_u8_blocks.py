"""Plain reference for KMeans over a table no chip holds as float32:
Lloyd's algorithm as Flink ML states it, the rows kept as the whole grey
levels they are.

The algorithm and its arithmetic are ``lloyd_kmeans_blocks.py``'s: the
start is ``numpy.random.default_rng(seed).permutation(rows)[:k]``; each
iteration every point goes to its nearest centroid by squared euclidean
distance, the first index on a tie; a centroid becomes the mean of its
points, and one that got none stays.  Plain ``jax.numpy``, the operands of
both contractions (scores and one-hot sums) rounded to
``reference_params.operand_dtype``, everything else float32, every
contraction at ``precision="highest"``, a block of
``reference_params.block`` rows (2^15 unless given) at a time.

One thing of that arithmetic is written out here: a row's nearest centroid
is the first index of its lowest score by ``min`` and a comparison, not by
``jnp.argmin``, which on the TPU, compiled into one program with the
contraction that makes the scores, picks another centroid for 0.6-0.8% of
these rows (``first_lowest`` below has the reading).  With it a sound fit
of twenty passes ends ON this reference in the median centroid (0.0 at
2,025,000 rows on one chip, where ``lloyd_kmeans_blocks.py`` reads 0.03),
and the control stands clear of it.

What else differs is what it holds.  8,100,000 x 784 float32 values are
25.4 GB; as ``uint8`` they are 6.35 GB, and exact, because every value is
a whole level in 0-255 (checked: another table is refused).  The blocks
are divided evenly, in table order, over the devices JAX has (four on the
cell's host, one in a rehearsal), each device keeps its blocks as
``uint8`` and widens one block at a time, and an iteration is one jitted
program a device (the same program, run where its blocks are) whose
partial sums and counts are brought to the first device and added there by
plain ``jax.numpy`` additions, where the centroids are updated and sent
back.  No kernel, no ``shard_map``, no collective; it imports nothing of
the program and takes nothing it made.  The sums are of whole levels and
stay under 2^24, so the order in which blocks and devices are added does
not show.

What is compared (the centroids the last timed fit returned), as
``lloyd_kmeans_blocks.py`` has it:

- ``centroid_gap_worst``: the WORST centroid's ``|c - c_ref|`` over the RMS
  norm of the reference's centroids.
- ``centroid_gap_median``: the median centroid's, by the same measure.
- ``objective_gap``: ``|J - J_ref| / J_ref`` of the within-cluster sum of
  squares ``J = sum_i min_j |x_i - c_j|^2`` of the rows under the returned
  centroids and under the reference's, both in float32 at ``highest``
  with nothing rounded; infinity if the answer has another shape or is
  not finite.

The configuration's ``limits`` say which of them are held, and where.
``control`` is the same algorithm with the operands rounded to the next
precision down (``control_dtype``), put in the program's place.  The
stated reference's centroids and the table's ``uint8`` copy are kept (on
the host) for the last data it was asked about, so that a control or a
fault compared on the same seed makes neither again; the blocks are
uploaded anew by every call and freed when it returns.
"""

from __future__ import annotations

import functools
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from references import lloyd_kmeans
from references.lloyd_kmeans import _stated, initial_centroids

BLOCK = 1 << 15
PIECE = 1 << 10                 # rows narrowed to uint8 at a time
THREADS = min(24, os.cpu_count() or 1)
NUMBERS = ("centroid_gap_worst", "centroid_gap_median", "objective_gap")
#: ``no_exchange``: the chips' sums and counts never meet, so the first
#: chip returns Lloyd over its own run of rows
FAULTS = lloyd_kmeans.FAULTS + ("no_exchange",)


def levels(points: np.ndarray, block: int) -> np.ndarray:
    """The rows as ``(blocks, block, d)`` ``uint8``, zero rows padding the
    last block; ``ValueError`` unless every value is a whole level 0-255."""
    rows, d = points.shape
    block = min(block, rows)
    n_blocks = -(-rows // block)
    out = np.zeros((n_blocks * block, d), np.uint8)

    def narrow(first: int) -> bool:
        # a piece that stays in a core's cache: narrowed, then widened
        # back and compared
        chunk = points[first:first + PIECE]
        mine = out[first:first + len(chunk)]
        mine[...] = chunk
        return np.array_equal(mine, chunk)

    with ThreadPoolExecutor(THREADS) as pool:
        if not all(pool.map(narrow, range(0, rows, PIECE))):
            raise ValueError("the rows are not whole levels in 0-255: this "
                             "reference keeps them as uint8")
    return out.reshape(n_blocks, block, d)


@functools.lru_cache(maxsize=None)
def _programs(operand_dtype: str):
    """``(partial_stats, update, objective)``, jitted; each runs on the
    device that holds its arguments."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    if operand_dtype == "float32":
        operand = lambda x: x                                # noqa: E731
    else:
        # a product of two such values is exact in float32, so rounding
        # the operands and contracting in float32 is the lower-precision
        # MXU pass with float32 accumulation
        operand = lambda x: x.astype(operand_dtype).astype(    # noqa: E731
            jnp.float32)

    def first_lowest(scores):
        """Every row's lowest score's first index.  Not ``jnp.argmin``:
        compiled into one TPU program with the contraction that makes its
        argument it picks another index for 0.6-0.8% of these rows (425
        to 531 of 65,536 at k 4096, the scores themselves equal to
        float64's to the last digit; handed the same scores as an argument
        it picks none wrongly: my chip run, PR 39, one v5e)."""
        lowest = jnp.min(scores, axis=1, keepdims=True)
        index = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        return jnp.min(jnp.where(scores == lowest, index, scores.shape[1]),
                       axis=1)

    @jax.jit
    def partial_stats(centroids, blocks, valid):
        """Sums and counts of this device's blocks."""
        k, d = centroids.shape
        c2 = jnp.sum(centroids * centroids, axis=1)
        rounded = operand(centroids)

        def body(carry, xs):
            levels_u8, ok = xs
            block = operand(levels_u8.astype(jnp.float32))
            scores = c2[None, :] - 2.0 * jnp.dot(block, rounded.T,
                                                 precision=hi)
            nearest = first_lowest(scores)
            onehot = (nearest[:, None] == jnp.arange(k)[None]
                      ).astype(jnp.float32) * ok[:, None]
            return (carry[0] + jnp.dot(onehot.T, block, precision=hi),
                    carry[1] + jnp.sum(onehot, axis=0)), None

        init = (jnp.zeros((k, d), jnp.float32), jnp.zeros((k,), jnp.float32))
        return jax.lax.scan(body, init, (blocks, valid))[0]

    @jax.jit
    def update(centroids, sums, counts):
        """The devices' partial sums added, then the means; a centroid
        that got no point stays."""
        total, count = sum(sums[1:], sums[0]), sum(counts[1:], counts[0])
        return jnp.where(count[:, None] > 0,
                         total / jnp.maximum(count, 1.0)[:, None], centroids)

    @jax.jit
    def objective(centroids, blocks, valid):
        """This device's part of the within-cluster sum of squares, a
        block's part at a time (float32 at ``highest``, nothing rounded)."""
        c2 = jnp.sum(centroids * centroids, axis=1)

        def body(_, xs):
            levels_u8, ok = xs
            block = levels_u8.astype(jnp.float32)
            scores = c2[None, :] - 2.0 * jnp.dot(block, centroids.T,
                                                 precision=hi)
            within = jnp.sum(block * block, axis=1) + jnp.min(scores, axis=1)
            return None, jnp.sum(within * ok)

        return jax.lax.scan(body, None, (blocks, valid))[1]

    return partial_stats, update, objective


def _held(host_blocks: np.ndarray, rows: int, keep=None) -> list:
    """``(device, blocks, valid)`` for every device that gets blocks: the
    blocks divided evenly in table order, a device's put waited for before
    the next one's; ``valid`` masks the zero rows of the last block and
    the rows ``keep`` (0/1 per row, repeated to the table's length) leaves
    out."""
    import jax

    n_blocks, block, _ = host_blocks.shape
    valid = (np.arange(n_blocks * block) < rows).astype(np.float32)
    if keep is not None:
        valid = valid * np.resize(keep, valid.shape).astype(np.float32)
    valid = valid.reshape(n_blocks, block)
    held = []
    devices = jax.local_devices()
    for device, mine in zip(devices, np.array_split(np.arange(n_blocks),
                                                    len(devices))):
        if len(mine):
            lo, hi = mine[0], mine[-1] + 1
            blocks = jax.device_put(host_blocks[lo:hi], device)
            blocks.block_until_ready()
            held.append((device, blocks, jax.device_put(valid[lo:hi],
                                                        device)))
    return held


def _fit(points, host_blocks, ref: dict, seed, operand_dtype, keep=None):
    """Lloyd from the seed's start; ``keep`` leaves rows out."""
    import jax

    partial_stats, update, _ = _programs(operand_dtype)
    held = _held(host_blocks, len(points), keep)
    first = held[0][0]
    centroids = jax.device_put(
        initial_centroids(points, int(ref["k"]), seed).astype(np.float32),
        first)
    for _ in range(int(ref["iterations"])):
        parts = [partial_stats(jax.device_put(centroids, device), blocks,
                               valid) for device, blocks, valid in held]
        sums, counts = zip(*(jax.device_put(part, first) for part in parts))
        centroids = update(centroids, sums, counts)
    return np.asarray(centroids, np.float64)


def _objective(host_blocks, rows: int, centroids) -> float:
    import jax

    _, _, objective = _programs("float32")
    c = np.asarray(centroids, np.float32)
    parts = [objective(jax.device_put(c, device), blocks, valid)
             for device, blocks, valid in _held(host_blocks, rows)]
    return float(sum(np.sum(np.asarray(part, np.float64)) for part in parts))


#: for the last data asked about: its ``uint8`` blocks (``"levels"``) and
#: the stated reference's centroids and objective (``"want"``), on the
#: host; nothing is kept on the device between calls
_KEPT: dict = {}


def _levels_of(points: np.ndarray, ref: dict) -> np.ndarray:
    key = (id(points), points.shape, int(ref.get("block", BLOCK)))
    if _KEPT.get("levels_key") != key:
        _KEPT.clear()
        _KEPT.update(levels_key=key, levels=levels(points, key[2]))
    return _KEPT["levels"]


def compare(config: dict, data: dict, answer: dict, seed: int) -> dict:
    ref = config["reference_params"]
    points = data["features"]
    host_blocks = _levels_of(points, ref)
    key = (int(seed), repr(sorted(ref.items())))
    if _KEPT.get("want_key") != key:
        want = _fit(points, host_blocks, ref, seed, _stated(ref))
        _KEPT.update(want_key=key, want=(
            want, _objective(host_blocks, len(points), want)))
    want, j_want = _KEPT["want"]
    got = np.asarray(answer["centroids"], np.float64).reshape(
        int(ref["k"]), -1)
    if got.shape != want.shape or not np.isfinite(got).all():
        return dict.fromkeys(NUMBERS, float("inf"))
    rms = float(np.sqrt(np.mean(np.sum(want * want, axis=1))))
    gaps = np.sqrt(np.sum((got - want) ** 2, axis=1)) / rms
    j_got = _objective(host_blocks, len(points), got)
    return {"centroid_gap_worst": float(np.max(gaps)),
            "centroid_gap_median": float(np.median(gaps)),
            "objective_gap": abs(j_got - j_want) / j_want}


def control(config: dict, data: dict, seed: int, dtype=None) -> dict:
    ref = config["reference_params"]
    points = data["features"]
    return {"centroids": _fit(points, _levels_of(points, ref), ref, seed,
                              dtype or ref["control_dtype"])}


def fault(config: dict, data: dict, seed: int, kind: str) -> dict:
    """The reference with one fault planted, as an answer: the start
    returned unchanged; every second row left out, the means taken over
    the rest; one centroid, the last, scaled by 1.1; the exchange between
    the configuration's ``chips`` left out, so that only the first chip's
    run of rows (the first ``ceil(rows / chips)``) is ever seen."""
    ref = config["reference_params"]
    points = data["features"]
    if kind == "unchanged":
        return {"centroids": initial_centroids(points, int(ref["k"]), seed)}
    if kind not in FAULTS:
        raise ValueError(kind)
    keep = None
    if kind == "half_batch":
        keep = np.array([1.0, 0.0])
    elif kind == "no_exchange":
        keep = np.arange(len(points)) < -(-len(points) // int(config["chips"]))
    got = _fit(points, _levels_of(points, ref), ref, seed, _stated(ref), keep)
    if kind == "altered":
        got[-1] *= 1.1
    return {"centroids": got}
