"""Plain reference for KMeans at a k in the thousands: Lloyd's algorithm
as Flink ML states it, the block of rows a parameter.

The algorithm and its arithmetic are ``lloyd_kmeans.py``'s (its jitted
program is this file's too; only the blocks it is handed differ): the
start is ``numpy.random.default_rng(seed).permutation(rows)[:k]``;
each iteration every point goes to its nearest centroid by squared
euclidean distance, the first index on a tie; a centroid becomes the mean
of its points, and one that got none stays.  Plain ``jax.numpy``, the
operands of both contractions (scores and one-hot sums) rounded to
``reference_params.operand_dtype``, everything else float32, every
contraction at ``precision="highest"``.  What differs is the size of what
it holds: a block of ``reference_params.block`` rows (2^15 unless given)
makes a score tile and a one-hot of ``block x k`` floats, 0.54 GB each at
k 4096, where the other file's fixed 2^18 rows would take 4.3 GB each
beside 6.35 GB of points.  It imports nothing of the program and takes
nothing it made.

What is compared (the centroids the last timed fit returned):

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
stated reference's centroids are kept (on the host) for the last data it
was asked about, so that a control or a fault compared on the same seed
does not run it again; the blocks are uploaded anew by every call and
freed when it returns.
"""

from __future__ import annotations

import functools

import numpy as np

from references.lloyd_kmeans import (FAULTS, _program, _stated,
                                     initial_centroids)

BLOCK = 1 << 15


@functools.lru_cache(maxsize=None)
def _objective_program():
    """The within-cluster sum of squares of given centroids, a block's
    part at a time (float32 at ``highest``, nothing rounded)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def objective(centroids, blocks, valid):
        d = centroids.shape[1]
        c2 = jnp.sum(centroids * centroids, axis=1)

        def body(_, xs):
            packed, ok = xs
            block = packed.reshape(-1, d)
            scores = c2[None, :] - 2.0 * jnp.dot(
                block, centroids.T, precision=jax.lax.Precision.HIGHEST)
            within = jnp.sum(block * block, axis=1) + jnp.min(scores, axis=1)
            return None, jnp.sum(within * ok)

        return jax.lax.scan(body, None, (blocks, valid))[1]

    return objective


def _blocks(points: np.ndarray, block: int):
    """The points on the device in one upload as ``(blocks, block * d / 128,
    128)``, zero rows padding the last block, with the mask of real rows.
    Rows are packed to whole 128-lane tiles (a row of 784 floats is padded
    to 896 in the chip's tiled layout) and unpacked block by block."""
    import jax
    import jax.numpy as jnp

    rows, d = points.shape
    block = min(block, rows)
    n_blocks = -(-rows // block)
    valid = (np.arange(n_blocks * block) < rows).astype(np.float32)
    lanes = 128 if (block * d) % 128 == 0 else d
    whole = rows // block
    packed = jax.device_put(points[:whole * block].reshape(whole, -1, lanes))
    if n_blocks > whole:
        # only the last block is copied on the host to get its zero rows
        last = np.zeros((1, block, d), np.float32)
        last[0, :rows - whole * block] = points[whole * block:]
        packed = jnp.concatenate(
            [packed, jax.device_put(last.reshape(1, -1, lanes))])
    return packed, jnp.asarray(valid.reshape(n_blocks, block))


#: the stated reference's centroids and objective for the last data asked
#: about (host arrays; nothing is kept on the device between calls)
_WANT: dict = {}


def _fit(points, ref: dict, seed, operand_dtype, keep=None, held=None):
    """Lloyd from the seed's start; ``keep`` (0/1 per row) leaves rows
    out; ``held`` is ``_blocks``' pair where the caller has it already."""
    import jax.numpy as jnp

    blocks, valid = held or _blocks(points, int(ref.get("block", BLOCK)))
    if keep is not None:
        valid = valid * jnp.asarray(np.resize(keep, valid.shape), jnp.float32)
    start = jnp.asarray(initial_centroids(points, int(ref["k"]), seed),
                        jnp.float32)
    out = _program(operand_dtype)(start, blocks, valid,
                                  int(ref["iterations"]))
    return np.asarray(out, np.float64)


def _objective(held, centroids) -> float:
    import jax.numpy as jnp

    parts = _objective_program()(jnp.asarray(centroids, jnp.float32), *held)
    return float(np.sum(np.asarray(parts, np.float64)))


NUMBERS = ("centroid_gap_worst", "centroid_gap_median", "objective_gap")


def compare(config: dict, data: dict, answer: dict, seed: int) -> dict:
    ref = config["reference_params"]
    points = data["features"]
    held = _blocks(points, int(ref.get("block", BLOCK)))
    key = (id(points), points.shape, int(seed), repr(sorted(ref.items())))
    if key not in _WANT:
        want = _fit(points, ref, seed, _stated(ref), held=held)
        _WANT.clear()
        _WANT[key] = (want, _objective(held, want))
    want, j_want = _WANT[key]
    got = np.asarray(answer["centroids"], np.float64).reshape(
        int(ref["k"]), -1)
    if got.shape != want.shape or not np.isfinite(got).all():
        return dict.fromkeys(NUMBERS, float("inf"))
    rms = float(np.sqrt(np.mean(np.sum(want * want, axis=1))))
    gaps = np.sqrt(np.sum((got - want) ** 2, axis=1)) / rms
    j_got = _objective(held, got)
    return {"centroid_gap_worst": float(np.max(gaps)),
            "centroid_gap_median": float(np.median(gaps)),
            "objective_gap": abs(j_got - j_want) / j_want}


def control(config: dict, data: dict, seed: int, dtype=None) -> dict:
    ref = config["reference_params"]
    return {"centroids": _fit(data["features"], ref, seed,
                              dtype or ref["control_dtype"])}


def fault(config: dict, data: dict, seed: int, kind: str) -> dict:
    """The reference with one fault planted, as an answer: the start
    returned unchanged; every second row left out, the means taken over
    the rest; one centroid, the last, scaled by 1.1."""
    ref = config["reference_params"]
    points = data["features"]
    if kind == "unchanged":
        return {"centroids": initial_centroids(points, int(ref["k"]), seed)}
    keep = np.array([1.0, 0.0]) if kind == "half_batch" else None
    got = _fit(points, ref, seed, _stated(ref), keep)
    if kind == "altered":
        got[-1] *= 1.1
    elif kind != "half_batch":
        raise ValueError(kind)
    return {"centroids": got}
