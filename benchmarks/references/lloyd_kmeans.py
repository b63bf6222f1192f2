"""Plain reference for KMeans: Lloyd's algorithm as Flink ML states it.

Start: ``k`` points taken by ``numpy.random.default_rng(seed)
.permutation(rows)[:k]`` (``KMeans.selectRandomCentroids``: shuffle with
the seed, take k).  Each iteration: every point goes to its nearest
centroid by squared euclidean distance, the first index on a tie; a
centroid becomes the mean of its points, and one that got none stays.

It is plain ``jax.numpy``, run over blocks of rows on whatever device JAX
gives it, in the precision the configuration states
(``reference_params.operand_dtype``): the operands of both contractions
(scores and one-hot sums) rounded to that type, everything else float32,
every contraction at ``precision="highest"`` (a product of two such
operands is exact in float32, so this is the stated MXU pass with float32
accumulation; ``float32`` rounds nothing).  It imports nothing of the
program and takes nothing it made; the caller runs it once the window has
closed and the program's arrays are freed.

What is compared (the centroids the last timed fit returned):

- ``centroid_gap_worst``: the WORST centroid's ``|c - c_ref|`` over the RMS
  norm of the reference's centroids: every centroid is held.
- ``centroid_gap_median``: the median centroid's, by the same measure.

The configuration's ``limits`` say which of them are held, and where.

``control`` is the same algorithm with the operands rounded to the next
precision down (``control_dtype``), put in the program's place.
"""

from __future__ import annotations

import functools

import numpy as np

BLOCK = 1 << 18


def initial_centroids(points: np.ndarray, k: int, seed: int) -> np.ndarray:
    idx = np.random.default_rng(seed).permutation(len(points))[:k]
    return points[idx]


@functools.lru_cache(maxsize=None)
def _program(operand_dtype: str):
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

    def block_stats(centroids, block, valid):
        """Sums and counts of one block of rows; ``valid`` masks the zero
        rows that pad the last block."""
        c2 = jnp.sum(centroids * centroids, axis=1)
        scores = c2[None, :] - 2.0 * jnp.dot(
            operand(block), operand(centroids).T, precision=hi)
        nearest = jnp.argmin(scores, axis=1)
        onehot = (nearest[:, None] == jnp.arange(centroids.shape[0])[None]
                  ).astype(jnp.float32) * valid[:, None]
        sums = jnp.dot(onehot.T, operand(block), precision=hi)
        return sums, jnp.sum(onehot, axis=0)

    def one_pass(centroids, blocks, valid):
        def body(carry, xs):
            packed, ok = xs
            # rows arrive packed to 128 lanes (see _blocks)
            s, c = block_stats(
                centroids, packed.reshape(-1, centroids.shape[1]), ok)
            return (carry[0] + s, carry[1] + c), None

        k, d = centroids.shape
        init = (jnp.zeros((k, d), jnp.float32), jnp.zeros((k,), jnp.float32))
        (sums, counts), _ = jax.lax.scan(body, init, (blocks, valid))
        return sums, counts

    @functools.partial(jax.jit, static_argnames="iterations")
    def lloyd(centroids, blocks, valid, iterations):
        def step(c, _):
            sums, counts = one_pass(c, blocks, valid)
            new = jnp.where(counts[:, None] > 0,
                            sums / jnp.maximum(counts, 1.0)[:, None], c)
            return new, None

        out, _ = jax.lax.scan(step, centroids, None, length=iterations)
        return out

    return lloyd


def _blocks(points: np.ndarray):
    """The points on the device in one upload as ``(blocks, BLOCK * d / 128,
    128)``, zero rows padding the last block, with the mask of real rows.
    Rows are packed to whole 128-lane tiles (a narrow float32 array is
    padded in the chip's tiled layout) and unpacked block by block."""
    import jax
    import jax.numpy as jnp

    rows, d = points.shape
    block = min(BLOCK, rows)
    n_blocks = -(-rows // block)
    if n_blocks * block > rows:
        points = np.concatenate(
            [points, np.zeros((n_blocks * block - rows, d), np.float32)])
    valid = (np.arange(n_blocks * block) < rows).astype(np.float32)
    lanes = 128 if (block * d) % 128 == 0 else d
    return (jax.device_put(points.reshape(n_blocks, -1, lanes)),
            jnp.asarray(valid.reshape(n_blocks, block)))


def _fit(points, ref: dict, seed, operand_dtype, keep=None):
    """Lloyd from the seed's start; ``keep`` (0/1 per row) leaves rows
    out."""
    import jax.numpy as jnp

    blocks, valid = _blocks(points)
    if keep is not None:
        valid = valid * jnp.asarray(np.resize(keep, valid.shape), jnp.float32)
    start = jnp.asarray(initial_centroids(points, int(ref["k"]), seed),
                        jnp.float32)
    out = _program(operand_dtype)(start, blocks, valid,
                                  int(ref["iterations"]))
    return np.asarray(out, np.float64)


def _stated(ref: dict) -> str:
    return ref.get("operand_dtype", "float32")


def compare(config: dict, data: dict, answer: dict, seed: int) -> dict:
    ref = config["reference_params"]
    want = _fit(data["features"], ref, seed, _stated(ref))
    got = np.asarray(answer["centroids"], np.float64).reshape(
        int(ref["k"]), -1)
    if got.shape != want.shape or not np.isfinite(got).all():
        return {"centroid_gap_worst": float("inf"),
                "centroid_gap_median": float("inf")}
    rms = float(np.sqrt(np.mean(np.sum(want * want, axis=1))))
    gaps = np.sqrt(np.sum((got - want) ** 2, axis=1)) / rms
    return {"centroid_gap_worst": float(np.max(gaps)),
            "centroid_gap_median": float(np.median(gaps))}


def control(config: dict, data: dict, seed: int, dtype=None) -> dict:
    ref = config["reference_params"]
    return {"centroids": _fit(data["features"], ref, seed,
                              dtype or ref["control_dtype"])}


FAULTS = ("unchanged", "half_batch", "altered")


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
