"""Grey-level digit-like images for KMeans, from a seed: the shape of
MNIST8m (Loosli, Canu, Bottou: infinite MNIST), not its pixels.

``prototypes`` smooth ``side x side`` images are drawn from the seed: each
a few strokes (line segments and arcs between points of the central box)
and a blob, rendered as the brightest of the Gaussian dots along them,
cut off below ``ink_floor`` and scaled to 0-255.  A row is one prototype,
chosen uniformly, shifted by up to ``max_shift`` pixels either way,
multiplied by a per-row gain in ``[gain_low, 1]``, with uniform noise of
``+-noise`` grey levels on its inked pixels, rounded and clipped to whole
levels 0-255 (exact in bfloat16), float32.  About a fifth of the pixels
are inked, at a mean level near 150, as in the source's digits.

Rows are made in fixed chunks, each from its own child of
``SeedSequence(seed)``, on a few threads: the rows do not depend on how
many threads ran.  A chunk is a gather of its prototypes' shifted images
and a few passes in place over a
thread's own scratch (fresh host memory is dear).
"""

from __future__ import annotations

import os
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNK = 1 << 11
THREADS = min(12, os.cpu_count() or 1)
DOTS = 14                       # Gaussian dots along one stroke


def prototypes(params: dict, rng) -> np.ndarray:
    """``(prototypes, side, side)`` float32 images in [0, 255]."""
    count, side = int(params["prototypes"]), int(params["side"])
    strokes, width = int(params["strokes"]), float(params["stroke_width"])
    margin = int(params["max_shift"]) + 3
    lo, hi = margin, side - 1 - margin
    # a stroke: a quadratic arc from a to b bent towards c
    a, b, c = (rng.uniform(lo, hi, size=(count, strokes + 1, 1, 2))
               for _ in range(3))
    t = np.linspace(0.0, 1.0, DOTS)[None, None, :, None]
    dots = (1 - t) ** 2 * a + 2 * t * (1 - t) * c + t ** 2 * b
    dots[:, -1] = a[:, -1]                  # the last "stroke" is a blob
    sigma = np.full((count, strokes + 1, 1), width)
    sigma[:, -1] = rng.uniform(1.5, 2.5, size=(count, 1)) * width
    grid = np.arange(side, dtype=np.float32)
    images = np.zeros((count, side, side), np.float32)
    for s in range(strokes + 1):
        dy = grid[None, None, :] - dots[:, s, :, 0:1].astype(np.float32)
        dx = grid[None, None, :] - dots[:, s, :, 1:2].astype(np.float32)
        inv = (0.5 / sigma[:, s] ** 2).astype(np.float32)[:, :, None]
        ey = np.exp(-dy * dy * inv)         # (count, DOTS, side)
        ex = np.exp(-dx * dx * inv)
        # the brightest dot at each pixel
        np.maximum(images, np.max(ey[:, :, :, None] * ex[:, :, None, :],
                                  axis=1), out=images)
    floor = float(params["ink_floor"])
    images = np.where(images < floor, 0.0,
                      np.minimum(1.0, float(params["ink_gain"]) * images))
    return (255.0 * images).astype(np.float32)


def generate(params: dict, seed: int) -> dict:
    rows, dim = int(params["rows"]), int(params["dim"])
    side, shift = int(params["side"]), int(params["max_shift"])
    if side * side != dim:
        raise ValueError(f"dim {dim} is not side {side} squared")
    noise = float(params["noise"])
    gain_low = float(params["gain_low"])
    starts = list(range(0, rows, CHUNK))
    children = np.random.SeedSequence(int(seed)).spawn(len(starts) + 1)
    protos = prototypes(params, np.random.default_rng(children[-1]))
    shifts = [(dy, dx) for dy in range(-shift, shift + 1)
              for dx in range(-shift, shift + 1)]
    # every prototype under every shift, a row each (the margin of the
    # strokes' box keeps the ink off the border, so rolling moves it)
    shifted = np.stack([np.roll(protos, s, axis=(1, 2)) for s in shifts],
                       axis=1).reshape(len(protos) * len(shifts), dim)
    points = np.empty((rows, dim), np.float32)

    local = threading.local()

    def draw(i: int) -> None:
        rng = np.random.default_rng(children[i])
        out = points[starts[i]:starts[i] + CHUNK]
        if not hasattr(local, "jitter"):    # a thread's scratch, made once
            local.jitter = np.empty((CHUNK, dim), np.float32)
            local.inked = np.empty((CHUNK, dim), bool)
        jitter, inked = local.jitter[:len(out)], local.inked[:len(out)]
        which = rng.integers(0, len(shifted), size=len(out))
        np.take(shifted, which, axis=0, out=out)
        np.greater(out, 0.0, out=inked)
        out *= rng.uniform(gain_low, 1.0, size=(len(out), 1)).astype(
            np.float32)
        rng.random(out=jitter, dtype=np.float32)
        jitter -= 0.5
        jitter *= 2.0 * noise
        jitter *= inked
        out += jitter
        np.rint(out, out=out)
        np.clip(out, 0.0, 255.0, out=out)

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(draw, range(len(starts))))
    return {"features": points}
