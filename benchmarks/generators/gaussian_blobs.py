"""Gaussian blobs for KMeans, from a seed (the recipe of chip_smoke.py's
``kmeans`` leg): ``centers`` blob centres drawn ``center_scale * N(0, I)``,
every point a centre chosen uniformly plus ``N(0, I)``, float32.

Rows are drawn in fixed chunks, each from its own child of
``SeedSequence(seed)``, on a few threads: the points do not depend on how
many threads ran.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNK = 1 << 19
THREADS = min(12, os.cpu_count() or 1)


def generate(params: dict, seed: int) -> dict:
    rows, dim = int(params["rows"]), int(params["dim"])
    n_centers = int(params["centers"])
    scale = float(params["center_scale"])
    starts = list(range(0, rows, CHUNK))
    children = np.random.SeedSequence(int(seed)).spawn(len(starts) + 1)
    centers = (scale * np.random.default_rng(children[-1]).standard_normal(
        (n_centers, dim))).astype(np.float32)
    points = np.empty((rows, dim), np.float32)

    def draw(i: int) -> None:
        rng = np.random.default_rng(children[i])
        out = points[starts[i]:starts[i] + CHUNK]
        rng.standard_normal(out=out, dtype=np.float32)
        out += centers[rng.integers(0, n_centers, size=len(out))]

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(draw, range(len(starts))))
    return {"features": points}
