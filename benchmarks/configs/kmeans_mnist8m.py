"""Operations and bytes of one Lloyd iteration of KMeans at a k in the
thousands, from shapes alone (``rows``, ``k`` and ``dim`` of the
configuration): what NO implementation of the stated algorithm avoids."""


def step_counts(config: dict) -> dict:
    """One Lloyd iteration over ``n`` points: the distance of every point
    to every one of ``k`` centroids (2 n k d FLOP) and one addition a
    value for the per-cluster sums (n d), the points read once (4 n d
    bytes).  Compute-bound at k 4096, d 784 (66 ms of arithmetic at the
    bf16 peak against 7.8 ms of bytes at 2,025,000 rows).  A one-hot
    contraction for the sums is an implementation's choice (a second
    2 n k d) and is NOT counted: a step that forms its sums so tops out
    near half of this count's peak."""
    n, k, d = int(config["rows"]), int(config["k"]), int(config["dim"])
    return {"flops": 2.0 * n * k * d + 1.0 * n * d, "bytes": 4.0 * n * d}


def kernel_counts(config: dict) -> dict:
    """``kmeans_update_stats`` is the whole iteration: points and centroids
    in, ``(k, d)`` sums and ``k`` counts out; the same arithmetic."""
    n, k, d = int(config["rows"]), int(config["k"]), int(config["dim"])
    return {"flops": 2.0 * n * k * d + 1.0 * n * d,
            "bytes": 4.0 * (n * d + 2 * k * d + k)}

