"""Operations and bytes of one Lloyd iteration of KMeans, from shapes
alone (``rows``, ``k`` and ``dim`` of the configuration)."""


def step_counts(config: dict) -> dict:
    """One Lloyd iteration over ``n`` points: the distances to ``k``
    centroids (2 n k d FLOP) and the per-cluster sums as a one-hot
    contraction (2 n k d), the points read once.  HBM-bound at k 10, d 20
    (1.95 ms of bytes against 0.08 ms of arithmetic at 20 M rows)."""
    n, k, d = int(config["rows"]), int(config["k"]), int(config["dim"])
    return {"flops": 4.0 * n * k * d, "bytes": 4.0 * n * d}


def kernel_counts(config: dict) -> dict:
    """``kmeans_update_stats`` is the whole iteration: points and centroids
    in, ``(k, d)`` sums and ``k`` counts out."""
    n, k, d = int(config["rows"]), int(config["k"]), int(config["dim"])
    return {"flops": 4.0 * n * k * d,
            "bytes": 4.0 * (n * d + 2 * k * d + k)}
