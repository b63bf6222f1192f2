"""Operations and bytes of one Lloyd iteration of KMeans at a k in the
thousands on a host whose ``chips`` chips SHARE every iteration (the rows
divided over them, the centroids on each): the least work of the fullest
chip, from shapes alone (``rows``, ``k``, ``dim`` and ``chips`` of the
configuration).

Why the division by the chips: the readers (``step_mfu_pct``,
``kmeans_stats_mfu_pct``, ``kernel_roofline_pct``) divide these counts by
ONE chip's peaks, and the time they are held against (``step_ms``, a
scope's or a kernel's) is the fullest chip's.  The whole iteration's
counts over that time would be a share of one chip's peak of work that
four chips did: up to 400%, an impossible reading.  A chip cannot do less
than its even share of the iteration, so the whole iteration's counts
over the chips are what no implementation on this deployment avoids, and
they are ``kmeans_mnist8m.py``'s counts at its quarter of the rows.

The all-reduce's bytes (the ``(k, d)`` sums and ``k`` counts of every
chip, 12.85 MB, over the chips' interconnect) are the implementation's:
an algorithm that sent nothing but finished centroids would still be
Lloyd's.  They are not counted, and ``harness/peaks.json`` has no
interconnect peak to hold them against; ``kmeans_reduce_ms`` gives their
time."""


from configs import kmeans_mnist8m as one_chip


def _a_chips_share(config: dict) -> dict:
    return {**config, "rows": int(config["rows"]) // int(config["chips"])}


def step_counts(config: dict) -> dict:
    """A chip's even share of one Lloyd iteration, as
    ``kmeans_mnist8m.py: step_counts`` counts it: the distance of each of
    its ``n / chips`` points to every one of ``k`` centroids and one
    addition a value for the sums, its points read once.  1.30e13 FLOP at
    8,100,000 rows on four chips: 66.0 ms at the bf16 peak against 7.8 ms
    of bytes.  The one-hot contraction for the sums is NOT counted."""
    return one_chip.step_counts(_a_chips_share(config))


def kernel_counts(config: dict) -> dict:
    """``kmeans_update_stats`` on a chip is that chip's whole share of the
    iteration: its points and the centroids in, ``(k, d)`` sums and ``k``
    counts out; the same arithmetic."""
    return one_chip.kernel_counts(_a_chips_share(config))
