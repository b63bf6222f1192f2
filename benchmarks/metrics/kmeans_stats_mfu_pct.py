"""The stats' share of the chip's peak, the XLA around the kernel
included: the least time of one Lloyd iteration from counts that no
implementation avoids (``configs/<counts>.py: step_counts``: every point
scored against every centroid, the points read once) over
``kmeans_stats_ms``.  Beside it ``kernel_roofline_pct`` reads the custom
calls alone."""

from harness import program_scopes


def read(ctx):
    return program_scopes.share_pct(ctx, "kmeans.stats", "step_counts")
