"""The level histograms against their roofline: the least time of one
tree's histograms, from counts that do not depend on the implementation
(``configs/<counts>.py: hist_counts``: every row's bins, gradient,
hessian and node id read once a level; HBM-bound), over
``gbt_hist_ms``."""

from harness import program_scopes


def read(ctx):
    return program_scopes.share_pct(ctx, "gbt.hist", "hist_counts")
