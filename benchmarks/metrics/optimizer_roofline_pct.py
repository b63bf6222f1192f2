"""Adam against its roofline: the least time of dense Adam's six streams
over both tables (``configs/<counts>.py: optimizer_counts``; HBM-bound)
over ``optimizer_ms``."""

from harness import program_scopes


def read(ctx):
    return program_scopes.share_pct(ctx, "widedeep.optimizer",
                                    "optimizer_counts")
