"""The row gathers against their roofline: the least time to read one row
of ``rank`` floats for every rating, once a side (``configs/<counts>.py:
gather_counts``; HBM-bound), over ``als_gather_ms``."""

from harness import program_scopes


def read(ctx):
    return program_scopes.share_pct(ctx, "als.gather", "gather_counts")
