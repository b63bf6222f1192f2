"""The row lookups against their roofline: the least time to read one
embedding row and one wide weight a slot (``configs/<counts>.py:
lookup_counts``; HBM-bound) over ``lookup_ms``."""

from harness import program_scopes


def read(ctx):
    return program_scopes.share_pct(ctx, "widedeep.lookup", "lookup_counts")
