"""The towers' share of the chip's peak: the least time of their 6 FLOP a
weight a row (``configs/<counts>.py: towers_counts``) over ``towers_ms``."""

from harness import program_scopes


def read(ctx):
    return program_scopes.share_pct(ctx, "widedeep.towers", "towers_counts")
