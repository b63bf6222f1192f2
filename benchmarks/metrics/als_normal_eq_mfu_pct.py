"""The normal equations' share of the chip's peak: the least time of the
symmetric half of every group's Gram matrix and its right-hand side
(``configs/<counts>.py: normal_eq_counts``) over ``als_normal_eq_ms``."""

from harness import program_scopes


def read(ctx):
    return program_scopes.share_pct(ctx, "als.normal_eq", "normal_eq_counts")
