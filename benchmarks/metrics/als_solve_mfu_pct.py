"""The solves' share of the chip's peak: the least time of one Cholesky
factorisation and two triangular solves a group (``configs/<counts>.py:
solve_counts``) over ``als_solve_ms``."""

from harness import program_scopes


def read(ctx):
    return program_scopes.share_pct(ctx, "als.solve", "solve_counts")
