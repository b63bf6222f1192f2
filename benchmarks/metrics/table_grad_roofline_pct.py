"""The table gradient against its roofline: the least time to read every
slot's gradient row and write every touched table row once
(``configs/<counts>.py: table_grad_counts``; HBM-bound) over
``table_grad_ms``."""

from harness import program_scopes


def read(ctx):
    return program_scopes.share_pct(ctx, "widedeep.table_grad",
                                    "table_grad_counts")
