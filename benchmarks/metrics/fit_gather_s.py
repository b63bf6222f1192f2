"""Mean seconds a fit of the traced window spends in the program's span
``fit.gather``: pulling the table's columns into whole host arrays (for
KMeans the stack and the cast to the device's dtype; for the linear fit
the columns and the range check of the hashed indices)."""

from harness import program_spans


def read(ctx):
    return program_spans.span_seconds(ctx, "fit.gather")
