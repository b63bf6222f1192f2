"""What the program's spans still cannot see: the root span ``fit`` less
the union of its phase spans; the mean over the fits of the traced
window."""

from harness import program_spans


def read(ctx):
    return program_spans.mean(ctx, lambda r: r["root_s"] - r["phases_s"])
