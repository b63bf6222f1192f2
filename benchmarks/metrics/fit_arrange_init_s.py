"""Mean seconds a ``KMeans`` fit of the traced window spends in the
program's span ``fit.arrange.init``, a part of ``fit.arrange``:
choosing the initial centroids (a permutation of the rows)."""

from harness import program_spans


def read(ctx):
    return program_spans.span_seconds(ctx, "fit.arrange.init")
