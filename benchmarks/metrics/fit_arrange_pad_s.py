"""Mean seconds a ``KMeans`` fit of the traced window spends in the
program's span ``fit.arrange.pad``, a part of ``fit.arrange``:
``pad_rows_with_mask`` makes a padded copy of the points, and the row mask."""

from harness import program_spans


def read(ctx):
    return program_spans.span_seconds(ctx, "fit.arrange.pad")
