"""Mean seconds a ``KMeans`` fit of the traced window spends in the
program's span ``fit.gather.stack``, a part of ``fit.gather``:
``stack_vectors`` makes one whole float64 host array of the column."""

from harness import program_spans


def read(ctx):
    return program_spans.span_seconds(ctx, "fit.gather.stack")
