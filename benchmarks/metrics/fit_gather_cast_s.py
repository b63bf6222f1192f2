"""Mean seconds a ``KMeans`` fit of the traced window spends in the
program's span ``fit.gather.cast``, a part of ``fit.gather``:
the cast of the stacked array to the device's float32."""

from harness import program_spans


def read(ctx):
    return program_spans.span_seconds(ctx, "fit.gather.cast")
