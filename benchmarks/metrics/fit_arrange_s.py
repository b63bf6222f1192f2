"""Mean seconds a fit of the traced window spends in the program's span
``fit.arrange``: planning and laying the rows out on the host (the initial
state and the padding of KMeans; the casts, the epoch permutation and the
ELL layout of the linear fit)."""

from harness import program_spans


def read(ctx):
    return program_spans.span_seconds(ctx, "fit.arrange")
