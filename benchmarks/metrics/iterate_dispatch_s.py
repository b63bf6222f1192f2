"""Mean seconds a fit of the traced window spends in the program's span
``iterate.dispatch``: getting the fused program running (probe, trace,
lower, the compile-cache request, the enqueue).  It ends when the jitted
call returns, not when the device has finished."""

from harness import program_spans


def read(ctx):
    return program_spans.span_seconds(ctx, "iterate.dispatch")
