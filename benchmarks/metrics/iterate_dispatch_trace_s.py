"""Mean seconds a fit of the traced window spends in the program's span
``iterate.dispatch.trace``, the second stage of ``iterate.dispatch``: the
fused loop to a jaxpr (``jit(run).trace``), the body's second Python
trace.  ``None`` for a program whose dispatch is one span."""

from harness import program_scopes


def read(ctx):
    return program_scopes.span_seconds(ctx, "iterate.dispatch.trace")
