"""Mean seconds a fit of the traced window spends in the program's span
``iterate.dispatch.probe``, the first stage of ``iterate.dispatch``:
``jax.eval_shape`` of the loop's body, one abstract Python trace of it
that only learns whether the body votes and what it emits.  The jitted
program traces the body a second time (``iterate_dispatch_trace_s``).
``None`` for a program whose dispatch is one span."""

from harness import program_scopes


def read(ctx):
    return program_scopes.span_seconds(ctx, "iterate.dispatch.probe")
