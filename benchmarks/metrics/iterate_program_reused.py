"""The share of the traced window's fits whose fused program the process
itself had kept (1.0: every fit's dispatch went from its program key
straight to the enqueue; 0.0: each ran the probe, the trace, the lowering
and the compile-cache request): the counter ``reused`` that the program
notes on its span ``iterate.dispatch.compile``, mean over the fits.
``iterate_cache_hit`` says XLA compiled nothing for a fit's program;
this says which of the two that can be true by, the process's own entry
(1) or the persistent cache (0).  ``None`` for a program that notes no
such counter."""

from harness import program_scopes


def read(ctx):
    return program_scopes.note(ctx, "iterate.dispatch.compile", "reused")
