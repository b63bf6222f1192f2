"""The share of the traced window's fits whose fused program the
persistent compile cache served (1.0: every fit's; 0.0: XLA compiled
each): the counter ``cache_hit`` that the program notes on its span
``iterate.dispatch.compile`` from JAX's event
``/jax/compilation_cache/cache_hits`` around its one ``.compile()``,
mean over the fits.  ``compiles_in_window`` counts from outside,
process-wide; this is a fit's own request.  ``None`` for a program that
notes no such counter."""

from harness import program_scopes


def read(ctx):
    return program_scopes.note(ctx, "iterate.dispatch.compile", "cache_hit")
