"""Mean seconds a fit of the traced window spends in the program's span
``iterate.dispatch.compile``, the fourth stage of ``iterate.dispatch``:
the module's cache key, then the persistent compile cache's read and
deserialisation of the executable, or XLA's compilation where the cache
has none (``iterate_cache_hit`` says which).  ``None`` for a program
whose dispatch is one span."""

from harness import program_scopes


def read(ctx):
    return program_scopes.span_seconds(ctx, "iterate.dispatch.compile")
