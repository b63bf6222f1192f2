"""XLA compilations inside the window, process-wide: the program's
``utils/backend.count_compiles`` (every request that misses JAX's
in-memory cache of compiled functions) less the requests the persistent
compile cache served (``harness/compiles.py``).  It should read 0."""


def read(ctx):
    return ctx["compile_requests"] - ctx["compile_cache_hits"]
