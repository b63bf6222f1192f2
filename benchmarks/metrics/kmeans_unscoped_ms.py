"""Device self time a step of the fused program's operations that neither
``kmeans.stats`` nor ``kmeans.update`` claims (the loop, the copies of its
carry); with ``kmeans_stats_ms`` and ``kmeans_update_ms`` it adds up to
``step_ms``.  ``None`` where no operation carries a ``kmeans.*`` scope
(another estimator's fit, the parent commit's)."""

from harness import program_scopes


def read(ctx):
    if program_scopes.scope_ms(ctx, "kmeans.stats") is None:
        return None
    return program_scopes.unscoped_ms(ctx)
