"""Device self time a step (one tree) of the fused program's operations
that none of ``gbt.grad``, ``gbt.hist``, ``gbt.split`` and ``gbt.route``
claims (the loop, the copies of its carry); with the four it adds up to
``step_ms``.  ``None`` where no operation carries a ``gbt.*`` scope
(another estimator's fit, the parent commit's)."""

from harness import program_scopes


def read(ctx):
    if program_scopes.scope_ms(ctx, "gbt.hist") is None:
        return None
    return program_scopes.unscoped_ms(ctx)
