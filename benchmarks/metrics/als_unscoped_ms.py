"""Device self time a step of the fused program's operations that none of
the program's ``als.*`` scopes claims (the loops, the slices of the
plan's arrays, the write of a block's rows); with ``als_gather_ms``,
``als_normal_eq_ms`` and ``als_solve_ms`` it adds up to ``step_ms``.
``None`` where no operation carries a scope."""

from harness import program_scopes


def read(ctx):
    return program_scopes.unscoped_ms(ctx)
