"""Device self time a step of the operations under the program's scope
``als.solve``: the Cholesky factorisation of every group's normal
equations and both triangular solves."""

from harness import program_scopes


def read(ctx):
    return program_scopes.scope_ms(ctx, "als.solve")
