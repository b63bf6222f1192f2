"""Device self time a step (one tree) of the operations under the program's
scope ``gbt.route``: the rows to their children at every level, each
row's leaf value, the margin update."""

from harness import program_scopes


def read(ctx):
    return program_scopes.scope_ms(ctx, "gbt.route")
