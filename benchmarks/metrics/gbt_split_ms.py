"""Device self time a step (one tree) of the operations under the program's
scope ``gbt.split``: the best split of every node from its histograms,
the Newton values of the nodes that stop."""

from harness import program_scopes


def read(ctx):
    return program_scopes.scope_ms(ctx, "gbt.split")
