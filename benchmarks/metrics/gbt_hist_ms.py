"""Device self time a step (one tree) of the operations under the program's
scope ``gbt.hist``: every level's histograms of (grad, hess) over
(feature, bin), the Pallas call and the XLA that combines its partial
sums."""

from harness import program_scopes


def read(ctx):
    return program_scopes.scope_ms(ctx, "gbt.hist")
