"""Device self time a step (one Lloyd iteration) of the operations under
the program's scope ``kmeans.stats``: everything that turns points and
centroids into sums and counts, the Pallas call and the XLA around it
(``c2``, the centroids' rounding and padding, the cut of the result)."""

from harness import program_scopes


def read(ctx):
    return program_scopes.scope_ms(ctx, "kmeans.stats")
