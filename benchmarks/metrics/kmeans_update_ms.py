"""Device self time a step of the operations under the program's scope
``kmeans.update``: the fill rows' correction of the counts, the division,
the empty clusters that keep their centroid."""

from harness import program_scopes


def read(ctx):
    return program_scopes.scope_ms(ctx, "kmeans.update")
