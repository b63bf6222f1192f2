"""The devices on the mesh's ``data`` axis that a fit divided its rows
over: the counter ``shards`` that ``KMeans.fit`` notes on its span
``fit.arrange`` (4 in the cell that crosses chips, 1 on one chip).
``None`` where the span carries no such note (another estimator's fit,
the parent commit's)."""

from harness import program_scopes


def read(ctx):
    return program_scopes.note(ctx, "fit.arrange", "shards")
