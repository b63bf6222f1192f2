"""Mean seconds a ``GBTClassifier`` / ``GBTRegressor`` fit of the traced
window spends in the program's span ``fit.arrange.bin``, a part of
``fit.arrange``: the bin edges from a sample of the rows and the bin id
of every value of the table, on the host, on every refit."""

from harness import program_scopes


def read(ctx):
    return program_scopes.span_seconds(ctx, "fit.arrange.bin")
