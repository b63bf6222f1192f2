"""Mean seconds an ``ALS`` fit of the traced window spends in the
program's span ``fit.arrange.plan``, a part of ``fit.arrange``: each
side's stable order by group and the grouped layout of its ratings, on the
host, on every refit."""

from harness import program_scopes


def read(ctx):
    return program_scopes.span_seconds(ctx, "fit.arrange.plan")
