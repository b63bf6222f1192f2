"""Mean seconds an ``ALS`` fit of the traced window spends in the
program's span ``fit.gather.index``, a part of ``fit.gather``: the two
``np.unique(..., return_inverse=True)`` that turn the user and item labels
into positions."""

from harness import program_scopes


def read(ctx):
    return program_scopes.span_seconds(ctx, "fit.gather.index")
