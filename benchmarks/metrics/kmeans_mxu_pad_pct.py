"""The share of the MXU passes' operand area that is padding at the tiles
the fit planned, in percent: the counter ``mxu_padded_share`` that
``KMeans.fit`` notes on its span ``fit.arrange`` (the contractions run
over the padding as over data)."""

from harness import program_scopes


def read(ctx):
    share = program_scopes.note(ctx, "fit.arrange", "mxu_padded_share")
    return None if share is None else 100.0 * share
