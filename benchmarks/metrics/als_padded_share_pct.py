"""Pad slots over all slots of both sides' grouped layouts, in percent:
the counter ``padded_share`` that the program notes on its span
``fit.arrange.plan`` (the gathers and the contractions run over every
slot)."""

from harness import program_scopes


def read(ctx):
    share = program_scopes.note(ctx, "fit.arrange.plan", "padded_share")
    return None if share is None else 100.0 * share
