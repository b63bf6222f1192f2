"""The largest number of a step's heavy indices (weights more than the
layout's threshold of slots in one batch, updated by the heavy path's
matvec and not through the ELL grid), a fit of the traced window on the
mean: the counter ``need_heavy`` that the program notes on its span
``fit.arrange.ell_layout``.  ``None`` for a fit that built no layout, and
for a program that notes no such counter."""

from harness import program_scopes


def read(ctx):
    return program_scopes.note(ctx, "fit.arrange.ell_layout", "need_heavy")
