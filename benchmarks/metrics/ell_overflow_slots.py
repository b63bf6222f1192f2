"""The largest number of a step's categorical slots that the ELL grid
cannot hold and the layout sends to the overflow list, a fit of the
traced window on the mean: the counter ``need_ovf`` that the program
notes on its span ``fit.arrange.ell_layout``.  ``None`` for a fit that
built no layout, and for a program that notes no such counter."""

from harness import program_scopes


def read(ctx):
    return program_scopes.note(ctx, "fit.arrange.ell_layout", "need_ovf")
