"""Mean seconds a linear fit of the traced window spends in the program's
span ``fit.arrange.ell_layout``, a part of ``fit.arrange``: the host's
build of the ELL routing of every step's categorical slots
(``ops/ell_scatter.py: ell_layout``, the native builder).  0 for a linear
fit that laid its rows out (``fit.arrange.permute``) and built no layout:
the XLA plan, as off the chip; ``None`` for any other fit."""

from harness import program_scopes


def seconds(record):
    spans = record["span_s"]
    if "fit.arrange.ell_layout" in spans:
        return spans["fit.arrange.ell_layout"]
    return 0.0 if "fit.arrange.permute" in spans else None


def read(ctx):
    return program_scopes.mean(ctx, seconds)
