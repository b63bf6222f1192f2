"""Mean seconds a ``WideDeep`` fit of the traced window spends in the
program's span ``fit.arrange.layout``, a part of ``fit.arrange``: the epoch
order (``plan_epoch_layout``, a permutation of the rows) and the four
``prepare_epoch_tensor`` that lay rows, ids, labels and weights out by it."""

from harness import program_scopes


def read(ctx):
    return program_scopes.span_seconds(ctx, "fit.arrange.layout")
