"""Mean seconds a ``WideDeep`` fit of the traced window spends in the
program's span ``fit.arrange.params``, a part of ``fit.arrange``: the
towers drawn on the host, the dispatch of the tables' draw on the device,
the optimizer's state made beside them."""

from harness import program_scopes


def read(ctx):
    return program_scopes.span_seconds(ctx, "fit.arrange.params")
