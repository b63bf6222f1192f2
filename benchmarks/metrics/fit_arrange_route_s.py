"""Mean seconds a ``WideDeep`` fit of the traced window spends in the
program's span ``fit.arrange.route``, a part of ``fit.arrange``:
``ops/emb_grad.emb_grad_route`` on the host, one stable sort of a step's
slot ids for every step of the epoch, on every refit."""

from harness import program_scopes


def read(ctx):
    return program_scopes.span_seconds(ctx, "fit.arrange.route")
