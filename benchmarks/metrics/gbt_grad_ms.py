"""Device self time a step (one tree) of the operations under the program's
scope ``gbt.grad``: the logistic loss's gradient and hessian of every
row from its margin and label."""

from harness import program_scopes


def read(ctx):
    return program_scopes.scope_ms(ctx, "gbt.grad")
