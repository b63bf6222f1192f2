"""Device self time a step of the operations under the program's scope
``linear.apply``: a linear step's weight update (on the ELL plan the
Pallas call ``ell_scatter_apply``, the overflow scatter, the heavy
indices' update; on the XLA plan the scatter-add of the slots) and the
dense block's update."""

from harness import program_scopes


def read(ctx):
    return program_scopes.scope_ms(ctx, "linear.apply")
