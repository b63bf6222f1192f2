"""Device self time a step of the operations under the program's scope
``linear.margin``: a linear step's margins, the dense matvec and the
categorical part (on the ELL plan the Pallas call ``ell_margin``, the
overflow gather-add and the heavy indices' matvec; on the XLA plan the
gather of the slots' weights)."""

from harness import program_scopes


def read(ctx):
    return program_scopes.scope_ms(ctx, "linear.margin")
