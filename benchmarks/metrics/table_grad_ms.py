"""Device self time a step of the operations under the program's scope
``widedeep.table_grad``: the routed gradient of both tables (permutation
gather, fold, placement into a table-shaped array)."""

from harness import program_scopes


def read(ctx):
    return program_scopes.scope_ms(ctx, "widedeep.table_grad")
