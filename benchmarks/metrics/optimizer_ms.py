"""Device self time a step of the operations under the program's scope
``widedeep.optimizer``: Adam over every parameter, the tables included."""

from harness import program_scopes


def read(ctx):
    return program_scopes.scope_ms(ctx, "widedeep.optimizer")
