"""Device self time a step of the operations under the program's scope
``widedeep.towers``: forward and backward of the wide sum and the deep
tower, the loss between them."""

from harness import program_scopes


def read(ctx):
    return program_scopes.scope_ms(ctx, "widedeep.towers")
