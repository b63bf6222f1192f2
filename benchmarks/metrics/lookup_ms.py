"""Device self time a step of the operations under the program's scope
``widedeep.lookup``: one embedding row and one wide weight gathered for
every slot of the batch."""

from harness import program_scopes


def read(ctx):
    return program_scopes.scope_ms(ctx, "widedeep.lookup")
