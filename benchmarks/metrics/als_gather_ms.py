"""Device self time a step (one ALS iteration) of the operations under the
program's scope ``als.gather``: one row of the other side's factors read
for every slot of both sides' grouped layouts."""

from harness import program_scopes


def read(ctx):
    return program_scopes.scope_ms(ctx, "als.gather")
