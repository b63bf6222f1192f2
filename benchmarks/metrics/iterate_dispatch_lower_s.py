"""Mean seconds a fit of the traced window spends in the program's span
``iterate.dispatch.lower``, the third stage of ``iterate.dispatch``: the
jaxpr to a StableHLO module, every Pallas call through Mosaic.  ``None``
for a program whose dispatch is one span."""

from harness import program_scopes


def read(ctx):
    return program_scopes.span_seconds(ctx, "iterate.dispatch.lower")
