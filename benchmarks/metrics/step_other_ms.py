"""Device self time a step of the fused program's operations that none of
the program's scopes claims (the loops themselves, the slices of the epoch
tensors, the loss log).  ``None`` where no operation carries a scope."""

from harness import program_scopes


def read(ctx):
    return program_scopes.unscoped_ms(ctx)
