"""Device self time a step of the operations under the program's scope
``als.normal_eq``: ``A_g = Y_g^T diag(w) Y_g`` and ``b_g`` as batched
contractions over each group's own slots, and the regularisation."""

from harness import program_scopes


def read(ctx):
    return program_scopes.scope_ms(ctx, "als.normal_eq")
