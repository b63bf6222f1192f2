"""Device self time a step of the fused program's operations that neither
``linear.margin`` nor ``linear.apply`` claims (the loss and its residuals,
the extended residuals, the intercept, the loop and its carry); with
``lr_margin_ms`` and ``lr_apply_ms`` it adds up to ``step_ms``.  ``None``
where no operation carries a ``linear.*`` scope (another estimator's fit,
a program without these scopes)."""

from harness import program_scopes


def read(ctx):
    if program_scopes.scope_ms(ctx, "linear.margin") is None:
        return None
    return program_scopes.unscoped_ms(ctx)
