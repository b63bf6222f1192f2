"""Table rows one step touches, the mean over the steps of an epoch: the
counter ``unique_mean`` that the program notes on its span
``fit.arrange.route`` (of ``global_batch_size * n_cat`` slots a step)."""

from harness import program_scopes


def read(ctx):
    return program_scopes.note(ctx, "fit.arrange.route", "unique_mean")
