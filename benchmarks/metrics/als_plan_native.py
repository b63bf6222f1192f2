"""1 where every ``ALS`` fit of the traced window placed its ratings in
the grouped plan's slots by the native counting pass
(``native/als_plan.cpp``), 0 where the machine gave no library and the
plan fell back to NumPy (``fit_arrange_plan_s`` is then several times
longer): the counter ``placed_native`` that the program notes on its span
``fit.arrange.plan``.  ``None`` for a program that notes no such
counter."""

from harness import program_scopes


def read(ctx):
    return program_scopes.note(ctx, "fit.arrange.plan", "placed_native")
