"""1 where every ``ALS`` fit of the traced window indexed both its label
columns by the native hashing pass (``native/als_plan.cpp: als_index``),
0 where either column fell back to NumPy's sort and binary search (no
library, or a column that is short or not of integers;
``fit_gather_index_s`` is then several times longer): the counter
``native`` that the program notes on its span ``fit.gather.index``.
``None`` for a program that notes no such counter."""

from harness import program_scopes


def read(ctx):
    return program_scopes.note(ctx, "fit.gather.index", "native")
