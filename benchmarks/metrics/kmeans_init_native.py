"""1 where every ``KMeans`` fit of the traced window drew its random start
by the native pass (``native/kmeans_start.cpp``: NumPy's
``permutation(n)[:k]`` without the permutation), 0 where NumPy drew it
(no library, or a pass that declined; ``fit_arrange_init_s`` is then about
three times longer at 20 M rows): the counter ``native`` that the program
notes on its span ``fit.arrange.init``.  ``None`` for a program that notes
no such counter."""

from harness import program_scopes


def read(ctx):
    return program_scopes.note(ctx, "fit.arrange.init", "native")
