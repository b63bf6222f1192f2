"""1 where every ``KMeans`` fit of the traced window summed its rows in a
stats kernel that adds each row to its winner's row of the resident sums,
0 where the kernel contracted a one-hot of the winners with the rows: the
counter ``sums`` (``"by_row"`` or ``"onehot"``) that the program notes on
its span ``fit.arrange``.  ``None`` for a fit on the XLA body, and for a
program that notes no such counter."""

from harness import program_scopes

_FORMS = {"by_row": 1.0, "onehot": 0.0}


def read(ctx):
    return program_scopes.mean(ctx, lambda r: _FORMS.get(
        r["notes"].get("fit.arrange", {}).get("sums")))
