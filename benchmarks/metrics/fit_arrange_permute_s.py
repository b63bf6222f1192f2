"""Mean seconds a linear fit of the traced window spends in the program's
span ``fit.arrange.permute``, a part of ``fit.arrange``: where the fit
lays its rows out in the epoch order.  On one device that is the dispatch
of the device's gather of the flat columns (``sgd.py: _epoch_tensors``);
on a mesh of several devices, and in a program without that gather, the
host's ``prepare_epoch_tensor`` of each column."""

from harness import program_scopes


def read(ctx):
    return program_scopes.span_seconds(ctx, "fit.arrange.permute")
