"""Mean seconds a fit of the traced window spends in the program's span
``iterate.dispatch.enqueue``, the last stage of ``iterate.dispatch``: the
call of the compiled executable (argument handling, donation, launch).
It ends when the call returns; nothing is fetched.  ``None`` for a
program whose dispatch is one span."""

from harness import program_scopes


def read(ctx):
    return program_scopes.span_seconds(ctx, "iterate.dispatch.enqueue")
