"""Mean seconds a fit of the traced window is blocked on the device's
work: the device-busy seconds inside the program's span ``fit.fetch``,
the fence every fit ends in.  The rest of that span is the wait for the
transfer and counts as ``fit_upload_s``.  ``None`` without a chip in the
trace."""

from harness import program_spans


def read(ctx):
    return program_spans.mean(ctx, lambda r: r["busy_s"].get("fit.fetch"))
