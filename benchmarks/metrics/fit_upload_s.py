"""Mean seconds the host transfer costs a fit of the traced window: the
program's span ``fit.upload`` (the puts return: they are asynchronous, so
this is the hand-over) plus the wait for the transfer, which shows in the
fit's first fence: the seconds of ``fit.fetch`` in which the device ran
nothing.  ``None`` without a chip in the trace."""

from harness import program_spans


def seconds(record):
    wait = program_spans.transfer_wait_s(record)
    put = record["total_s"].get("fit.upload")
    return None if wait is None or put is None else put + wait


def read(ctx):
    return program_spans.mean(ctx, seconds)
