"""Process start to the window's start: imports, data from the seed, the
native libraries' build on a checkout's first run, the warm-up call and,
on a cold cache, compilation (host clock)."""


def read(ctx):
    return ctx["setup_s"]
