"""Mean host-clock seconds of one call of the traced window.  A call ends
in a host fetch of the parameters, which is a fence."""


def read(ctx):
    calls = ctx["calls"]
    return sum(end - start for start, end, _ in calls) / len(calls)
