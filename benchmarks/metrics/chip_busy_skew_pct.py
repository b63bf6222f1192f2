"""How far the mean chip lags the fullest: 100 x (1 - the mean over the
chips of their busy time in the traced window / the fullest chip's), from
the reduced trace ``run.py`` hands every reader (``busy_mean_ns``,
``busy_ns``).  On a host whose chips share every step it is the share of
the fullest chip's busy time that the others, in the mean, spent waiting
for it or for the host.  ``None`` in a cell of one chip, where there is
nobody to lag."""


def read(ctx):
    trace = ctx["trace"]
    if (int(ctx["workload"]["chips"]) < 2 or not trace
            or not trace.get("busy_ns")):
        return None
    return 100.0 * (1.0 - trace["busy_mean_ns"] / trace["busy_ns"])
