"""1 - (union of device-operation intervals) / traced window, on the
fullest device."""


def read(ctx):
    trace = ctx["trace"]
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_ns"] / trace["window_ns"])
