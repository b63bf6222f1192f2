"""The peak bytes of the fullest chip after the window, as the device
line has them (live arrays and what the running program reserved), in
10^9 bytes."""


def read(ctx):
    if ctx["peaks"] is None:
        return None
    return ctx["memory_peak_bytes"] / 1e9
