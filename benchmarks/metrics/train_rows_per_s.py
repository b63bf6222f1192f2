"""Rows x passes completed by ALL calls of the window, over ALL the time
from the window's start to the last call's return (host clock)."""


def read(ctx):
    calls = ctx["calls"]
    work = sum(ctx["rows"] * passes for _, _, passes in calls)
    return work / (calls[-1][1] - ctx["window_start"])
