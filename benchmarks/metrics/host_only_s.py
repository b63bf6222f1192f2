"""Per call: its length less the union of device-operation intervals that
fall inside the benchmark's ``fit.call`` annotation around it; the mean
over the traced window.  Both from the trace, so on one clock."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["calls"]:
        return None
    alone = [(c["end_ns"] - c["start_ns"] - c["busy_ns"]) / 1e9
             for c in trace["calls"]]
    return sum(alone) / len(alone)
