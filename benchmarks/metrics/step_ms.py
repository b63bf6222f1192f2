"""Device time of the call's fused program over passes x steps per pass.

The fused program is the XLA module with the most device time inside
``fit.call``; the passes are those the call's model reports, the steps per
pass the configuration's ``steps_per_pass``."""


def step_seconds(ctx):
    trace = ctx["trace"]
    if not trace or not trace["calls"]:
        return None
    steps_per_pass = int(ctx["config"]["steps_per_pass"])
    per_call = []
    for call, (_, _, passes) in zip(trace["calls"], ctx["calls"]):
        if not call["module_ns"]:
            return None
        fused = max(call["module_ns"].values())
        per_call.append(fused / 1e9 / (passes * steps_per_pass))
    return sum(per_call) / len(per_call)


def read(ctx):
    s = step_seconds(ctx)
    return None if s is None else 1e3 * s
