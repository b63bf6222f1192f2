"""The cell's Pallas calls against their roofline: the least time of one
step's kernels, from their operand and result shapes
(``configs/<config>.py: kernel_counts``), times the steps of the call,
over the summed device time of the call's kernel events.

The rule that finds the kernel events (read off the first traces, PR 26):
an event of the device's ``XLA Ops`` line inside ``fit.call`` is a Pallas
call if its opcode is ``custom-call`` (the trace names an operation by its
HLO line, ``trace_reduce.short_name`` keeps result and opcode, e.g.
``ell_margin_fused.3 custom-call``; every ``tpu_custom_call`` of these
programs is a Pallas kernel; the unnamed ``custom-call.N`` beside them
are 1 ns markers and add nothing to the sum).  No ``pallas_call`` of the program passes
``name=``; the result's name follows the jitted function around the call,
which a refactor can change, so the rule does not lean on it."""

from harness import files
from metrics.step_mfu_pct import least_seconds


def is_kernel(name: str, stats: dict) -> bool:
    return name.endswith(" custom-call") or "custom-call" in str(
        stats.get("hlo_category", "")).lower()


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["calls"] or ctx["peaks"] is None:
        return None
    counts = files.module("configs", ctx["config"]["counts"]).kernel_counts(
        ctx["config"])
    steps_per_pass = int(ctx["config"]["steps_per_pass"])
    shares = []
    for call, (_, _, passes) in zip(trace["calls"], ctx["calls"]):
        spent = sum(ns for name, ns, stats in call["ops"]
                    if is_kernel(name, stats)) / 1e9
        if spent <= 0.0:
            return None
        least = least_seconds(counts, ctx["peaks"]) * passes * steps_per_pass
        shares.append(100.0 * least / spent)
    return sum(shares) / len(shares)
