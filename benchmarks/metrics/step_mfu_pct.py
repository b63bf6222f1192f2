"""The whole step's share of the chip's peak: the least time the chip
could take for the step's arithmetic and bytes, from counts that do not
depend on the implementation (``configs/<config>.py: step_counts``), over
``step_ms``."""

from harness import files
from metrics import step_ms


def least_seconds(counts: dict, peaks: dict) -> float:
    return max(counts["flops"] / peaks["flops_per_s"],
               counts["bytes"] / peaks["hbm_bytes_per_s"])


def read(ctx):
    s = step_ms.step_seconds(ctx)
    if s is None or ctx["peaks"] is None:
        return None
    counts = files.module("configs", ctx["config"]["counts"]).step_counts(
        ctx["config"])
    return 100.0 * least_seconds(counts, ctx["peaks"]) / s
