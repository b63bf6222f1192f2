"""The ELL margin kernel against its roofline: the least time of one
call of the Pallas kernel ``ell_margin`` from the bytes of its operands
and its result, over the device time of that kernel's events a step.

Bytes (``margin_bytes``), HBM-bound: the weights and the layout's ``src``,
``pos`` and ``mask`` grids in (4 bytes a slot each, one slot a weight),
the extended margins out: the batch and a nonempty pad to whole 256-lane
rows, in whole (8, 128) tiles.

The kernel's events are those of the call's ``XLA Ops`` line named
``ell_margin[.N] custom-call``: the program names the Pallas call.  A
program that names it otherwise, or runs the XLA twin, gives ``None``."""

import re

from metrics.kernel_roofline_pct import is_kernel
from metrics.step_mfu_pct import least_seconds


def extended_len(batch: int) -> int:
    """The batch and a nonempty zero pad to whole 256-lane rows."""
    return batch + (256 - batch % 256 or 256)


def margin_bytes(config: dict) -> float:
    f = int(config["num_features"])
    m_rows = -(-extended_len(int(config["global_batch_size"])) // 128)
    m_rows += (-m_rows) % 8
    return 4.0 * (4 * f + m_rows * 128)


def kernel_share(ctx, kernel: str, kernel_bytes) -> "float | None":
    """The mean over the window's calls of the least time of ``kernel``'s
    steps over its events' device time, in percent."""
    trace = ctx["trace"]
    if not trace or not trace["calls"] or ctx["peaks"] is None:
        return None
    named = re.compile(re.escape(kernel) + r"(\.\d+)? custom-call")
    spent = [sum(ns for name, ns, stats in call["ops"]
                 if named.fullmatch(name) and is_kernel(name, stats)) / 1e9
             for call in trace["calls"]]
    # another estimator's fit runs no such kernel, and its configuration
    # has none of the shapes the bytes are counted from
    if min(spent) <= 0.0:
        return None
    least = least_seconds({"flops": 0.0, "bytes": kernel_bytes(ctx["config"])},
                          ctx["peaks"])
    steps_per_pass = int(ctx["config"]["steps_per_pass"])
    shares = [100.0 * least * passes * steps_per_pass / s
              for s, (_, _, passes) in zip(spent, ctx["calls"])]
    return sum(shares) / len(shares)


def read(ctx):
    return kernel_share(ctx, "ell_margin", margin_bytes)
