"""What ``program_spans`` cannot see of a traced ``fit()``: the program's
``jax.named_scope`` s on the device's operations, the counters a span
carries as notes, and parts of a phase that ``program_spans.PARTS`` does
not name.

``fits_of_cell(cell)`` reads the trace ``run.py`` left under
``.bench_trace/<cell>/`` once per process (``xplane_wire``: the scope of
a device operation is in its metadata's ``tf_op``, which
``jax.profiler.ProfileData`` does not give) and returns one record per
root span ``fit`` inside one of the benchmark's ``fit.call`` marks:

    {"start_ns", "end_ns",
     "span_s": {name: seconds of the spans of that name inside the root},
     "notes":  {name: the stats of the first span of that name},
     "scope_ns": {scope: self nanoseconds of the fused program's
                  operations whose ``tf_op`` holds ``/<scope>/``},
     "program_ns": self nanoseconds of all its operations}

The fused program is the XLA module with most device time inside the
root (``step_ms``'s rule).  ``scope_ns`` is empty where the trace has no
chip or no operation carries a scope; a trace without program spans (the
parent commit's) gives no record, and every reader ``None``.

    python benchmarks/harness/program_scopes.py <trace dir>

prints every fit of the trace under ``<trace dir>/plugins/profile/``.
"""

from __future__ import annotations

import functools
import glob
import os
import re
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from harness import program_spans as ps
from harness import trace_reduce as tr
from harness import xplane_wire

SCOPE = re.compile(r"/([A-Za-z_][\w]*\.[\w.]+)/")
SPAN_PREFIXES = ("fit", "iterate")


def _keep(plane: str, line: str, event: str) -> bool:
    if tr.DEVICE_PLANE.match(plane):
        return line in (tr.OPS_LINE, tr.MODULES_LINE)
    return event.startswith(SPAN_PREFIXES)


def scope_of(stats: dict):
    """The innermost dotted scope in an operation's ``tf_op``
    (``.../widedeep.towers/jvp()/dot_general:`` -> ``widedeep.towers``)."""
    found = SCOPE.findall(str(stats.get("tf_op", "")))
    return found[-1] if found else None


def fits(trace: dict) -> list:
    marks = [(lo, hi) for _, lo, hi in tr.annotations(trace, {ps.CALL_MARK})]
    planes = tr.device_planes(trace)
    plane = max(planes, key=lambda p: tr.total(tr.busy(p)), default=None)
    ops = tr.self_times(tr.line_events(plane, tr.OPS_LINE)) if plane else []
    modules = tr.line_events(plane, tr.MODULES_LINE) if plane else []
    out = []
    for host in trace["planes"]:
        if tr.DEVICE_PLANE.match(host["name"]):
            continue
        for line in host["lines"]:
            for name, lo, dur, _ in line["events"]:
                hi = lo + dur
                if name == ps.ROOT_SPAN and any(
                        m_lo <= lo and hi <= m_hi for m_lo, m_hi in marks):
                    inside = [e for e in line["events"]
                              if lo <= e[1] and e[1] + e[2] <= hi]
                    out.append(_record(lo, hi, inside, ops, modules))
    return sorted(out, key=lambda r: r["start_ns"])


def _record(lo, hi, inside, ops, modules) -> dict:
    span_s, notes = {}, {}
    for name, _, dur, stats in inside:
        span_s[name] = span_s.get(name, 0.0) + dur / 1e9
        notes.setdefault(name, stats)
    scope_ns, program_ns = {}, 0.0
    mine = [m for m in modules if lo <= m[1] < hi]
    if mine:
        _, m_lo, m_dur, _ = max(mine, key=lambda m: m[2])
        for _, start, self_ns, stats in ops:
            if m_lo <= start < m_lo + m_dur:
                program_ns += self_ns
                scope = scope_of(stats)
                if scope:
                    scope_ns[scope] = scope_ns.get(scope, 0.0) + self_ns
    return {"start_ns": lo, "end_ns": hi, "span_s": span_s, "notes": notes,
            "scope_ns": scope_ns, "program_ns": program_ns}


@functools.lru_cache(maxsize=None)
def fits_of_cell(cell: str) -> tuple:
    return _fits_under(os.path.join(ps.ROOT, ".bench_trace", cell))


def _fits_under(trace_dir: str) -> tuple:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        return ()
    return tuple(fits(xplane_wire.read(found[0], _keep)))


def mean(ctx, of_fit):
    """The mean over the window's fits of ``of_fit(record)``; ``None`` if
    there is no fit or a fit gives ``None``."""
    values = [of_fit(r) for r in fits_of_cell(ctx["cell"])]
    if not values or any(v is None for v in values):
        return None
    return sum(values) / len(values)


def span_seconds(ctx, name: str):
    return mean(ctx, lambda r: r["span_s"].get(name))


def note(ctx, span: str, key: str):
    """The mean over the fits of the number the span ``span`` carries
    under ``key``."""
    return mean(ctx, lambda r: r["notes"].get(span, {}).get(key))


def steps_of_a_fit(ctx) -> float:
    """Steps of one fit of the window: the passes its model reports
    times the configuration's steps per pass (every fit of a window runs
    the same number)."""
    return ctx["calls"][0][2] * int(ctx["config"]["steps_per_pass"])


def scope_ms(ctx, scope: str):
    """Device self time a step of the operations under ``scope``."""
    steps = steps_of_a_fit(ctx)
    return mean(ctx, lambda r: None if scope not in r["scope_ns"]
                else r["scope_ns"][scope] / 1e6 / steps)


def unscoped_ms(ctx):
    """Device self time a step of the fused program's operations that no
    scope claims; ``None`` where none carries a scope."""
    steps = steps_of_a_fit(ctx)
    return mean(ctx, lambda r: None if not r["scope_ns"] else
                (r["program_ns"] - sum(r["scope_ns"].values())) / 1e6 / steps)


def share_pct(ctx, scope: str, counts_fn: str):
    """The least time of a step's operations under ``scope``, from the
    counts ``configs/<counts>.py: <counts_fn>`` gives for the shapes,
    over their measured time: a share of the roofline or of the peak."""
    from harness import files
    from metrics.step_mfu_pct import least_seconds

    ms = scope_ms(ctx, scope)
    if ms is None or ctx["peaks"] is None:
        return None
    counts = getattr(files.module("configs", ctx["config"]["counts"]),
                     counts_fn)(ctx["config"])
    return 100.0 * least_seconds(counts, ctx["peaks"]) / (ms / 1e3)


if __name__ == "__main__":
    for r in _fits_under(sys.argv[1]):
        print(f"fit: {(r['end_ns'] - r['start_ns']) / 1e9:.6f} s, the fused "
              f"program {r['program_ns'] / 1e9:.6f} s of device self time")
        for name, seconds in r["span_s"].items():
            print(f"  {name:26s} {seconds:10.6f}  {r['notes'][name]}")
        for scope, ns in sorted(r["scope_ns"].items(), key=lambda kv: -kv[1]):
            print(f"  {scope:26s} {ns / 1e9:10.6f} s on the device")
