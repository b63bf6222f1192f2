"""From a profiler trace to busy time, idle gaps and operation times.

``load_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
nothing but JAX, into plain data; ``load_json`` reads the same plain data
from a file (the recorded trace the tests use).  Everything else works on
the plain data:

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, duration_ns, {stats}],
                                       ...]}]}]}

Rules (read off a trace of each cell on the chip, PR 26; PERF.md section 3):

- a device plane is one whose name starts with ``/device:``; its line
  ``XLA Ops`` holds one event per executed HLO operation and its line
  ``XLA Modules`` one event per executed program;
- an operation that contains others on its line (a ``while`` around its
  body) counts by its SELF time in a breakdown, and once in a union;
- the benchmark's own annotations (``fit.call``, ``between_fits``) are
  events of those names on any line of a plane that is not a device.

    python benchmarks/harness/trace_reduce.py <trace.xplane.pb | trace.json>

prints the planes, lines and heaviest names of a trace: look before you
write a rule against it.
"""

from __future__ import annotations

import json
import re
import sys
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:[A-Za-z]+:\d+$")
SPANS = ("fit.call", "between_fits")
KEPT_STATS = ("hlo_category", "hlo_module", "program_id", "run_id")


def load_xplane(path: str, host_names=None, with_stats: bool = False) -> dict:
    """The trace as plain data.  ``host_names`` keeps, on planes that are
    not a chip's, only the events of those names (a traced window holds a
    million host events the reducer never reads); ``with_stats`` keeps an
    event's ``KEPT_STATS`` for a look by hand."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        on_chip = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            events = []
            for e in line.events:
                name = e.name
                if not on_chip and host_names and name not in host_names:
                    continue
                stats = ({k: v for k, v in e.stats if k in KEPT_STATS}
                         if with_stats else {})
                events.append([short_name(name), float(e.start_ns),
                               float(e.duration_ns), stats])
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def short_name(name: str) -> str:
    """The trace names a device operation by its whole HLO line; keep the
    result's name (``%ell_margin_fused.3``) and the opcode."""
    if " = " not in name:
        return name
    result, rest = name.split(" = ", 1)
    opcode = re.search(r"\b([a-z][a-z0-9-]*)\(", rest)
    return f"{result.lstrip('%')} {opcode.group(1) if opcode else ''}".strip()


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def device_planes(trace: dict) -> list:
    """One plane per chip: ``/device:TPU:<n>``, not the planes of a chip's
    other units that the profiler lists beside it."""
    return [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]


def line_events(plane: dict, line_name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == line_name:
            return line["events"]
    return []


def annotations(trace: dict, names) -> list:
    """``(name, start_ns, end_ns)`` of the host annotations in ``names``,
    in time order."""
    found = []
    for plane in trace["planes"]:
        if plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            for name, start, dur, _ in line["events"]:
                if name in names:
                    found.append((name, start, start + dur))
    return sorted(found, key=lambda a: a[1])


def union(intervals) -> list:
    """Merged ``(start, end)`` intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def clip(merged, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in merged
            if min(e, hi) > max(s, lo)]


def total(merged) -> float:
    return float(sum(e - s for s, e in merged))


def gaps(merged, lo: float, hi: float) -> list:
    """The idle intervals of ``[lo, hi]`` that ``merged`` leaves."""
    out, at = [], lo
    for s, e in clip(merged, lo, hi):
        if s > at:
            out.append([at, s])
        at = max(at, e)
    if hi > at:
        out.append([at, hi])
    return out


def self_times(events) -> list:
    """``[name, start_ns, self_ns, stats]`` per event of one line: its
    duration less what the events nested inside it cover."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []
    for name, start, dur, stats in order:
        while stack and stack[-1][0] <= start:
            stack.pop()
        rec = [name, start, dur, stats]
        if stack and start + dur <= stack[-1][0] + 1e-6:
            stack[-1][1][2] -= dur
        out.append(rec)
        stack.append((start + dur, rec))
    return out


def busy(plane: dict) -> list:
    """The merged intervals in which an operation ran on this device."""
    return union((s, s + d) for _, s, d, _ in line_events(plane, OPS_LINE))


def window_of(marks: list) -> tuple:
    if not marks:
        raise ValueError("the trace holds none of the benchmark's "
                         "annotations")
    return marks[0][1], max(m[2] for m in marks)


def top(pairs, n: int = 10) -> list:
    sums = defaultdict(float)
    for name, ns in pairs:
        sums[name] += ns
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def reduce(trace: dict, span_names=SPANS, call_name: str = SPANS[0]) -> dict:
    """Everything the per-layer readers and the breakdown need; device
    operations are those of the fullest chip (busiest inside the window)."""
    marks = annotations(trace, set(span_names))
    lo, hi = window_of(marks)
    planes = device_planes(trace)
    if not planes:
        raise ValueError("the trace has no device plane")
    busy_by_chip = [clip(busy(p), lo, hi) for p in planes]
    at = max(range(len(planes)), key=lambda i: total(busy_by_chip[i]))
    plane, merged = planes[at], busy_by_chip[at]
    ops = [e for e in self_times(line_events(plane, OPS_LINE))
           if lo <= e[1] < hi]
    modules = [e for e in line_events(plane, MODULES_LINE)
               if lo <= e[1] < hi]
    calls = []
    for name, start, end in marks:
        if name != call_name:
            continue
        inside = [e for e in ops if start <= e[1] < end]
        by_module = defaultdict(float)
        for m_name, m_start, m_dur, _ in modules:
            if start <= m_start < end:
                by_module[m_name] += m_dur
        calls.append({
            "start_ns": start, "end_ns": end,
            "busy_ns": total(clip(merged, start, end)),
            "module_ns": dict(by_module),
            "ops": [[e[0], e[2], e[3]] for e in inside],
        })
    idle = []
    for g_lo, g_hi in gaps(merged, lo, hi):
        covered = 0.0
        for name, start, end in marks:
            part = min(g_hi, end) - max(g_lo, start)
            if part > 0:
                idle.append((name, part))
                covered += part
        if g_hi - g_lo - covered > 1e-3:
            idle.append(("outside_annotations", g_hi - g_lo - covered))
    return {
        "window_ns": hi - lo,
        "busy_ns": total(merged),
        "busy_mean_ns": sum(map(total, busy_by_chip)) / len(busy_by_chip),
        "device_plane": plane["name"],
        "calls": calls,
        "device_ops": top((e[0], e[2]) for e in ops),
        "idle_gaps": top(idle),
    }


def describe(trace: dict, out=sys.stdout) -> None:
    for plane in trace["planes"]:
        print("PLANE", plane["name"], file=out)
        for line in plane["lines"]:
            ev = line["events"]
            if not ev:
                continue
            span = (min(e[1] for e in ev), max(e[1] + e[2] for e in ev))
            print(f"  LINE {line['name']!r}: {len(ev)} events, "
                  f"{span[0] / 1e9:.6f}..{span[1] / 1e9:.6f} s", file=out)
            for name, sec in top(((e[0], e[2]) for e in self_times(ev)), 12):
                stats = next(e[3] for e in ev if e[0] == name)
                print(f"      {sec:12.6f} s  {name}  {stats}", file=out)


if __name__ == "__main__":
    path = sys.argv[1]
    describe(load_json(path) if path.endswith(".json")
             else load_xplane(path, with_stats=True))
