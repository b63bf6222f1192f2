"""The program's own spans inside ``fit()``, read from the run's trace.

``flink_ml_tpu.obs.tracer.span`` is a ``jax.profiler.TraceAnnotation``,
so in a traced run the program's spans are events on ``/host:CPU`` of the
same ``.xplane.pb`` as the device's operations, on the device's clock.
One ``fit()`` is one root span ``fit`` with the phase spans ``PHASES`` as
its flat children; a phase may come in adjacent pieces and may have the
parts ``PARTS`` inside it.

``fits(trace)`` works on the plain data ``trace_reduce`` defines and gives
one record per ``fit`` root that lies inside one of the benchmark's
``fit.call`` marks (so a warm-up is out):

    {"start_ns", "root_s",
     "total_s": {name: seconds of the spans of that name},
     "phases_s": the union of the phase spans,
     "busy_s":  {name: device-busy seconds inside the spans of that name}}

``busy_s`` is empty where the trace has no chip.  A trace without program
spans (the parent commit's) gives no record, and every reader ``None``.

    python benchmarks/harness/program_spans.py <trace dir>

prints the split of every fit of the trace under
``<trace dir>/plugins/profile/``.
"""

from __future__ import annotations

import functools
import glob
import os
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from harness import trace_reduce as tr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ROOT_SPAN = "fit"
PHASES = ("fit.gather", "fit.arrange", "fit.upload", "iterate.dispatch",
          "fit.fetch")
PARTS = ("fit.gather.stack", "fit.gather.cast", "fit.arrange.init",
         "fit.arrange.pad", "fit.arrange.permute", "fit.arrange.ell_layout")
NAMES = frozenset((ROOT_SPAN,) + PHASES + PARTS)
CALL_MARK = tr.SPANS[0]


def fits(trace: dict) -> list:
    """One record per ``fit()`` of the traced window, in time order.  A
    span belongs to the root that contains it on its line (its thread)."""
    marks = [(lo, hi) for _, lo, hi in tr.annotations(trace, {CALL_MARK})]
    busy = max((tr.busy(p) for p in tr.device_planes(trace)), key=tr.total,
               default=None)
    out = []
    for plane in trace["planes"]:
        if tr.DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            spans = [e for e in line["events"] if e[0] in NAMES]
            for name, lo, dur, _ in spans:
                hi = lo + dur
                if name != ROOT_SPAN or not any(
                        m_lo <= lo and hi <= m_hi for m_lo, m_hi in marks):
                    continue
                inside = sorted((e for e in spans
                                 if lo <= e[1] and e[1] + e[2] <= hi),
                                key=lambda e: (e[1], -e[2]))
                out.append(_record(inside, busy))
    return sorted(out, key=lambda r: r["start_ns"])


def _record(inside, busy) -> dict:
    """``inside``: the root first, then its spans in time order."""
    total_s, busy_s, by_name = {}, {}, {}
    for name, start, dur, _ in inside:
        total_s[name] = total_s.get(name, 0.0) + dur / 1e9
        by_name.setdefault(name, []).append((start, start + dur))
    if busy is not None:
        for name, intervals in by_name.items():
            busy_s[name] = sum(tr.total(tr.clip(busy, lo, hi))
                               for lo, hi in intervals) / 1e9
    phases = tr.union(iv for name in PHASES for iv in by_name.get(name, ()))
    return {"start_ns": inside[0][1], "root_s": inside[0][2] / 1e9,
            "total_s": total_s, "phases_s": tr.total(phases) / 1e9,
            "busy_s": busy_s}


def transfer_wait_s(record):
    """The seconds of ``fit.fetch`` in which the device ran nothing: the
    puts are asynchronous, so the host's wait for the transfer shows in
    the fit's first fence and not in ``fit.upload``.  ``None`` without a
    chip in the trace."""
    busy = record["busy_s"].get("fit.fetch")
    fetch = record["total_s"].get("fit.fetch")
    return None if busy is None or fetch is None else fetch - busy


@functools.lru_cache(maxsize=None)
def fits_of_cell(cell: str) -> tuple:
    """The records of the traced run of ``cell``, whose trace ``run.py``
    left under ``.bench_trace/<cell>/``; read once per process."""
    return _fits_under(os.path.join(ROOT, ".bench_trace", cell))


def _fits_under(trace_dir: str) -> tuple:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        return ()
    return tuple(fits(tr.load_xplane(
        found[0], host_names=NAMES | set(tr.SPANS))))


def mean(ctx, of_fit):
    """The mean over the window's fits of ``of_fit(record)``; ``None``
    if there is no fit or a fit gives ``None``."""
    values = [of_fit(r) for r in fits_of_cell(ctx["cell"])]
    if not values or any(v is None for v in values):
        return None
    return sum(values) / len(values)


def span_seconds(ctx, name: str):
    """Mean seconds a fit spends in the spans named ``name``."""
    return mean(ctx, lambda r: r["total_s"].get(name))


if __name__ == "__main__":
    for r in _fits_under(sys.argv[1]):
        print(f"fit: root {r['root_s']:.6f} s, unattributed "
              f"{r['root_s'] - r['phases_s']:.6f} s, transfer wait "
              f"{transfer_wait_s(r)}")
        for name, seconds in r["total_s"].items():   # by first start
            print(f"  {name:26s} {seconds:10.6f}  device busy "
                  f"{r['busy_s'].get(name, '-')}")
