"""Span tracing — the request's life story, end to end (ISSUE 13).

The reference's only latency surface is Flink LatencyMarker stats in the
per-round wrapper (SURVEY §5,
``AbstractPerRoundWrapperOperator.java:500-553``) — per-operator
aggregates with no per-request correlation.  :class:`SpanTracer` is the
TPU-native replacement: a **lock-cheap ring-buffered host tracer** whose
spans carry correlation ids, so one exported trace shows
"WAL window N → cut T → delta publish → generation G served request R"
as nested/adjacent events on a shared timeline.

Design stance:

- **One clock with the device.**  Every :meth:`SpanTracer.span` is
  also a ``jax.profiler.TraceAnnotation``: inside a profiler session
  (``jax.profiler.start_trace``) the span lands on the ``/host:CPU``
  plane of the same ``.xplane.pb`` as the device's operations, on the
  device's clock, with its ids as the event's stats — so a span can
  explain a device gap.  No switch turns this on; with no session the
  annotation is inert (about a microsecond, nothing kept).
- **Ring off by default, near-free when off.**  Every instrumentation
  site goes through :meth:`SpanTracer.span` (or guards on
  :attr:`SpanTracer.enabled`); with the ring off ``span()`` is the bare
  annotation — one small object, no lock, no clock read, nothing
  recorded (``tracer.count`` stays 0).  Ring on or off, tracing makes
  ZERO new XLA lowerings: it is pure host bookkeeping and never touches
  a traced program.
- **Bounded memory.**  Completed spans land in a preallocated ring
  (default 64 Ki spans); the lock is held only for the slot bump +
  assignment — never across a clock read or an export.
- **Correlation ids, not parent pointers.**  Spans carry a small dict
  of well-known keys (``request_id``, ``generation``, ``step``,
  ``window``, ``epoch``, ``op``, ``bucket``, ``fit`` — the contract
  ARCHITECTURE.md "Observability" documents); viewers nest by
  (tid, time) containment, and cross-thread causality rides the shared
  ids (a publish's ``generation`` is the served request's
  ``generation``).
- **Device work is fenced, never blocked in step fns.**  Spans that
  claim to cover device execution end on a ``device_get`` of the
  fetched output (the ``utils/profiler.StepTimer`` probe pattern) on
  the HOST side of the dispatch boundary; nothing inside a jitted
  step/scan body ever synchronizes (the graftlint host-sync pass
  covers ``flink_ml_tpu/obs/``).

Exports: Chrome-trace JSON (the ``traceEvents`` array Perfetto and
``chrome://tracing`` load directly) and JSONL (one span per line, the
machine-diffable form).  Both writes are crash-atomic
(tmp -> ``os.replace`` — the PR 5 contract; this module is in the
graftlint atomic-writes durable set).
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import json
import os
import threading
import time

from typing import Any, Dict, Iterator, List, Optional

from jax.profiler import TraceAnnotation

__all__ = ["Span", "SpanTracer", "tracer", "CORRELATION_KEYS"]

#: the correlation-id contract: instrumentation sites only attach these
#: keys (plus free-form strings prefixed ``x_`` for experiments), so a
#: trace consumer can join spans across threads/subsystems without
#: guessing.  ``request_id`` = one serving request; ``generation`` = the
#: live model generation; ``step`` = the trainer's global step (a
#: checkpoint cut and its publish share it); ``window`` = the WAL
#: window index; ``epoch``/``op``/``bucket`` label loops and dispatch;
#: ``tenant`` = the multi-tenant scheduler's tenant name (ISSUE 14) —
#: queue-wait/serve/shed spans carry it, so one trace shows
#: cross-tenant interleaving on the shared device.  ``fit`` = one
#: ``fit()`` call (a process-unique integer from
#: :meth:`SpanTracer.fit_span`, shared by every span opened inside it).
#: Beside its ids a span may carry, through ``note()``, counters of the
#: work it did, named where they are noted; PERF.md section 3 lists every
#: span's notes with the metric that reads each, and ARCHITECTURE.md
#: "Observability" the operator's question behind the ones no metric
#: reads.  Nobody joins spans on those.
CORRELATION_KEYS = ("request_id", "generation", "step", "window",
                    "epoch", "op", "bucket", "tenant", "fit")

_fit_ids = itertools.count(1)
#: the ``fit`` id of the ``fit()`` call this context is inside, if any:
#: how the id reaches ``iterate`` and the registry's dispatch spans
#: without a parameter
_current_fit: contextvars.ContextVar = contextvars.ContextVar(
    "flink_ml_tpu_fit", default=None)


class Span:
    """One completed (or instant) event: wall interval on this host's
    ``perf_counter`` timebase plus the correlation-id dict."""

    __slots__ = ("name", "cat", "t0", "dur", "tid", "ph", "ids")

    def __init__(self, name: str, cat: str, t0: float, dur: float,
                 tid: int, ph: str, ids: Dict[str, Any]):
        self.name = name
        self.cat = cat
        self.t0 = t0
        self.dur = dur
        self.tid = tid
        self.ph = ph            # "X" complete | "i" instant
        self.ids = ids

    def as_dict(self) -> Dict[str, Any]:
        out = {"name": self.name, "cat": self.cat,
               "t0_s": self.t0, "dur_s": self.dur,
               "tid": self.tid, "ph": self.ph}
        out.update(self.ids)
        return out


class _ProfilerSpan(TraceAnnotation):
    """The ring-off span: the profiler's annotation and nothing else.
    ``note(**ids)`` chains and adds stats to the event, so
    instrumentation sites never branch."""

    __slots__ = ()

    def note(self, **ids) -> "_ProfilerSpan":
        self.set_metadata(**ids)
        return self


class _LiveSpan(_ProfilerSpan):
    """One in-flight span with the ring on: the same annotation, and a
    commit to the ring at exit.  ``note(**ids)`` attaches correlation
    ids discovered mid-span (e.g. the generation captured after the
    batch formed)."""

    __slots__ = ("_tracer", "name", "cat", "ids", "_t0")

    def __init__(self, tracer: "SpanTracer", name: str, cat: str,
                 ids: Dict[str, Any]):
        super().__init__(name, **ids)
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.ids = ids
        self._t0 = 0.0

    def __enter__(self) -> "_LiveSpan":
        super().__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self._tracer.add(self.name, self._t0, time.perf_counter(),
                         cat=self.cat, **self.ids)
        super().__exit__(*exc)
        return False

    def note(self, **ids) -> "_LiveSpan":
        self.ids.update(ids)
        return super().note(**ids)


class SpanTracer:
    """Ring-buffered host span recorder (module doc).  One process-wide
    instance lives at :data:`tracer`; tests may construct private
    ones."""

    def __init__(self, capacity: int = 1 << 16):
        if capacity <= 0:
            raise ValueError("capacity must be positive")
        self.enabled = False
        self._capacity = capacity
        self._buf: List[Optional[Span]] = [None] * capacity
        self._n = 0              # monotonic commit counter
        self._dropped = 0        # spans overwritten by the ring wrap
        self._lock = threading.Lock()
        self._epoch = time.perf_counter()   # export-time origin

    # -- lifecycle ----------------------------------------------------------
    def enable(self, capacity: Optional[int] = None) -> "SpanTracer":
        """Clear and start recording (``capacity`` resizes the ring)."""
        with self._lock:
            if capacity is not None and capacity != self._capacity:
                if capacity <= 0:
                    raise ValueError("capacity must be positive")
                self._capacity = capacity
            self._buf = [None] * self._capacity
            self._n = 0
            self._dropped = 0
            self._epoch = time.perf_counter()
            self.enabled = True
        return self

    def disable(self) -> "SpanTracer":
        """Stop recording; already-captured spans stay exportable."""
        self.enabled = False
        return self

    def clear(self) -> None:
        with self._lock:
            self._buf = [None] * self._capacity
            self._n = 0
            self._dropped = 0

    # -- recording ----------------------------------------------------------
    def span(self, name: str, cat: str = "host", **ids):
        """Context manager timing a code region.  Always a profiler
        annotation (inert outside a profiler session); with the ring on
        also a live span committed to the ring at exit.  Inside a
        :meth:`fit_span` the span carries that call's ``fit`` id."""
        fit = _current_fit.get()
        if fit is not None:
            ids.setdefault("fit", fit)
        if not self.enabled:
            return _ProfilerSpan(name, **ids)
        return _LiveSpan(self, name, cat, ids)

    @contextlib.contextmanager
    def fit_span(self, op: str):
        """The root span ``fit`` of one ``fit()`` call of the estimator
        ``op``: draws the call's ``fit`` id and makes it current, so every
        span opened inside (here, in ``iterate``, in the registry) shares
        it."""
        token = _current_fit.set(next(_fit_ids))
        try:
            with self.span("fit", "fit", op=op) as span:
                yield span
        finally:
            _current_fit.reset(token)

    def add(self, name: str, t0: float, t1: float, *, cat: str = "host",
            tid: Optional[int] = None, **ids) -> None:
        """Commit a RETROACTIVE span measured by the caller (``t0``/``t1``
        on the ``perf_counter`` timebase) — how queue-wait is recorded:
        the serve loop stamps it from the request's submit timestamp
        once the batch forms, no tracer work on the submit path.
        Ring-only: a profiler annotation cannot be back-dated."""
        if not self.enabled:
            return
        self._commit(Span(name, cat, t0, max(t1 - t0, 0.0),
                          tid if tid is not None else
                          threading.get_ident(), "X", ids))

    def instant(self, name: str, cat: str = "host", **ids) -> None:
        """Zero-duration marker event (e.g. a shed, a rollback).
        Ring-only, like :meth:`add`."""
        if not self.enabled:
            return
        self._commit(Span(name, cat, time.perf_counter(), 0.0,
                          threading.get_ident(), "i", ids))

    def _commit(self, span: Span) -> None:
        # lock-cheap: the lock covers only the slot bump + assignment
        with self._lock:
            idx = self._n % self._capacity
            if self._buf[idx] is not None:
                self._dropped += 1
            self._buf[idx] = span
            self._n += 1

    # -- reading ------------------------------------------------------------
    @property
    def count(self) -> int:
        """Spans committed since enable (monotonic — includes spans the
        ring has since overwritten)."""
        return self._n

    @property
    def dropped(self) -> int:
        return self._dropped

    def spans(self) -> List[Span]:
        """Retained spans, oldest first (ring order)."""
        with self._lock:
            n, cap = self._n, self._capacity
            if n <= cap:
                return [s for s in self._buf[:n] if s is not None]
            head = n % cap
            return [s for s in self._buf[head:] + self._buf[:head]
                    if s is not None]

    def find(self, name: Optional[str] = None, **ids) -> Iterator[Span]:
        """Retained spans matching ``name`` and every given id."""
        for span in self.spans():
            if name is not None and span.name != name:
                continue
            if all(span.ids.get(k) == v for k, v in ids.items()):
                yield span

    # -- export -------------------------------------------------------------
    def _us(self, t: float) -> float:
        return (t - self._epoch) * 1e6

    def chrome_events(self) -> List[Dict[str, Any]]:
        """The Chrome-trace ``traceEvents`` array (what Perfetto /
        ``chrome://tracing`` load): ``ph: "X"`` complete events with
        microsecond ``ts``/``dur`` relative to the tracer's enable
        point, correlation ids under ``args``."""
        pid = os.getpid()
        events = []
        for s in self.spans():
            ev: Dict[str, Any] = {
                "name": s.name, "cat": s.cat, "ph": s.ph,
                "ts": round(self._us(s.t0), 3), "pid": pid, "tid": s.tid,
                "args": dict(s.ids),
            }
            if s.ph == "X":
                ev["dur"] = round(s.dur * 1e6, 3)
            else:
                ev["s"] = "t"          # instant scope: thread
            events.append(ev)
        return events

    def export_chrome(self, path: str) -> int:
        """Write Chrome-trace JSON (atomic: tmp -> ``os.replace``).
        Returns the event count."""
        events = self.chrome_events()
        payload = {"traceEvents": events, "displayTimeUnit": "ms",
                   "otherData": {"dropped_spans": self._dropped}}
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        return len(events)

    def export_jsonl(self, path: str) -> int:
        """One span per line (machine-diffable; atomic full rewrite)."""
        spans = self.spans()
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            for s in spans:
                f.write(json.dumps(s.as_dict()) + "\n")
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        return len(spans)


#: THE process-wide tracer every instrumentation site records into.
tracer = SpanTracer()
