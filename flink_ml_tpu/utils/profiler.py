"""Device-fenced wall timing — the TPU answer to the reference's latency
tracking.

The reference's only tracing is Flink LatencyMarker stats in the per-round
wrapper (SURVEY §5).  Here a wall-clock timing of device work ends on a
fetch of its result (:class:`StepTimer`, :func:`fenced_call`).  To put a
named host phase on the profiler's timeline use ``obs.tracer.span``:
every span is a ``jax.profiler.TraceAnnotation``.
"""

from __future__ import annotations

import time

from typing import Any, Callable, Optional, Tuple

import jax

__all__ = ["StepTimer", "fenced_call"]


class StepTimer:
    """Wall-clock timer with a device fence: dispatch is asynchronous, so
    ``stop(probe_array)`` fetches the probe value (``device_get`` waits
    for the work that produces it) before reading the clock."""

    def __init__(self) -> None:
        self._t0: Optional[float] = None
        self.laps = []

    def start(self) -> "StepTimer":
        self._t0 = time.perf_counter()
        return self

    def stop(self, probe=None) -> float:
        if probe is not None:
            jax.device_get(probe)
        if self._t0 is None:
            raise RuntimeError("StepTimer.stop() before start()")
        elapsed = time.perf_counter() - self._t0
        self.laps.append(elapsed)
        self._t0 = None
        return elapsed


def _default_probe(result: Any) -> Any:
    """The completion probe when the caller names none: the first array
    leaf of the result — fetching ANY output of a dispatch waits for
    the whole dispatch."""
    for leaf in jax.tree_util.tree_leaves(result):
        if hasattr(leaf, "shape"):
            return leaf
    return None


def fenced_call(fn: Callable, *args: Any,
                probe_of: Optional[Callable[[Any], Any]] = None,
                **kwargs: Any) -> Tuple[Any, float]:
    """THE device-fenced wall-timing idiom (ISSUE 13 satellite), one
    copy: run ``fn(*args, **kwargs)``, fence completion by
    ``device_get``-ing a probe from the result (``probe_of(result)``,
    default: first array leaf), and return ``(result, seconds)``.

    This is what bench.py's leg timings and the tracing layer's
    device-execute spans ride, replacing the hand-rolled
    ``perf_counter -> call -> np.asarray(...) -> perf_counter`` copies;
    the graftlint ``unfenced-timing`` pass flags the hand-rolled form
    when the fence is missing.  Never call this from inside a jitted
    step/scan body — the fence belongs on the host side of the dispatch
    boundary (the ``StepTimer`` stance)."""
    timer = StepTimer().start()
    result = fn(*args, **kwargs)
    probe = probe_of(result) if probe_of is not None \
        else _default_probe(result)
    return result, timer.stop(probe)
