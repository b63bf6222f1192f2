"""Device-fenced wall timing — the TPU answer to the reference's latency
tracking.

The reference's only tracing is Flink LatencyMarker stats in the per-round
wrapper (SURVEY §5).  Here a wall-clock timing of device work ends on a
fetch of its result (:class:`StepTimer`).  To put a named host phase on
the profiler's timeline use ``obs.tracer.span``: every span is a
``jax.profiler.TraceAnnotation``.
"""

from __future__ import annotations

import time

from typing import Optional

import jax

__all__ = ["StepTimer"]


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
