"""Compilations the window pays for.

The program's ``count_compiles`` counts every request that misses JAX's
in-memory cache of compiled functions (the backend-compile event fires
around ``compile_or_get_cached``, hit or miss).  A request that the
persistent cache then serves loads a program and compiles nothing; JAX
records ``/jax/compilation_cache/cache_hits`` for it.  What is left are
the compilations proper."""

from __future__ import annotations

import contextlib
import threading

_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_LOCK = threading.Lock()
_HITS = [0]
_LISTENING = [False]


def _on_event(event: str, **_) -> None:
    if event == _HIT_EVENT:
        with _LOCK:
            _HITS[0] += 1


@contextlib.contextmanager
def counting():
    """``with counting() as read: ...; read()`` gives ``(requests, hits)``
    since entry, process-wide."""
    import jax.monitoring

    from flink_ml_tpu.utils.backend import count_compiles

    with _LOCK:
        if not _LISTENING[0]:
            jax.monitoring.register_event_listener(_on_event)
            _LISTENING[0] = True
        before = _HITS[0]
    with count_compiles() as requests:
        yield lambda: (requests(), _HITS[0] - before)
