"""Process-level JAX backend control: the virtual CPU platform the tests
and dry runs use, the persistent compile cache, and the process-wide
counters of compiles and of persistent-cache hits.

``force_virtual_cpu`` is the one shared implementation of the "reset to
an n-device virtual CPU platform" step used by the driver's multi-chip
dry run, the two-process distributed tests, and the multi-host example.
Its ordering constraint: ``jax_num_cpu_devices`` has a validator that
raises ``RuntimeError`` when a backend is already initialized and the
value changes, so any live backend is torn down *before* the config
update.

Capability parity note: this is the stand-in for the reference's
MiniCluster test harness (flink-ml-tests
``.../iteration/UnboundedStreamIterationITCase.java:71``), which brings
up N task managers in one JVM; here N virtual CPU devices stand in for N
TPU chips.
"""

from __future__ import annotations

import contextlib
import os
import threading
from typing import Callable, Iterator

__all__ = ["cache_hit_count", "compile_count", "count_compiles",
           "enable_compile_cache", "force_virtual_cpu"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def force_virtual_cpu(n_devices: int, *, verify: bool = True) -> None:
    """Pin this process to an ``n_devices``-device virtual CPU platform,
    whether or not a backend is already initialized.

    ``verify=False`` skips the final device-count check, leaving the
    backend *uninitialized* — required when ``jax.distributed.initialize``
    runs next, since it refuses to start after any device use.
    """
    import jax
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        from jax.extend.backend import clear_backends

        clear_backends()
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n_devices)
    if verify and len(jax.devices()) < n_devices:
        raise RuntimeError(
            f"requested {n_devices} virtual CPU devices, "
            f"got {len(jax.devices())}")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``tests_tpu``) call
    this before their first compile.  Where ``JAX_COMPILATION_CACHE_DIR``
    is set JAX already reads the directory from it and nothing is set
    here; otherwise the cache lives at ``<checkout>/.jax_cache`` — a
    fixed path, because a cache that moves between runs never hits.
    Every compile is kept, however short, so that a second run of the
    same program adds no entry.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# jax.monitoring reports every XLA backend compile (a jit cache miss, on
# whichever thread it happens) under this event; a program loaded from a
# serialized executable does not compile and does not report.
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# ... and every compile request that the persistent cache served under
# this one
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_COMPILE_LOCK = threading.Lock()
_COMPILES = [0]
_CACHE_HITS = [0]
_LISTENING = [False]


def _on_duration_event(event: str, duration_secs: float, **_) -> None:
    if event == _BACKEND_COMPILE_EVENT:
        with _COMPILE_LOCK:
            _COMPILES[0] += 1


def _on_event(event: str, **_) -> None:
    if event == _CACHE_HIT_EVENT:
        with _COMPILE_LOCK:
            _CACHE_HITS[0] += 1


def _count(counter: list) -> int:
    import jax.monitoring

    with _COMPILE_LOCK:
        if not _LISTENING[0]:
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration_event)
            jax.monitoring.register_event_listener(_on_event)
            _LISTENING[0] = True
        return counter[0]


def compile_count() -> int:
    """XLA compiles in this process, on any thread, since the first call
    of this or of :func:`cache_hit_count` (which installs the listeners
    and returns 0)."""
    return _count(_COMPILES)


def cache_hit_count() -> int:
    """Compile requests of this process, on any thread, that JAX's
    persistent compile cache served (a program read and deserialised,
    nothing compiled), since the first call of this or of
    :func:`compile_count`.  ``iterate`` reads it before and after its one
    ``.compile()`` and notes the difference as ``cache_hit`` on the span
    ``iterate.dispatch.compile``; a compile on another thread inside that
    interval would be counted with it, which no caller does today."""
    return _count(_CACHE_HITS)


@contextlib.contextmanager
def count_compiles() -> Iterator[Callable[[], int]]:
    """``with count_compiles() as count: ...; count()`` — compiles since
    entry, process-wide: a compile on a serve thread counts, which the
    thread-local counters of ``jax._src.test_util`` miss."""
    before = compile_count()
    yield lambda: compile_count() - before
