"""Registry autotuning — measured backend/block choices, persisted.

The registry's ``lookup`` used to pick entries by static priority, and
the Pallas kernels picked their tile sizes by an analytic VMEM descent
(``ops/kmeans_pallas.py::_pick_block``).  Both are guesses about a
machine the process is actually standing on.  This module replaces the
guess with a measurement, once per fleet:

- :func:`choose` times every candidate (one warm-up call so compile cost
  never pollutes the ranking, then best-of-``repeats`` over ``iters``
  calls, device-synced), picks the winner, and commits the decision to
  the AOT cache root (``kernels/aot.py``, ``autotune/`` subdir — same
  durability contract as the executables).
- A recorded decision is honored WITHOUT re-search by every later call
  in this process and by every later process pointed at the cache root:
  ``registry.lookup`` consults :func:`decided_backend` when several
  backends are available for an op, and the block-size pickers consult
  :func:`decided_choice` before re-running the search.
- Decisions are keyed by ``(op, sig)`` + (backend, device kind): a
  decision measured on one chip generation never leaks onto another.
- Everything degrades to the analytic/priority behavior when no cache
  root is configured — autotuning is an opt-in of the same env knob as
  the executable cache.

Accounting rides :data:`~flink_ml_tpu.kernels.registry.kernel_stats`
(``tuned_ops``): which ops were tuned, what won, whether the decision
was measured fresh or loaded, and what the search cost — so the
cold-start composition is a number, not a vibe.
"""

from __future__ import annotations

import time

from typing import Callable, Dict, Optional, Tuple

__all__ = [
    "choose",
    "decided_backend",
    "decided_choice",
    "enabled",
    "measure",
]


def enabled() -> bool:
    """True when a persistent cache root is configured — the autotuner's
    opt-in gate (searches without a place to persist the winner would
    re-pay the search every process, the exact disease this cures)."""
    from .aot import active_cache

    return active_cache() is not None


def _sig_repr(sig: tuple) -> str:
    return repr(tuple(sig))


def get_decision(op: str, sig: tuple = ()) -> Optional[Dict]:
    """The recorded decision for ``(op, sig)``, or None (disabled /
    never measured / measured for a different device)."""
    from .aot import active_cache

    cache = active_cache()
    if cache is None:
        return None
    return cache.get_decision(op, _sig_repr(sig))


def decided_backend(op: str, sig: tuple = ()) -> Optional[str]:
    """The measured-best BACKEND for ``(op, sig)`` — what
    ``registry.lookup`` consults when several entries are available."""
    dec = get_decision(op, sig)
    if dec is not None and dec.get("kind") == "backend":
        return dec["choice"]
    return None


def decided_choice(op: str, sig: tuple = ()) -> Optional[str]:
    """The measured-best choice token of any kind (block sizes record
    ``kind="block"`` with the block as a string token)."""
    dec = get_decision(op, sig)
    return dec["choice"] if dec is not None else None


def measure(candidates: Dict[str, Callable[[], object]], *,
            iters: int = 3, repeats: int = 2) -> Dict[str, float]:
    """Wall-time each candidate thunk: one untimed warm-up call
    (compile + transfer costs stay out of the ranking), then
    best-of-``repeats`` averages over ``iters`` synced calls, so a
    one-off GC pause cannot crown the wrong winner.  Returns ``{name: best_ms_per_call}``."""
    import jax

    timings: Dict[str, float] = {}
    for name, thunk in candidates.items():
        jax.block_until_ready(thunk())          # compile + warm
        best = None
        for _ in range(repeats):
            t0 = time.perf_counter()
            out = None
            for _ in range(iters):
                out = thunk()
            jax.block_until_ready(out)
            dt = (time.perf_counter() - t0) / iters
            best = dt if best is None else min(best, dt)
        timings[name] = best * 1e3
    return timings


def choose(op: str, sig: tuple,
           candidates: Dict[str, Callable[[], object]], *,
           kind: str = "backend", iters: int = 3, repeats: int = 2,
           probe: str = "") -> Tuple[str, Dict]:
    """Resolve ``(op, sig)`` to the measured-best candidate name.

    A recorded decision whose choice is still among ``candidates`` is
    returned WITHOUT running anything (source ``"cache"``).  Otherwise
    every candidate is measured (source ``"measured"``), the winner is
    persisted to the cache root when one is configured, and
    ``kernel_stats.tuned_ops`` records the decision either way.
    ``probe`` documents what the thunks actually ran (shape, rows) so a
    reader of the decision file can judge its transferability."""
    from .aot import active_cache
    from .registry import kernel_stats

    cache = active_cache()
    dec = cache.get_decision(op, _sig_repr(sig)) if cache else None
    if dec is not None and dec.get("choice") in candidates:
        kernel_stats.record_autotune(op, sig, dec["choice"],
                                     kind=dec.get("kind", kind),
                                     source="cache",
                                     search_ms=0.0,
                                     timings=dec.get("timings_ms", {}))
        return dec["choice"], dec
    t0 = time.perf_counter()
    timings = measure(candidates, iters=iters, repeats=repeats)
    search_ms = (time.perf_counter() - t0) * 1e3
    choice = min(timings, key=timings.get)
    decision = {
        "format": 1,
        "op": op,
        "sig": _sig_repr(sig),
        "kind": kind,
        "choice": choice,
        "timings_ms": {k: round(v, 4) for k, v in timings.items()},
        "search_ms": round(search_ms, 2),
        "probe": probe,
        "device": ({"backend": cache.fingerprint["backend"],
                    "device_kind": cache.fingerprint["device_kind"]}
                   if cache else None),
    }
    if cache is not None:
        cache.record_decision(decision)
    kernel_stats.record_autotune(op, sig, choice, kind=kind,
                                 source="measured", search_ms=search_ms,
                                 timings=decision["timings_ms"])
    return choice, decision
