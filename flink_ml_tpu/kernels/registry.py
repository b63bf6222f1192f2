"""Unified kernel registry — ONE compiled surface for pipelines, serving,
and training (ROADMAP item 5).

Three kernel notions grew up independently in this repo: chain
``StageKernel`` segments (``api/chain.py``, PR 4), serving bucketed
executors (``serving/executor.py``, PR 2), and the ``ops/`` Pallas
kernels — each with its own dispatch, padding, and caching rules.  This
module collapses them into one registry with two faces:

- **Implementation lookup** (:func:`lookup`): ``(op, schema-signature,
  backend) -> KernelEntry``.  Training step builders resolve their hot
  path here instead of branching on ``use_pallas`` by hand
  (``models/common/sgd.py``'s ELL path, GBT's histogram impl, KMeans'
  fit plan, Wide&Deep's routed table gradient).  A Pallas implementation
  registered once is picked up by every consumer; the XLA lowering
  registered for the same op is the automatic non-TPU fallback (A/B
  parity asserted in ``tests/test_kernels.py``'s matrix).

- **Dispatch surface** (:func:`dispatch`): THE shared plan-static jit
  (moved here from ``api/chain.py``'s segment runner).  A "plan" is a
  tuple of ``(fn, static)`` stage pairs with params as runtime device
  arguments, so chain segments, the specialized serving executors, and
  the models' own predict entry points all hit ONE compile cache: the
  same ``(op, schema, bucket)`` warmed by any consumer is a cache hit
  for the others (lowering-counter-asserted).

Padding is NOT re-decided per consumer: every registered kernel names
one of the two documented contracts in ``utils/padding.py`` — the
masked pad-to-multiple rule (``pad_rows_with_mask``) or the maskless
zero-fill block rule (``pad_rows_to_block`` + the kernel's own
pad-correction), and the dispatch surface pads rows to the shared
power-of-two buckets (``pad_rows_to_bucket``) exactly as the predict
entry points always did.

Observability: compile-count / cache-hit / dispatch-latency gauges live
on :data:`kernel_stats` and publish into any ``MetricGroup`` (serving
endpoints re-export them per batch), so cross-consumer compile reuse —
CV folds, hot-swap generations, fused serving — is a counted number.
"""

from __future__ import annotations

import threading
import time

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np

from ..obs.trace import tracer

__all__ = [
    "KernelEntry",
    "KernelStats",
    "backends",
    "dispatch",
    "dispatch_count",
    "kernel_stats",
    "lookup",
    "ops",
    "register_kernel",
    "tpu_only",
]


def tpu_only() -> bool:
    """The default availability gate for Pallas/MXU-shaped entries."""
    return jax.default_backend() == "tpu"


@dataclass(frozen=True)
class KernelEntry:
    """One registered implementation of an op on one backend.

    ``fn``'s calling convention is per-``convention``:

    - ``"impl"`` — a raw device function; training step builders call
      it inside their own jitted step/scan (the enclosing program is
      the executable).  Most impl ops register ONE uniform signature
      across backends (the ELL ops, ``routed_table_grad``); the KMeans
      PLANNING ops intentionally do not — their backends take genuinely
      different operands (mask vs maskless contract, a measure
      singleton vs euclidean-only), so the lookup is a plan decision
      and the single backend branch lives NEXT TO the registration
      (``models/clustering/kmeans.py``), never at scattered call
      sites.  An op's calling convention is documented at its
      registration.
    - ``"stage"`` — the chain ``StageKernel`` convention
      ``fn(static, params, cols) -> {name: array}``; dispatched through
      the shared plan jit (:func:`dispatch`), where the ``(fn, static)``
      pair IS the compiled-program identity shared across consumers.

    ``supports(sig)`` is the shape/schema contract (e.g. the fused ELL
    kernels need ``rows % 8 == 0``); ``available()`` is the backend
    gate (Pallas entries default to TPU-only).  A *forced* backend
    lookup bypasses ``available`` — tests run Pallas kernels in
    interpret mode on CPU — but never ``supports``: a shape
    the kernel cannot express must fail loudly, not fall back silently.

    ``forced_only`` is THE way an entry stays out of automatic
    selection on every device: the reason, in words (the TPU compiler's
    refusal of a parked kernel, a measured mismatch with the XLA twin,
    operands only one caller builds).  :func:`lookup` never picks such
    an entry by itself; a forced ``backend=`` lookup still reaches it
    (the interpret-mode parity matrix), and ``chip_smoke.py``'s
    op -> backend table prints the reason.
    """

    op: str
    backend: str
    fn: Callable
    priority: int = 0
    supports: Optional[Callable[[tuple], bool]] = None
    available: Optional[Callable[[], bool]] = None
    convention: str = "impl"   # "impl" | "stage"
    forced_only: Optional[str] = None

    def supports_sig(self, sig: tuple) -> bool:
        return self.supports is None or bool(self.supports(sig))

    def is_available(self) -> bool:
        if self.forced_only is not None:
            return False
        return self.available is None or bool(self.available())


_REGISTRY: Dict[str, Dict[str, KernelEntry]] = {}
_REG_LOCK = threading.Lock()
# Catalog-load state has its OWN (reentrant) lock: the import must not
# run under _REG_LOCK — the catalog's modules call register_kernel,
# which takes it.  RLock so a registering module that itself looks
# something up at import time cannot self-deadlock.
_CATALOG_LOCK = threading.RLock()
_CATALOG_LOADED = [False]


def _ensure_catalog() -> None:
    """Import the modules that register kernels (idempotent, lazy — at
    first lookup, not at package import, so there is no import cycle
    between ``kernels`` and the model/op modules that register into
    it).  Concurrent first lookups serialize on the catalog lock so no
    thread ever reads a half-populated registry, and the loaded flag
    only latches AFTER a successful import — a transient import failure
    surfaces on every lookup until it actually succeeds, instead of
    permanently reporting 'unknown kernel op'."""
    if _CATALOG_LOADED[0]:
        return
    with _CATALOG_LOCK:
        if _CATALOG_LOADED[0]:
            return
        from . import catalog  # noqa: F401  (imports register as a side effect)
        _CATALOG_LOADED[0] = True


def register_kernel(op: str, backend: str, fn: Callable, *,
                    priority: int = 0,
                    supports: Optional[Callable[[tuple], bool]] = None,
                    available: Optional[Callable[[], bool]] = None,
                    convention: str = "impl",
                    forced_only: Optional[str] = None) -> KernelEntry:
    """Register (or replace — module reloads must not duplicate) the
    implementation of ``op`` on ``backend``."""
    if convention not in ("impl", "stage"):
        raise ValueError(f"unknown convention {convention!r}")
    entry = KernelEntry(op=op, backend=backend, fn=fn, priority=priority,
                        supports=supports, available=available,
                        convention=convention, forced_only=forced_only)
    with _REG_LOCK:
        _REGISTRY.setdefault(op, {})[backend] = entry
    return entry


def ops() -> Tuple[str, ...]:
    _ensure_catalog()
    return tuple(sorted(_REGISTRY))


def backends(op: str) -> Tuple[str, ...]:
    _ensure_catalog()
    if op not in _REGISTRY:
        raise KeyError(f"unknown kernel op {op!r}; registered: {ops()}")
    return tuple(sorted(_REGISTRY[op]))


def lookup(op: str, sig: tuple = (), *,
           backend: Optional[str] = None) -> KernelEntry:
    """Resolve ``(op, schema-signature)`` to the best registered entry.

    ``backend`` forces a specific implementation (the tests' XLA
    oracles): availability is bypassed — the caller owns
    running e.g. a Pallas kernel in interpret mode — but a PROVIDED
    ``sig`` still gates through ``supports``, so a shape outside the
    kernel's contract raises instead of silently computing the wrong
    thing.  A forced lookup with no sig returns the entry unchecked
    (the parity matrix probes kernels below their planning thresholds
    on purpose; the kernel's own shape validation still applies at call
    time)."""
    _ensure_catalog()
    table = _REGISTRY.get(op)
    if table is None:
        raise KeyError(f"unknown kernel op {op!r}; registered: {ops()}")
    if backend is not None:
        entry = table.get(backend)
        if entry is None:
            raise KeyError(
                f"op {op!r} has no backend {backend!r}; registered: "
                f"{tuple(sorted(table))}")
        if sig != () and not entry.supports_sig(sig):
            raise ValueError(
                f"op {op!r} backend {backend!r} does not support "
                f"signature {sig!r}")
        return entry
    cands = [e for e in table.values()
             if e.is_available() and e.supports_sig(sig)]
    if not cands:
        raise ValueError(
            f"no available backend of op {op!r} supports signature "
            f"{sig!r} (registered: {tuple(sorted(table))})")
    if len(cands) > 1:
        # a persisted autotune decision beats static priority: the
        # measured-best backend for this (op, sig) on THIS device kind,
        # recorded once by whichever process searched first (no-op —
        # None — when no cache root is configured or nothing is recorded)
        from . import autotune

        tuned = autotune.decided_backend(op, sig)
        if tuned is not None:
            for e in cands:
                if e.backend == tuned:
                    return e
    # deterministic: priority desc, backend name as the tiebreak
    cands.sort(key=lambda e: (-e.priority, e.backend))
    return cands[0]


# --------------------------------------------------------------------------
# observability
# --------------------------------------------------------------------------

class KernelStats:
    """Dispatcher-level accounting: how many distinct ``(plan, shapes)``
    programs compiled, how often later dispatches reused one, and what a
    dispatch costs wall-clock.

    ``compiles`` mirrors the shared jit's cache keying (plan identity +
    operand shapes/dtypes), so "second consumer was a cache hit" is a
    gauge — not only a lowering-counter assertion buried in tests.
    Latency is time-to-return of the (async) dispatch: steady-state it
    is the dispatch overhead, on a cold key it includes the compile
    (which is exactly what an operator wants to see spike)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.compiles = 0
        self.cache_hits = 0
        self.dispatches = 0
        self._lat_ema_ms = 0.0
        self._last_ms = 0.0
        self.per_op: Dict[str, Dict[str, int]] = {}
        #: cache-source accounting (ISSUE 12): where executables came
        #: from — persistent-cache loads vs live compiles — plus the
        #: failure ledger (quarantines never crash, so they MUST count)
        self.aot_hits = 0
        self.aot_misses = 0
        self.aot_stores = 0
        self.aot_store_failed = 0
        self.aot_quarantined = 0
        self.aot_unserializable = 0
        self._aot_load_ms = 0.0
        self._compile_ms = 0.0
        #: autotune decisions observed this process: "op|sig" -> the
        #: chosen backend/block, decision source, and search cost
        self.tuned_ops: Dict[str, Dict[str, Any]] = {}
        #: per-THREAD mirrors of (compiles, aot_hits, cache_hits) — the
        #: warm-up source attribution diffs these, so a hot-swap warming
        #: on the deploy thread is never mislabeled by the old
        #: generation's concurrent serving dispatches
        self._tls = threading.local()

    def _tls_bump(self, field: str) -> None:
        counts = getattr(self._tls, "counts", None)
        if counts is None:
            counts = self._tls.counts = {"compiles": 0, "aot_hits": 0,
                                         "cache_hits": 0}
        counts[field] += 1

    def thread_counts(self) -> Tuple[int, int, int]:
        """(compiles, aot_hits, cache_hits) recorded by THIS thread —
        the race-free warm-up probe (see :meth:`counts` for the
        process-wide view)."""
        counts = getattr(self._tls, "counts", None)
        if counts is None:
            return (0, 0, 0)
        return (counts["compiles"], counts["aot_hits"],
                counts["cache_hits"])

    def record_aot(self, op: str, *, event: str,
                   seconds: float = 0.0) -> None:
        """One persistent-cache event: ``hit`` (deserialized from disk,
        ``seconds`` = load wall), ``miss`` (live compile, ``seconds`` =
        compile wall), ``store``, ``quarantine`` (corrupt/skewed entry
        moved aside), ``unserializable`` (backend refused serialize)."""
        ms = seconds * 1e3
        with self._lock:
            if event == "hit":
                self.aot_hits += 1
                self._aot_load_ms += ms
            elif event == "miss":
                self.aot_misses += 1
                self._compile_ms += ms
            elif event == "store":
                self.aot_stores += 1
            elif event == "store_failed":
                self.aot_store_failed += 1
            elif event == "quarantine":
                self.aot_quarantined += 1
            elif event == "unserializable":
                self.aot_unserializable += 1
            else:
                raise ValueError(f"unknown AOT event {event!r}")
            if event == "hit":
                self._tls_bump("aot_hits")
            if event in ("hit", "miss"):
                rec = self.per_op.setdefault(
                    op, {"dispatches": 0, "compiles": 0, "cache_hits": 0})
                rec["aot_hits"] = rec.get("aot_hits", 0) \
                    + (1 if event == "hit" else 0)
                rec["aot_misses"] = rec.get("aot_misses", 0) \
                    + (1 if event == "miss" else 0)
                which = "aot_load_ms" if event == "hit" else "compile_ms"
                rec[which] = round(rec.get(which, 0.0) + ms, 3)

    def record_autotune(self, op: str, sig: tuple, choice: str, *,
                        kind: str, source: str, search_ms: float,
                        timings: Dict[str, float]) -> None:
        """One autotune resolution: ``source`` "measured" = a fresh
        search ran (and persisted, cache permitting); "cache" = a
        recorded winner was honored with zero search cost."""
        with self._lock:
            self.tuned_ops[f"{op}|{sig!r}"] = {
                "choice": choice, "kind": kind, "source": source,
                "search_ms": round(search_ms, 2), "timings_ms": timings,
            }

    def counts(self) -> Tuple[int, int, int]:
        """(compiles, aot_hits, cache_hits), process-wide.  The serving
        executors' warm-up attribution diffs :meth:`thread_counts`
        instead — this view races with concurrent serving threads."""
        with self._lock:
            return (self.compiles, self.aot_hits, self.cache_hits)

    def record(self, op: str, *, compiled: bool, seconds: float) -> None:
        ms = seconds * 1e3
        with self._lock:
            self.dispatches += 1
            if compiled:
                self.compiles += 1
                self._tls_bump("compiles")
            else:
                self.cache_hits += 1
                self._tls_bump("cache_hits")
            self._last_ms = ms
            self._lat_ema_ms = (0.8 * self._lat_ema_ms + 0.2 * ms
                                if self._lat_ema_ms else ms)
            rec = self.per_op.setdefault(
                op, {"dispatches": 0, "compiles": 0, "cache_hits": 0})
            rec["dispatches"] += 1
            rec["compiles" if compiled else "cache_hits"] += 1

    @property
    def dispatch_latency_ms(self) -> float:
        return self._lat_ema_ms

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "compiles": self.compiles,
                "cache_hits": self.cache_hits,
                "dispatches": self.dispatches,
                "dispatch_latency_ms": round(self._lat_ema_ms, 4),
                "last_dispatch_ms": round(self._last_ms, 4),
                "aot": {
                    "hits": self.aot_hits,
                    "misses": self.aot_misses,
                    "stores": self.aot_stores,
                    "store_failed": self.aot_store_failed,
                    "quarantined": self.aot_quarantined,
                    "unserializable": self.aot_unserializable,
                    "load_ms": round(self._aot_load_ms, 3),
                    "compile_ms": round(self._compile_ms, 3),
                },
                "tuned_ops": {k: dict(v)
                              for k, v in self.tuned_ops.items()},
                "per_op": {k: dict(v) for k, v in self.per_op.items()},
            }

    def publish(self, group) -> None:
        """Refresh gauges on ``group`` (the ``PrefetchStats.publish``
        idiom): serving endpoints re-export the registry's counters into
        their own metric subtree.  The
        cache-source gauges make cold-start composition a measured
        number: ``aot_load_ms`` vs ``compile_ms`` is literally 'what the
        persistent cache saved this process'."""
        snap = self.snapshot()
        for name in ("compiles", "cache_hits", "dispatches",
                     "dispatch_latency_ms", "last_dispatch_ms"):
            group.gauge(name).set(snap[name])
        for name in ("hits", "misses", "stores", "store_failed",
                     "quarantined", "unserializable", "load_ms",
                     "compile_ms"):
            group.gauge(f"aot_{name}").set(snap["aot"][name])
        group.gauge("tuned_ops").set(len(snap["tuned_ops"]))
        group.gauge("ops_seen").set(len(snap["per_op"]))


#: THE process-wide stats instance (one dispatch surface, one ledger).
kernel_stats = KernelStats()


# --------------------------------------------------------------------------
# the shared dispatch surface — ONE jit for every plan
# (moved verbatim from api/chain.py, which now delegates here)
# --------------------------------------------------------------------------

def _run_plan(plan: tuple, params_seq: tuple, one, cols: Dict[str, Any]):
    import jax.numpy as jnp

    out = dict(cols)
    for (fn, static), params in zip(plan, params_seq):
        produced = fn(static, params, out)
        # Rounding barrier: multiply every float output by a RUNTIME 1.0.
        # Without it LLVM contracts elementwise chains across the stage
        # boundary (a trailing mul fused into the next stage's add/sub as
        # one fma), skipping the intermediate rounding the stagewise path
        # performs — 1-ulp drift that breaks bit-exactness.  The compiler
        # cannot fold the mul (the value is a runtime argument), yet any
        # contraction THROUGH it is value-identical: fma(t, 1, c) rounds
        # to exactly t + c.  (jax.lax.optimization_barrier does not help
        # here — XLA duplicates producers into consumer fusions across
        # it.)  Integer columns are exact and pass through untouched.
        out.update({
            name: col * one
            if jnp.issubdtype(jnp.result_type(col), jnp.inexact) else col
            for name, col in produced.items()})
    return out


_ONE = np.float32(1.0)   # the runtime rounding-barrier operand

_JIT_LOCK = threading.Lock()
_PLAN_JIT: list = []


def _plan_jit() -> Callable:
    """The lazily-built shared jit.  static_argnums=0: the plan tuple of
    (fn, static) pairs IS the program identity; params/cols are runtime
    device args — a CrossValidator's k fold models, hot-swapped serving
    generations, and the models' own predict entry points all hit one
    cache entry per (plan, schema, bucket).  On TPU the column dict is
    donated: every consumer's cols are per-call transfer buffers (chain
    segments re-pad per batch, serving pads per request), dead after the
    call — donation lets XLA reuse the HBM allocation.  CPU ignores
    donation, so it is skipped there to avoid spurious warnings (the
    stance ``serving/executor.py`` always took)."""
    if not _PLAN_JIT:
        with _JIT_LOCK:
            if not _PLAN_JIT:
                donate = (3,) if tpu_only() else ()
                _PLAN_JIT.append(jax.jit(_run_plan, static_argnums=(0,),
                                         donate_argnums=donate))
    return _PLAN_JIT[0]


_SEEN_KEYS: set = set()
_DISPATCHES = [0]


def _shape_key(params_seq, cols) -> tuple:
    leaves, treedef = jax.tree_util.tree_flatten((params_seq, cols))
    return (treedef,
            tuple((np.shape(leaf), np.result_type(leaf).str)
                  for leaf in leaves))


_PLAN_KEY_MEMO: Dict[Any, str] = {}


def _persistent_plan_key(cache, plan: tuple, shape_key: tuple) -> str:
    """The durable form of the in-memory dispatch key: plan identity by
    qualified names + bytecode fingerprints (``aot.plan_token``) instead
    of object identity, shapes by their existing repr, the environment
    fingerprint folded in by the cache."""
    memo = (plan, shape_key)
    with _JIT_LOCK:
        key = _PLAN_KEY_MEMO.get(memo)
    if key is None:
        from .aot import plan_token

        treedef, shapes = shape_key
        key = cache.key_for("plan", plan_token(plan),
                            repr((str(treedef), shapes)))
        with _JIT_LOCK:
            _PLAN_KEY_MEMO[memo] = key
    return key


def dispatch(plan: tuple, params_seq: tuple, cols: Dict[str, Any], *,
             op: Optional[str] = None) -> Dict[str, Any]:
    """Run ``plan`` over ``cols`` through THE shared jit, with compile /
    cache-hit / latency accounting.  ``op`` labels the per-op counters
    (defaults to the stage fns' names).

    With a persistent AOT cache configured (``kernels/aot.py``), the
    compiled program for each (plan, shapes) key is held as an explicit
    ``jax.stages.Compiled`` — loaded from the cache dir when a previous
    process already compiled it (cold-start becomes a deserialize),
    compiled-and-stored otherwise.  Either way the executable is the
    SAME lowered program the shared jit would run, so outputs are
    bit-identical across the two paths (asserted in
    ``tests/test_aot_cache.py``)."""
    label = op or "+".join(fn.__name__ for fn, _ in plan)
    key = (plan, _shape_key(params_seq, cols))
    with _JIT_LOCK:
        seen = key in _SEEN_KEYS
        _SEEN_KEYS.add(key)
        _DISPATCHES[0] += 1
    from .aot import active_cache

    cache = active_cache()
    # the dispatch span measures time-to-return of the ASYNC dispatch
    # (the same wall kernel_stats records): steady-state it is the
    # dispatch overhead, on a cold key it includes the compile.  The
    # device-execute completion is a separate, FENCED span recorded by
    # the consumer that fetches the output (api/chain.py::run_kernel) —
    # never a block inside this hot path.
    if cache is None:
        t0 = time.perf_counter()
        with tracer.span("registry_dispatch", cat="kernel", op=label):
            out = _plan_jit()(plan, params_seq, _ONE, cols)
        kernel_stats.record(label, compiled=not seen,
                            seconds=time.perf_counter() - t0)
        return out
    pkey = _persistent_plan_key(cache, plan, key[1])
    compiled, source = cache.load_or_build(
        pkey,
        lambda: _plan_jit().lower(plan, params_seq, _ONE, cols).compile(),
        label=label)
    t0 = time.perf_counter()
    with tracer.span("registry_dispatch", cat="kernel", op=label):
        try:
            out = compiled(params_seq, _ONE, cols)
        except TypeError:
            # an operand aspect the shape key cannot see (weak types)
            # diverged from the lowering — correctness comes first: run the
            # plain jit path for this call, keep the entry for callers it fits
            out = _plan_jit()(plan, params_seq, _ONE, cols)
    kernel_stats.record(label, compiled=(source == "compile"),
                        seconds=time.perf_counter() - t0)
    return out


def dispatch_count() -> int:
    """Shared-jit invocations so far (one per segment/kernel run)."""
    return _DISPATCHES[0]
