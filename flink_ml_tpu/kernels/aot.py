"""Persistent AOT executable cache — a new process serves in seconds.

The reference keeps long-lived operators hot inside one Flink job, so
compilation cost is paid once per cluster.  Our processes instead repaid
every XLA compile on every restart: serving ``warm_up()`` compiles each
``(op, schema, bucket)`` at startup, which at hundreds of tenants x
bucket ladders is minutes of cold-start.  This module makes compiled
executables a DURABLE artifact:

- **AOT compile**: the registry's dispatch surface (and the
  :func:`aot_jit`-wrapped training step builders) compile through
  ``jax.jit(...).lower().compile()`` so the resulting
  ``jax.stages.Compiled`` is a first-class object we can serialize
  (``jax.experimental.serialize_executable``) instead of an entry buried
  in the jit's in-process cache.
- **Persistent cache**: serialized executables live in a cache directory
  (``FLINK_ML_TPU_AOT_CACHE_PATH`` / ``FrameworkConfig.aot_cache_path``),
  one committed subdirectory per key under ``exec/``.  Every entry
  speaks the PR 5 durability contract (``robustness/durability.py``):
  payload files -> ``manifest.json`` CRCs -> ``COMMITTED`` marker, all
  written into a tmp dir that is ``os.replace``d into place — a crash
  mid-write never leaves a trusted half-entry.
- **Keying**: plan identity (module-qualified fn names + bytecode
  fingerprints + static config) + operand treedef/shapes/dtypes — the
  registry's existing in-memory cache key — EXTENDED with the
  environment fingerprint (jax/jaxlib versions, backend, device kind,
  cache format).  A new jaxlib or a different chip simply misses; it can
  never load an executable built for another world.
- **Fail-safe loads**: a corrupt entry (torn payload, flipped byte,
  missing manifest) or a version-skewed one (meta fingerprint not this
  process's environment) is QUARANTINED (``<key>.corrupt``) and the
  caller transparently falls back to a live compile — never a crash,
  never wrong bits (the executable's own arg validation rejects any
  shape/dtype drift the key missed).

The same cache root also stores the registry autotuner's measured
decisions (``kernels/autotune.py``, ``autotune/`` subdir), so one
directory is THE portable warm state of a process fleet.
"""

from __future__ import annotations

import hashlib
import inspect
import json
import logging
import os
import pickle
import shutil
import threading
import time

from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

__all__ = [
    "ExecutableCache",
    "active_cache",
    "aot_jit",
    "env_fingerprint",
    "plan_token",
    "reset_cache",
    "set_cache",
    "stable_repr",
]

log = logging.getLogger("flink_ml_tpu.kernels")

#: bump when the entry layout / key recipe changes: old entries become
#: fingerprint-skewed (quarantined on contact), never misread
AOT_FORMAT = 2   # 2: meta records the executable's device ids

_EXEC_DIR = "exec"
_TUNE_DIR = "autotune"
_PAYLOAD = "executable.bin"
_TREES = "trees.pkl"
_META = "meta.json"
_DECISION = "decision.json"


def env_fingerprint() -> Dict[str, Any]:
    """The environment a serialized executable is only valid in: jax +
    jaxlib versions (the PJRT serialization format owner), the backend,
    and the device kind (an executable for one chip generation is garbage
    on another).  Part of the key digest AND re-checked against the
    entry's meta on load, so a hand-copied or stale-keyed entry
    quarantines instead of deserializing garbage."""
    import jax
    import jaxlib

    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "format": AOT_FORMAT,
    }


def _code_fingerprint(fn: Callable) -> str:
    """Stable digest of a function's compiled bytecode — the
    invalidation handle for 'the kernel's code changed but its name did
    not'.  TRANSITIVE over module-level helpers: every global the
    bytecode references by name that is itself a Python function (or a
    dict of functions, the ``_HIST_IMPLS``-style dispatch-table idiom)
    folds its own bytecode in recursively, so editing a helper a kernel
    calls invalidates the kernel's cached executables too.  The closure
    stops at non-function globals (modules, classes, arrays): a key
    cannot see through those — the jax/jaxlib fingerprint and the
    ``AOT_FORMAT`` bump are the invalidation levers beyond it.
    Address-carrying reprs (code/object reprs embed ``0x...``) are
    never hashed."""
    h = hashlib.sha256()
    seen: set = set()

    def feed_code(code) -> None:
        h.update(code.co_code)
        for const in code.co_consts:
            if isinstance(const, (int, float, str, bytes, bool,
                                  type(None))):
                h.update(repr(const).encode())
            elif hasattr(const, "co_code"):
                feed_code(const)
        h.update(repr(code.co_names).encode())

    def feed_fn(f) -> None:
        wrapped = getattr(f, "__wrapped__", None)
        if wrapped is not None:       # aot_jit / functools wrappers
            feed_fn(wrapped)
            return
        code = getattr(f, "__code__", None)
        if code is None:
            h.update(repr(getattr(f, "__qualname__",
                                  type(f).__qualname__)).encode())
            return
        if id(code) in seen:
            return
        seen.add(id(code))
        feed_code(code)
        g = getattr(f, "__globals__", {})
        for name in code.co_names:
            ref = g.get(name)
            if ref is None:
                continue
            if isinstance(ref, dict):
                for val in ref.values():
                    if callable(val):
                        feed_fn(val)
            elif callable(ref) and (hasattr(ref, "__code__")
                                    or hasattr(ref, "__wrapped__")):
                feed_fn(ref)

    feed_fn(fn)
    return h.hexdigest()[:16]


def stable_repr(obj: Any, _depth: int = 0, _seen: Optional[set] = None
                ) -> str:
    """An address-free ``repr`` for cache keys: the default object repr
    embeds ``at 0x...``, which would give every process a different
    token for the same plan (KMeans statics carry the DistanceMeasure
    singleton).  Objects render as their qualified class plus the
    stable repr of their instance state, functions as qualified name +
    bytecode fingerprint; primitives/containers recurse.

    A value the renderer cannot stably see through (cyclic, or nested
    past the depth bound) is POISONED with its process-local ``id`` —
    the resulting key can never falsely match anything persisted by
    another process (or another object in this one), so an unkeyable
    static degrades to cache misses, never to loading the wrong
    executable."""
    if isinstance(obj, (int, float, complex, str, bytes, bool,
                        type(None))):
        return repr(obj)
    if _depth > 6:
        return f"<unkeyed:{type(obj).__qualname__}:{id(obj)}>"
    if _seen is None:
        _seen = set()
    if id(obj) in _seen:
        return f"<unkeyed:cycle:{id(obj)}>"
    _seen = _seen | {id(obj)}
    if isinstance(obj, tuple):
        return "(" + ",".join(stable_repr(x, _depth + 1, _seen)
                              for x in obj) + ")"
    if isinstance(obj, list):
        return "[" + ",".join(stable_repr(x, _depth + 1, _seen)
                              for x in obj) + "]"
    if isinstance(obj, dict):
        items = sorted((stable_repr(k, _depth + 1, _seen),
                        stable_repr(v, _depth + 1, _seen))
                       for k, v in obj.items())
        return "{" + ",".join(f"{k}:{v}" for k, v in items) + "}"
    if isinstance(obj, type):
        return f"<class {obj.__module__}.{obj.__qualname__}>"
    if callable(obj) and hasattr(obj, "__qualname__"):
        return (f"<fn {getattr(obj, '__module__', '?')}."
                f"{obj.__qualname__}:{_code_fingerprint(obj)}>")
    r = repr(obj)
    if " at 0x" not in r:
        return r
    state = getattr(obj, "__dict__", None)
    return (f"<{type(obj).__module__}.{type(obj).__qualname__} "
            f"{stable_repr(state, _depth + 1, _seen) if state else ''}>")


def plan_token(plan: tuple) -> str:
    """Cross-process identity of a dispatch plan: per stage, the
    module-qualified fn name, its bytecode fingerprint, and the static
    config tuple (address-free: :func:`stable_repr`).  Two processes
    running the same code build the same token; an edited kernel fn
    changes it."""
    parts = []
    for fn, static in plan:
        parts.append((f"{fn.__module__}.{fn.__qualname__}",
                      _code_fingerprint(fn), stable_repr(static)))
    return repr(parts)


def _digest(kind: str, token: str, shape_repr: str,
            fingerprint: Dict[str, Any]) -> str:
    blob = json.dumps({"kind": kind, "token": token, "shapes": shape_repr,
                       "env": fingerprint}, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()


class ExecutableCache:
    """One cache root: ``exec/<key>`` committed executable entries plus
    ``autotune/<key>`` committed decision entries, shared by every
    consumer in the process (and by every process pointed at the root).

    Loads are memoized per process (``_loaded``): a key deserializes
    once, steady-state dispatches call the held ``Compiled`` directly.
    """

    def __init__(self, root: str):
        self.root = root
        self._fingerprint = env_fingerprint()
        self._lock = threading.Lock()
        self._build_lock = threading.Lock()
        self._loaded: Dict[str, Any] = {}
        self._decisions: Optional[Dict[Tuple[str, str], Dict]] = None
        os.makedirs(os.path.join(root, _EXEC_DIR), exist_ok=True)
        os.makedirs(os.path.join(root, _TUNE_DIR), exist_ok=True)

    # -- keys ----------------------------------------------------------------
    @property
    def fingerprint(self) -> Dict[str, Any]:
        return dict(self._fingerprint)

    def key_for(self, kind: str, token: str, shape_repr: str) -> str:
        return _digest(kind, token, shape_repr, self._fingerprint)

    # -- the load-or-build protocol ------------------------------------------
    def load_or_build(self, key: str, build: Callable[[], Any], *,
                      label: str = "?") -> Tuple[Any, str]:
        """Resolve ``key`` to a callable executable: in-memory hit ->
        disk load (an *aot hit*) -> live ``build()`` (an *aot miss*,
        compile + store).  Returns ``(compiled, source)`` with source in
        ``("memory", "aot", "compile")``.  Disk failures of any kind
        degrade to the live compile; the event is accounted on
        ``kernel_stats``."""
        from .registry import kernel_stats

        with self._lock:
            compiled = self._loaded.get(key)
        if compiled is not None:
            return compiled, "memory"
        with self._build_lock:
            with self._lock:       # raced another thread's miss path
                compiled = self._loaded.get(key)
            if compiled is not None:
                return compiled, "memory"
            t0 = time.perf_counter()
            compiled = self._load_entry(key)
            if compiled is not None:
                kernel_stats.record_aot(label, event="hit",
                                        seconds=time.perf_counter() - t0)
                with self._lock:
                    self._loaded[key] = compiled
                return compiled, "aot"
            t0 = time.perf_counter()
            compiled = build()
            kernel_stats.record_aot(label, event="miss",
                                    seconds=time.perf_counter() - t0)
            self._store_entry(key, compiled, label)
            with self._lock:
                self._loaded[key] = compiled
            return compiled, "compile"

    def forget_loaded(self) -> None:
        """Drop the in-process executable memo (tests: force the next
        dispatch through the disk-load path, as a fresh process would)."""
        with self._lock:
            self._loaded.clear()

    # -- disk entries --------------------------------------------------------
    def _entry_dir(self, key: str) -> str:
        return os.path.join(self.root, _EXEC_DIR, key)

    def _load_entry(self, key: str):
        import jax
        from jax.experimental import serialize_executable as se

        from ..robustness.durability import (CorruptStateError, quarantine,
                                             verify_dir)
        from .registry import kernel_stats

        entry = self._entry_dir(key)
        if not os.path.isdir(entry):
            return None
        try:
            verify_dir(entry, allow_legacy=False)
            with open(os.path.join(entry, _META)) as f:
                meta = json.load(f)
            if meta.get("fingerprint") != self._fingerprint:
                raise CorruptStateError(
                    f"{entry}: executable fingerprint "
                    f"{meta.get('fingerprint')!r} is not this process's "
                    f"{self._fingerprint!r} (version/backend skew)")
            with open(os.path.join(entry, _TREES), "rb") as f:
                in_tree, out_tree = pickle.load(f)
            with open(os.path.join(entry, _PAYLOAD), "rb") as f:
                payload = f.read()
            # load onto the devices the stored program was compiled for:
            # the default is ALL local devices, which a one-device
            # program cannot execute on
            by_id = {d.id: d for d in jax.devices()}
            return se.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=[by_id[i] for i in meta["device_ids"]])
        except CorruptStateError as exc:
            log.warning("AOT cache entry failed validation (%s); "
                        "quarantining and recompiling live", exc)
            kernel_stats.record_aot(key, event="quarantine")
            self._quarantine_entry(entry)
            return None
        except Exception as exc:  # noqa: BLE001 — CRC-valid garbage, pickle
            # drift inside the payload, PJRT refusal: same degraded path
            log.warning("AOT cache entry %s failed to deserialize (%r); "
                        "quarantining and recompiling live", entry, exc)
            kernel_stats.record_aot(key, event="quarantine")
            self._quarantine_entry(entry)
            return None

    @staticmethod
    def _quarantine_entry(entry: str) -> None:
        from ..robustness.durability import quarantine

        try:
            quarantine(entry)
        except OSError:
            # a concurrent process quarantined (or replaced) it first —
            # the bad bytes are out of our path either way
            pass

    def _store_entry(self, key: str, compiled, label: str) -> None:
        from jax.experimental import serialize_executable as se

        from ..robustness.durability import commit_dir
        from .registry import kernel_stats

        final = self._entry_dir(key)
        if os.path.isdir(final):
            return
        try:
            payload, in_tree, out_tree = se.serialize(compiled)
            device_ids = [d.id for d in
                          compiled.runtime_executable().local_devices()]
        except Exception as exc:  # noqa: BLE001 — backend w/o serialization
            kernel_stats.record_aot(label, event="unserializable")
            log.info("executable for %s is not serializable on this "
                     "backend (%r); serving from the in-process copy only",
                     label, exc)
            return
        tmp = f"{final}.tmp.{os.getpid()}"
        try:
            if os.path.exists(tmp):
                shutil.rmtree(tmp)
            os.makedirs(tmp)
            with open(os.path.join(tmp, _PAYLOAD), "wb") as f:
                f.write(payload)
            with open(os.path.join(tmp, _TREES), "wb") as f:
                pickle.dump((in_tree, out_tree), f)
            with open(os.path.join(tmp, _META), "w") as f:
                json.dump({"format": AOT_FORMAT, "label": label,
                           "key": key, "fingerprint": self._fingerprint,
                           "device_ids": device_ids,
                           "payload_bytes": len(payload)}, f, indent=1,
                          sort_keys=True)
            commit_dir(tmp)
            os.replace(tmp, final)
        except OSError as exc:
            # two legitimate shapes land here: another process committed
            # this key first (rename onto a non-empty dir — its entry is
            # as good as ours), or the cache volume itself failed the
            # write (ENOSPC, permissions).  Either way the executable in
            # hand is valid and the process must keep serving from it —
            # a broken cache DISK degrades persistence, never dispatch.
            shutil.rmtree(tmp, ignore_errors=True)
            if not os.path.isdir(final):
                kernel_stats.record_aot(label, event="store_failed")
                log.warning("AOT cache store of %s failed (%r); serving "
                            "from the in-process copy only", label, exc)
            return
        kernel_stats.record_aot(label, event="store")

    # -- autotune decisions (the same durable root) --------------------------
    def _decision_dir(self, key: str) -> str:
        return os.path.join(self.root, _TUNE_DIR, key)

    def _decision_key(self, op: str, sig_repr: str) -> str:
        env = {"backend": self._fingerprint["backend"],
               "device_kind": self._fingerprint["device_kind"]}
        return _digest("autotune", f"{op}|{sig_repr}", "", env)

    def _load_decisions(self) -> Dict[Tuple[str, str], Dict]:
        """Scan (once per process) every committed decision entry;
        corrupt or skewed entries quarantine exactly like executables."""
        from ..robustness.durability import (CorruptStateError, quarantine,
                                             verify_dir)
        from .registry import kernel_stats

        decisions: Dict[Tuple[str, str], Dict] = {}
        root = os.path.join(self.root, _TUNE_DIR)
        device = {"backend": self._fingerprint["backend"],
                  "device_kind": self._fingerprint["device_kind"]}
        for name in sorted(os.listdir(root)):
            entry = os.path.join(root, name)
            if not os.path.isdir(entry) or ".corrupt" in name \
                    or ".tmp." in name:
                continue
            try:
                verify_dir(entry, allow_legacy=False)
                with open(os.path.join(entry, _DECISION)) as f:
                    dec = json.load(f)
                if dec.get("device") != device:
                    # a VALID decision from another backend/chip sharing
                    # the fleet cache root: not ours to use — and not
                    # ours to destroy (its owner still loads it)
                    continue
                decisions[(dec["op"], dec["sig"])] = dec
            except (CorruptStateError, KeyError, json.JSONDecodeError,
                    OSError) as exc:
                log.warning("autotune decision %s failed validation (%r); "
                            "quarantining (re-search on next encounter)",
                            entry, exc)
                kernel_stats.record_aot(name, event="quarantine")
                try:
                    quarantine(entry)
                except OSError:
                    # the entry vanished mid-scan (a concurrent re-tune's
                    # retire window) or another process quarantined it
                    # first — either way it is gone from the scan's view
                    pass
        return decisions

    def decisions(self) -> Dict[Tuple[str, str], Dict]:
        with self._lock:
            if self._decisions is None:
                self._decisions = self._load_decisions()
            return self._decisions

    def get_decision(self, op: str, sig_repr: str) -> Optional[Dict]:
        return self.decisions().get((op, sig_repr))

    def record_decision(self, decision: Dict) -> None:
        """Commit one measured decision (op + sig + winner + timings)
        durably and into the in-memory view.  Same tmp -> commit ->
        ``os.replace`` protocol as executables."""
        from ..robustness.durability import commit_dir

        final = self._decision_dir(
            self._decision_key(decision["op"], decision["sig"]))
        tmp = f"{final}.tmp.{os.getpid()}"
        try:
            if os.path.exists(tmp):
                shutil.rmtree(tmp)
            os.makedirs(tmp)
            with open(os.path.join(tmp, _DECISION), "w") as f:
                json.dump(decision, f, indent=1, sort_keys=True)
            commit_dir(tmp)
            if os.path.isdir(final):       # re-tune overwrites: retire the
                shutil.rmtree(final)       # old committed entry first
            os.replace(tmp, final)
        except OSError as exc:
            # lost the race to a concurrent tuner, or the cache volume
            # failed the write: the measured decision still applies
            # in-process (below) — persistence degrades, search does not
            shutil.rmtree(tmp, ignore_errors=True)
            if not os.path.isdir(final):
                log.warning("autotune decision store for %s failed (%r); "
                            "kept in-process only",
                            decision.get("op"), exc)
        with self._lock:
            if self._decisions is None:
                self._decisions = self._load_decisions()
            self._decisions[(decision["op"], decision["sig"])] = decision


# ---------------------------------------------------------------------------
# the process-wide active cache (config-resolved, test-overridable)
# ---------------------------------------------------------------------------

_ACTIVE: list = []          # [] = unresolved; [None] = resolved, disabled
_ACTIVE_LOCK = threading.Lock()


def active_cache() -> Optional[ExecutableCache]:
    """The process's cache, resolved once from
    ``FrameworkConfig.aot_cache_path`` (env
    ``FLINK_ML_TPU_AOT_CACHE_PATH``); None when no root is configured —
    every AOT hook then degrades to exactly the pre-cache behavior."""
    if not _ACTIVE:
        with _ACTIVE_LOCK:
            if not _ACTIVE:
                from ..utils.config import get_config

                path = get_config().aot_cache_path
                _ACTIVE.append(ExecutableCache(path) if path else None)
    return _ACTIVE[0]


def set_cache(cache: Optional[ExecutableCache]) -> None:
    """Pin (or disable, with None) the process cache — tests and embedding
    applications that manage their own config lifecycle."""
    with _ACTIVE_LOCK:
        _ACTIVE.clear()
        _ACTIVE.append(cache)


def reset_cache() -> None:
    """Forget the resolution so the next :func:`active_cache` re-reads
    config (tests restoring global state)."""
    with _ACTIVE_LOCK:
        _ACTIVE.clear()


# ---------------------------------------------------------------------------
# aot_jit — persistent-executable wrapper for module-level jits
# (the training step builders' pre-warm path)
# ---------------------------------------------------------------------------

def _contains_tracer(leaves) -> bool:
    import jax

    return any(isinstance(leaf, jax.core.Tracer) for leaf in leaves)


class _AotJit:
    """``jax.jit`` plus the persistent executable cache.

    With no cache configured (or when called with tracers — i.e. from
    inside an enclosing jit/scan, where an executable cannot be invoked)
    this IS the wrapped jit: identical dispatch, identical cache
    behavior.  With a cache, top-level calls route through
    ``lower().compile()`` + the durable entry for their
    (code, static-args, operand-shapes) key, so a later process replays
    the compile as a deserialize.  Outputs are bit-identical either way:
    both paths run the same lowered program.
    """

    def __init__(self, fun: Callable, *, static_argnames=(),
                 donate_argnums=()):
        import jax

        self._fun = fun
        self._jit = jax.jit(fun, static_argnames=static_argnames,
                            donate_argnums=donate_argnums)
        self._static = frozenset(
            (static_argnames,) if isinstance(static_argnames, str)
            else static_argnames)
        self._params = list(inspect.signature(fun).parameters)
        self._label = f"{fun.__module__}.{fun.__qualname__}"
        self._token = (self._label, _code_fingerprint(fun))
        self._keys: Dict[Any, str] = {}
        self.__name__ = getattr(fun, "__name__", "aot_jit")
        self.__doc__ = fun.__doc__
        self.__wrapped__ = fun

    def _split(self, args, kwargs):
        statics = []
        dyn_args = []
        for i, a in enumerate(args):
            name = (self._params[i] if i < len(self._params)
                    else f"*{i}")
            if name in self._static:
                statics.append((name, a))
            else:
                dyn_args.append(a)
        dyn_kwargs = {}
        for name, v in kwargs.items():
            if name in self._static:
                statics.append((name, v))
            else:
                dyn_kwargs[name] = v
        return tuple(statics), tuple(dyn_args), dyn_kwargs

    def __call__(self, *args, **kwargs):
        import jax

        cache = active_cache()
        if cache is None:
            return self._jit(*args, **kwargs)
        statics, dyn_args, dyn_kwargs = self._split(args, kwargs)
        leaves, treedef = jax.tree_util.tree_flatten((dyn_args, dyn_kwargs))
        if _contains_tracer(leaves):
            # inside an enclosing trace (chunk scans call these):
            # executables cannot run there — inline as a nested jit
            return self._jit(*args, **kwargs)
        memo_key = (stable_repr(sorted(statics)), str(treedef),
                    tuple((np.shape(leaf), np.result_type(leaf).str)
                          for leaf in leaves))
        key = self._keys.get(memo_key)
        if key is None:
            key = cache.key_for(
                "jit", repr((self._token, memo_key[0])),
                repr((memo_key[1], memo_key[2])))
            self._keys[memo_key] = key
        compiled, _source = cache.load_or_build(
            key, lambda: self._jit.lower(*args, **kwargs).compile(),
            label=self._label)
        try:
            return compiled(*dyn_args, **dyn_kwargs)
        except TypeError:
            # an arg aspect the shape/dtype key cannot see (e.g. weak
            # types) diverged from the lowering: serve correctness from
            # the plain jit and leave the entry for callers it fits
            return self._jit(*args, **kwargs)

    # uniform AOT-ness probe for tests/tooling
    @property
    def aot_label(self) -> str:
        return self._label


def aot_jit(fun: Optional[Callable] = None, *, static_argnames=(),
            donate_argnums=()):
    """Decorator form of :class:`_AotJit` (usable bare or with args)."""
    if fun is None:
        return lambda f: _AotJit(f, static_argnames=static_argnames,
                                 donate_argnums=donate_argnums)
    return _AotJit(fun, static_argnames=static_argnames,
                   donate_argnums=donate_argnums)
