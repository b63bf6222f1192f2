"""Mid-training checkpoint/resume of iteration state.

The reference achieves exactly-once over a cyclic graph with coordinator/
barrier alignment plus a feedback-records-in-flight log (§3.4,
``checkpoint/Checkpoints.java:43-211``).  In the TPU-native design there are
no in-flight records: an epoch boundary is a consistent cut by construction
(the jitted step is the barrier), so a checkpoint is simply

    (epoch counter, state pytree, optional data-source cursor)

written atomically between epochs.  Exactly-once equivalence becomes
*deterministic replay*: state + epoch + cursor + RNG key fully determine the
rest of training (tested, not assumed — see tests/test_checkpoint.py).

Durability is VALIDATED (robustness PR): every checkpoint directory
carries a per-file CRC32 manifest and an atomic commit marker
(``robustness/durability.py`` — write payload -> manifest -> marker ->
rename), so a torn write, a bit flip, or a crash mid-save is *detected*
at restore time.  ``CheckpointManager.latest()`` scans newest->oldest,
quarantines invalid cuts (``<dir>.corrupt``) and returns the newest
VALID one instead of crashing on — or worse, silently restoring — bad
state.
"""

from __future__ import annotations

import json
import os
import shutil
import time
import zipfile

from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from ..obs.trace import tracer
from ..robustness.durability import (
    CorruptStateError,
    commit_dir,
    quarantine,
    verify_dir,
)
from ..robustness.faults import fault_point

__all__ = ["save_pytree", "load_pytree", "CheckpointManager",
           "CheckpointConfig", "mesh_shape_meta", "require_fleet_compat"]


def mesh_shape_meta(mesh, participant_count: Optional[int] = None
                    ) -> Dict[str, Any]:
    """The fleet-identity metadata every elastic-aware cut carries: the
    writing mesh's axis sizes plus the reduction participant count.  A
    restore onto a DIFFERENT fleet consults this to know what it is
    re-sharding from (``require_fleet_compat``) — a cut without it can
    only safely restore onto a fleet of the original shape."""
    meta: Dict[str, Any] = {
        "mesh_shape": {str(a): int(mesh.shape[a]) for a in mesh.axis_names}}
    if participant_count is not None:
        meta["participant_count"] = int(participant_count)
    return meta


def require_fleet_compat(meta: Dict[str, Any], *, saved_participants: int,
                         current_participants: int, path: str = "") -> None:
    """Gate a cross-fleet restore on the cut carrying mesh-shape
    metadata.  ``CheckpointManager.latest()`` historically assumed a cut
    from the same mesh shape; with elastic fleets a cut can legally
    restore onto a different one — but ONLY when the manifest records
    what fleet wrote it (``mesh_shape``/``participant_count``, attached
    by the elastic-aware fits).  A legacy cut restored onto a different
    fleet raises a diagnosable :class:`CorruptStateError` instead of a
    silent wrong-shape restore."""
    if saved_participants == current_participants:
        return
    if meta.get("mesh_shape") is None \
            and meta.get("participant_count") is None:
        where = f" at {path}" if path else ""
        raise CorruptStateError(
            f"checkpoint{where} holds reducer state for "
            f"{saved_participants} participant(s) but is being restored "
            f"onto a fleet of {current_participants}, and the cut "
            "predates mesh-shape metadata (no 'mesh_shape'/"
            "'participant_count' in its manifest) — refusing the "
            "wrong-shape restore; restore onto a fleet of the original "
            "size, or re-cut the checkpoint with an elastic-aware fit")

_LEAF = "__leaf__"


def _encode_key(key: Any) -> Any:
    """Dict keys keep their python type through JSON (json.dump would
    silently stringify int/bool keys, corrupting the pytree structure)."""
    if isinstance(key, str):
        return key
    if isinstance(key, bool):
        return {"__bool__": key}
    if isinstance(key, int):
        return {"__int__": key}
    if isinstance(key, float):
        return {"__float__": key}
    raise TypeError(f"Unsupported dict key type in checkpoint state: {key!r}")


def _decode_key(node: Any) -> Any:
    if isinstance(node, str):
        return node
    for tag in ("__bool__", "__int__", "__float__"):
        if tag in node:
            return node[tag]
    raise ValueError(f"Corrupt checkpoint key: {node!r}")


def _encode_structure(tree: Any, leaves: List[np.ndarray]) -> Any:
    """JSON-able structure skeleton with leaf placeholders.  Supports dict /
    list / tuple / namedtuple / None containers — the practical shapes of
    training state (incl. optax NamedTuple optimizer states) — plus the
    iteration runtime's :class:`~.body.Workset` (a workset iteration's
    hosted carry is ``(state, Workset)``, so the active-set mask and bound
    state round-trip through crash-recovery cuts bit-exactly)."""
    from .body import Workset

    if tree is None:
        return None
    if isinstance(tree, Workset):
        return {"__workset__": [_encode_structure(tree.mask, leaves),
                                _encode_structure(tree.bounds, leaves)]}
    if isinstance(tree, dict):
        return {"__dict__": [[_encode_key(k), _encode_structure(v, leaves)]
                             for k, v in tree.items()]}
    if isinstance(tree, tuple) and hasattr(tree, "_fields"):
        cls = type(tree)
        return {"__namedtuple__": f"{cls.__module__}.{cls.__qualname__}",
                "fields": [[f, _encode_structure(v, leaves)]
                           for f, v in zip(tree._fields, tree)]}
    if isinstance(tree, tuple):
        return {"__tuple__": [_encode_structure(v, leaves) for v in tree]}
    if isinstance(tree, list):
        return {"__list__": [_encode_structure(v, leaves) for v in tree]}
    idx = len(leaves)
    leaves.append(np.asarray(tree))
    return {_LEAF: idx, "__scalar__": np.ndim(tree) == 0
            and not isinstance(tree, (np.ndarray, jax.Array))}


def _resolve_namedtuple(qualified: str):
    import importlib

    module_name, _, qualname = qualified.rpartition(".")
    obj: Any = importlib.import_module(module_name)
    for part in qualname.split("."):
        obj = getattr(obj, part)
    return obj


def _decode_structure(node: Any, leaves: Dict[int, np.ndarray]) -> Any:
    from .body import Workset

    if node is None:
        return None
    if "__workset__" in node:
        mask_node, bounds_node = node["__workset__"]
        return Workset(_decode_structure(mask_node, leaves),
                       _decode_structure(bounds_node, leaves))
    if "__dict__" in node:
        return {_decode_key(k): _decode_structure(v, leaves)
                for k, v in node["__dict__"]}
    if "__namedtuple__" in node:
        values = {f: _decode_structure(v, leaves) for f, v in node["fields"]}
        cls = _resolve_namedtuple(node["__namedtuple__"])
        return cls(**values)
    if "__tuple__" in node:
        return tuple(_decode_structure(v, leaves) for v in node["__tuple__"])
    if "__list__" in node:
        return [_decode_structure(v, leaves) for v in node["__list__"]]
    leaf = leaves[node[_LEAF]]
    if node.get("__scalar__"):
        return leaf.item()
    return leaf


def _leaf_to_host(x: Any) -> Any:
    """Device leaf -> host value, multi-host safe: a jax.Array sharded over a
    multi-host mesh is NOT fully addressable (``jax.device_get`` would
    throw), so its global value is assembled with a ``process_allgather``
    collective — which every process must enter (it compiles to an
    all-gather over DCN/ICI)."""
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(x, tiled=True))
    return x


def save_pytree(path: str, tree: Any,
                meta: Optional[Dict[str, Any]] = None) -> None:
    """Atomically persist a pytree: arrays into one npz, structure + metadata
    into a JSON sidecar.  Device arrays are fetched to host first (one
    blocking transfer; callers wanting async snapshots copy the state with
    ``jax.device_get`` beforehand).

    Multi-host: every process participates in assembling the global value
    (collective), then ONLY process 0 touches the filesystem — no directory
    races — and a cross-host barrier makes the checkpoint visible to all
    processes before anyone proceeds (the directory must be on a filesystem
    shared by all hosts, the standard pod setup)."""
    multi = jax.process_count() > 1
    leaves: List[np.ndarray] = []
    host_tree = jax.device_get(jax.tree_util.tree_map(_leaf_to_host, tree))
    skeleton = _encode_structure(host_tree, leaves)
    if multi and jax.process_index() != 0:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(f"save_pytree:{path}")
        return
    tmp = path + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    np.savez(os.path.join(tmp, "leaves.npz"),
             **{f"leaf_{i}": leaf for i, leaf in enumerate(leaves)})
    with open(os.path.join(tmp, "structure.json"), "w") as f:
        json.dump({"skeleton": skeleton, "meta": meta or {}}, f)
    # commit protocol: CRC manifest -> (fault seam) -> COMMITTED marker,
    # all BEFORE the rename publishes the directory.  An injected crash
    # here leaves an uncommitted tmp (never trusted); an injected
    # torn/flip fault leaves a committed-but-invalid checkpoint that
    # verify_dir catches at restore (robustness/durability.py).
    commit_dir(tmp, fault_scope="checkpoint.write")
    if os.path.exists(path):
        # Overwrite dance keeping a valid copy at every instant: demote the
        # old checkpoint to .old, promote tmp, then drop .old.  A crash in
        # the window leaves either {path} or {path}.old readable —
        # load_pytree falls back to .old.
        old = path + ".old"
        if os.path.exists(old):
            shutil.rmtree(old)
        os.replace(path, old)
        os.replace(tmp, path)
        shutil.rmtree(old)
    else:
        os.replace(tmp, path)
    if multi:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(f"save_pytree:{path}")


def load_pytree(path: str) -> Tuple[Any, Dict[str, Any]]:
    """Validate (manifest CRCs + commit marker — legacy pre-manifest
    saves pass through) then decode.  Decode-time corruption that slips
    past a legacy save's missing manifest still surfaces as a
    diagnosable :class:`~..robustness.durability.CorruptStateError`
    naming the path, never as silently wrong state."""
    if not os.path.exists(os.path.join(path, "structure.json")) \
            and os.path.exists(os.path.join(path + ".old", "structure.json")):
        path = path + ".old"  # crashed mid-overwrite; previous copy is valid
    verify_dir(path)
    try:
        with open(os.path.join(path, "structure.json")) as f:
            doc = json.load(f)
        with np.load(os.path.join(path, "leaves.npz")) as data:
            leaves = {int(k.split("_", 1)[1]): data[k] for k in data.files}
        return _decode_structure(doc["skeleton"], leaves), doc.get("meta", {})
    except (json.JSONDecodeError, zipfile.BadZipFile, KeyError, EOFError,
            ValueError, FileNotFoundError) as exc:
        # FileNotFoundError: a legacy (pre-manifest) dir can pass
        # verify_dir yet be missing a payload file — a partial save,
        # quarantinable like any other corruption
        raise CorruptStateError(
            f"checkpoint at {path} failed to decode ({exc!r}); the save "
            "is truncated or corrupted — restore from an earlier "
            "checkpoint") from exc


class CheckpointConfig:
    def __init__(self, directory: str, interval: int = 1, max_to_keep: int = 2,
                 async_save: bool = False):
        if interval <= 0:
            raise ValueError("checkpoint interval must be positive")
        self.directory = directory
        self.interval = interval
        self.max_to_keep = max_to_keep
        # Overlap the device->host fetch + disk write with the next epoch's
        # compute (the iteration driver snapshots a device-side copy first so
        # donation can't invalidate the buffers being read).
        self.async_save = async_save


class CheckpointManager:
    """Epoch-granular checkpoint store: ``{dir}/ckpt-{epoch:08d}/``.

    The write is atomic (tmp dir + rename), so a crash mid-write leaves the
    previous checkpoint intact — the analog of the reference aborting a
    pending ``Checkpoints`` log on failure (``Checkpoints.java:179-211``)."""

    def __init__(self, config: CheckpointConfig):
        self.config = config
        os.makedirs(config.directory, exist_ok=True)
        self._pending: Optional["threading.Thread"] = None
        self._pending_error: Optional[BaseException] = None
        #: set by :meth:`latest` — the supervisor reads these to
        #: compute MTTR (detect -> restore complete) and steps replayed
        self.last_restore_at: Optional[float] = None
        self.last_restored_step: Optional[int] = None
        #: timestamp source for ``last_restore_at``; resilient_fit
        #: overwrites it with ITS clock so MTTR never mixes clock domains
        self.clock: Callable[[], float] = time.perf_counter

    def _ckpt_path(self, epoch: int) -> str:
        return os.path.join(self.config.directory, f"ckpt-{epoch:08d}")

    def list_epochs(self) -> List[int]:
        out = []
        for name in os.listdir(self.config.directory):
            if name.startswith("ckpt-") and not name.endswith(".tmp"):
                try:
                    out.append(int(name.split("-", 1)[1]))
                except ValueError:
                    continue
        return sorted(out)

    def should_save(self, epoch: int) -> bool:
        return epoch % self.config.interval == 0

    def save(self, epoch: int, state: Any,
             extra: Optional[Dict[str, Any]] = None) -> str:
        path = self._ckpt_path(epoch)
        meta = {"epoch": epoch}
        if extra:
            meta.update(extra)
        # the cut's slot key IS the trainer's global step for streaming
        # fits — the `step` correlation id a later delta publish carries
        with tracer.span("checkpoint_write", cat="train", step=int(epoch)):
            save_pytree(path, state, meta)
        self._gc()
        return path

    def save_async(self, epoch: int, state: Any,
                   extra: Optional[Dict[str, Any]] = None) -> None:
        """Kick the device->host fetch + write to a background thread.  At
        most one save is in flight; callers must pass state buffers that the
        training loop will NOT donate/overwrite (a device-side copy)."""
        import threading

        self.wait()

        def work():
            try:
                self.save(epoch, state, extra)
            except BaseException as e:  # surfaced on next wait()
                self._pending_error = e

        self._pending = threading.Thread(target=work, daemon=True)
        self._pending.start()

    def wait(self) -> None:
        """Block until the in-flight async save (if any) lands; re-raise its
        error.  Called before restore and at iteration end."""
        if self._pending is not None:
            self._pending.join()
            self._pending = None
        if self._pending_error is not None:
            error, self._pending_error = self._pending_error, None
            raise error

    def latest(self) -> Optional[Tuple[int, Any, Dict[str, Any]]]:
        """The newest VALID checkpoint, scanning newest->oldest.  A cut
        that fails validation/decoding (torn write, bit flip, crash
        mid-commit) is quarantined (``<dir>.corrupt`` — kept for
        forensics, invisible to future scans) and the scan falls back to
        the previous one; only when NO valid checkpoint exists does this
        return None.  The self-healing contract resilient_fit rides: a
        corrupted newest checkpoint costs replayed steps, never the
        run.

        The returned ``meta`` may carry the writing fleet's identity
        (``mesh_shape``/``participant_count`` — :func:`mesh_shape_meta`,
        attached by elastic-aware fits).  Restoring onto a *different*
        fleet is the caller's re-shard job; callers must gate it with
        :func:`require_fleet_compat` so a legacy cut (no fleet
        metadata) fails diagnosably instead of restoring wrong-shaped
        state."""
        self.wait()
        for epoch in reversed(self.list_epochs()):
            path = self._ckpt_path(epoch)
            try:
                state, meta = load_pytree(path)
            except CorruptStateError:
                quarantine(path)
                continue
            self.last_restore_at = self.clock()
            self.last_restored_step = int(meta["epoch"])
            return int(meta["epoch"]), state, meta
        return None

    def restore_latest(self) -> Optional[Tuple[int, Any, Dict[str, Any]]]:
        return self.latest()

    def _gc(self) -> None:
        keep = self.config.max_to_keep
        if keep <= 0:
            return
        if jax.process_count() > 1 and jax.process_index() != 0:
            return  # process 0 owns the directory (save_pytree writes there)
        for epoch in self.list_epochs()[:-keep]:
            shutil.rmtree(self._ckpt_path(epoch), ignore_errors=True)
