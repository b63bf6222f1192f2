"""Self-healing training drivers.

:func:`resilient_fit` supervises any checkpointing fit — the streaming
``sgd_fit_outofcore`` and the hosted ``iterate`` both speak the same
``(checkpoint=..., resume=...)`` kwargs — and turns a recoverable crash
into an automatic restore-and-continue instead of a dead process:

1. run the fit; on a recoverable failure (injected crash, I/O error),
2. back off (classified, deterministic schedule — :class:`~.retry
   .RetryPolicy` arithmetic), then
3. re-run with ``resume=True``: the fit restores from the newest VALID
   checkpoint (``CheckpointManager.latest()`` quarantines corrupt/
   partial cuts and falls back — :mod:`.durability`), re-seeks or
   replays its source past the cursor (seek protocol / WAL windows),
   and continues as if never interrupted.

Because restore + replay are deterministic (the PR 1/PR 3 crash+resume
guarantee, EF reducer state included), the supervised run's final
params are **bit-exact** vs the uninterrupted run — asserted in
tests/test_faults.py, including with a corrupted newest checkpoint in
the fallback path.

The per-restart :class:`RecoveryEvent` records MTTR (detect -> restore
complete, which is where training resumes) measured against the
manager's restore timestamp.
"""

from __future__ import annotations

import time

from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional

from ..obs.trace import tracer
from .faults import InjectedCrash
from .retry import RetryPolicy

__all__ = ["RecoveryEvent", "RecoveryReport", "resilient_fit",
           "default_recoverable"]


def default_recoverable(exc: BaseException) -> bool:
    """Can a restore-and-replay heal this?  Crashes and I/O failures
    yes; logic errors (bad config, schema mismatch, corrupt *input*
    data raising ValueError) no — re-running those burns restarts on a
    deterministic failure."""
    return isinstance(exc, (InjectedCrash, OSError, IOError,
                            ConnectionError, TimeoutError))


@dataclass
class RecoveryEvent:
    """One detected failure — or planned fleet resize — + the recovery
    that followed.  ``kind`` is ``"crash"`` (unplanned: injected crash,
    I/O failure, worker death) or ``"resize"`` (planned elasticity: a
    membership change detected at a chunk boundary); both ride the same
    restore-and-continue transition, so ``mttr_s`` doubles as the
    resize-pause wall (detect -> restore complete)."""
    error: str
    detected_at: float
    backoff_s: float = 0.0
    restored_step: Optional[int] = None
    mttr_s: Optional[float] = None   # detect -> restore complete
    kind: str = "crash"
    fleet_size: Optional[int] = None  # live workers AFTER the transition


@dataclass
class RecoveryReport:
    """Filled in place by :func:`resilient_fit` (pass ``report=``).
    Crash-elasticity and planned-elasticity share this one report:
    ``restarts`` counts unplanned recoveries, ``resizes`` counts
    planned membership transitions, and both append to ``events``."""
    restarts: int = 0
    resizes: int = 0
    recovered: bool = False
    events: List[RecoveryEvent] = field(default_factory=list)

    def as_dict(self) -> dict:
        return {
            "restarts": self.restarts,
            "resizes": self.resizes,
            "recovered": self.recovered,
            "events": [{
                "error": e.error,
                "kind": e.kind,
                "fleet_size": e.fleet_size,
                "backoff_s": round(e.backoff_s, 4),
                "restored_step": e.restored_step,
                "mttr_s": (round(e.mttr_s, 4)
                           if e.mttr_s is not None else None),
            } for e in self.events],
        }


def resilient_fit(fit: Callable, *args: Any,
                  checkpoint: Any,
                  max_restarts: int = 3,
                  backoff: Optional[RetryPolicy] = None,
                  recoverable: Callable[[BaseException], bool]
                  = default_recoverable,
                  report: Optional[RecoveryReport] = None,
                  clock: Callable[[], float] = time.perf_counter,
                  elastic: Any = None,
                  max_resizes: int = 64,
                  **kwargs: Any) -> Any:
    """Run ``fit(*args, checkpoint=manager, resume=..., **kwargs)`` under
    supervision; returns whatever ``fit`` returns.

    ``fit`` is any callable taking ``checkpoint``/``resume`` keywords —
    ``sgd_fit_outofcore``, ``iterate``, ``WideDeep.fit_outofcore``, or a
    closure that rebuilds per-attempt state (a fresh ``WindowLog`` over
    a live feed) before delegating.  The first attempt runs with
    ``resume=kwargs.get("resume", False)``; every restart forces
    ``resume=True`` so recovery restores from the newest valid cut and
    replays forward.

    ``checkpoint`` (a ``CheckpointConfig`` or ``CheckpointManager``) is
    normalized to ONE manager shared across attempts, so quarantine
    decisions and save-slot history persist through restarts.  Restarts
    back off on the policy's deterministic schedule (attempt i sleeps
    ``backoff.delay(i)``); a failure that ``recoverable`` rejects — or
    restart ``max_restarts + 1`` — re-raises immediately.

    **Elastic fleets** (``elastic=`` — an
    :class:`~flink_ml_tpu.parallel.elastic.ElasticCoordinator`): the
    supervised fit must accept ``membership=``/``mesh=`` keywords
    (``sgd_fit_outofcore`` and ``WideDeep.fit_outofcore`` do) — both
    are injected per attempt, with the mesh rebuilt from the
    coordinator's CURRENT fleet.  Two transitions share this one loop:

    - *planned elasticity*: the fit raises
      :class:`~flink_ml_tpu.parallel.elastic.ResizeRequested` at a
      chunk boundary after cutting a checkpoint; the supervisor records
      a ``kind="resize"`` event (no backoff, no restart budget
      consumed — a resize is not a failure) and re-runs with
      ``resume=True`` on the new mesh, which restores and re-shards the
      carry there.  ``max_resizes`` bounds a pathological churn loop.
    - *crash elasticity*: any recoverable failure additionally asks the
      coordinator for the post-crash fleet
      (:meth:`~flink_ml_tpu.parallel.elastic.ElasticCoordinator
      .on_failure` — lapsed leases reaped, else the deterministic
      victim), so recovery resumes onto the *surviving* fleet through
      exactly the same restore-and-reshard path.
    """
    # local import: checkpoint.py imports robustness.durability, so a
    # top-level import here would cycle through the package __init__
    from ..iteration.checkpoint import CheckpointConfig, CheckpointManager
    from ..parallel.elastic import ResizeRequested

    manager = (CheckpointManager(checkpoint)
               if isinstance(checkpoint, CheckpointConfig) else checkpoint)
    if not isinstance(manager, CheckpointManager):
        raise TypeError(
            "resilient_fit needs a CheckpointConfig/CheckpointManager "
            f"(got {type(checkpoint).__name__}): without durable cuts "
            "there is nothing to recover from")
    # MTTR subtracts the manager's restore stamp from this supervisor's
    # detect stamp — both must come from the SAME clock, including an
    # injected test clock
    manager.clock = clock
    backoff = backoff or RetryPolicy(max_attempts=max_restarts + 1)
    rep = report if report is not None else RecoveryReport()
    resume = bool(kwargs.pop("resume", False))
    restarts = 0
    resizes = 0
    while True:
        if elastic is not None:
            kwargs["membership"] = elastic
            kwargs["mesh"] = elastic.mesh()
        event: Optional[RecoveryEvent] = None
        if rep.events and rep.events[-1].mttr_s is None:
            event = rep.events[-1]
        try:
            result = fit(*args, checkpoint=manager, resume=resume, **kwargs)
        except ResizeRequested as exc:
            _close_event(event, manager, clock)
            if elastic is None:
                # a fit ran with membership= but nobody owns the resize
                raise
            if resizes >= max_resizes:
                raise RuntimeError(
                    f"fleet resized {resizes} times without the fit "
                    "completing (max_resizes) — membership is churning "
                    "faster than training progresses") from exc
            resizes += 1
            rep.resizes = resizes
            elastic.note_resize()
            rep.events.append(RecoveryEvent(
                error=repr(exc)[:200], detected_at=clock(),
                kind="resize", fleet_size=elastic.fleet_size))
            tracer.instant("fleet_resize", cat="train",
                           x_fleet=elastic.fleet_size,
                           x_step=exc.step)
            resume = True
            continue
        except Exception as exc:  # noqa: BLE001 — classified below
            _close_event(event, manager, clock)
            if restarts >= max_restarts or not recoverable(exc):
                raise
            restarts += 1
            rep.restarts = restarts
            pause = backoff.delay(restarts - 1)
            fleet_size = None
            if elastic is not None:
                # worker death: recovery resumes onto the surviving fleet
                elastic.on_failure(exc)
                fleet_size = elastic.fleet_size
            rep.events.append(RecoveryEvent(
                error=repr(exc)[:200], detected_at=clock(),
                backoff_s=pause, fleet_size=fleet_size))
            tracer.instant("recovery_restart", cat="train",
                           x_error=repr(exc)[:80])
            backoff.sleep(pause)
            resume = True
            continue
        _close_event(event, manager, clock)
        rep.recovered = restarts > 0
        return result


def _close_event(event: Optional["RecoveryEvent"], manager: Any,
                 clock: Callable[[], float]) -> None:
    """Stamp the open recovery event with the restore the just-finished
    attempt performed (manager.last_restore_at is set by ``latest()``;
    training resumes the moment it returns)."""
    if event is None:
        return
    restore_at = getattr(manager, "last_restore_at", None)
    if restore_at is not None and restore_at >= event.detected_at:
        event.mttr_s = restore_at - event.detected_at
        event.restored_step = getattr(manager, "last_restored_step", None)
        if event.kind == "resize":
            # the resize-pause span: detect -> restore complete, where
            # training resumes on the new fleet (both stamps from the
            # supervisor's clock — the perf_counter timebase unless a
            # test injected its own)
            tracer.add("resize_pause", event.detected_at, restore_at,
                       cat="train", x_fleet=event.fleet_size,
                       step=event.restored_step)
    else:
        # no checkpoint existed yet: recovery was a cold re-run
        event.mttr_s = clock() - event.detected_at
