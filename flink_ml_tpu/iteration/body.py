"""Iteration body contract: result type, listeners, epoch context.

Re-design of the reference's iteration API surface
(``IterationBody.java:54-98``, ``IterationBodyResult.java:28-76``,
``IterationListener.java:30-74``, ``IterationConfig.java:22-66``).

The body is a function, not a graph: ``body(state, epoch, data) ->
IterationBodyResult``.  ``state`` is the feedback-variable pytree — the
TPU-native feedback edge is simply that this pytree never leaves HBM between
epochs (donated jit buffers), replacing the reference's StateFun
FeedbackChannel + Tail/Head operators.
"""

from __future__ import annotations

import enum

from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax

__all__ = [
    "IterationBodyResult",
    "IterationListener",
    "EpochContext",
    "OperatorLifeCycle",
    "IterationConfig",
    "Workset",
    "active_fraction",
    "normalize_body_result",
    "with_program_key",
]


class OperatorLifeCycle(enum.Enum):
    """``IterationConfig.OperatorLifeCycle`` (``IterationConfig.java:22-66``):
    ALL_ROUND state is carried across epochs; PER_ROUND state is functionally
    re-initialised every epoch (the analog of the reference physically
    scrubbing per-round operator state,
    ``perround/AbstractPerRoundWrapperOperator.java:579-650``)."""

    ALL_ROUND = "all_round"
    PER_ROUND = "per_round"


@dataclass
class IterationConfig:
    """Mirror of ``IterationConfig.java`` extended with the TPU-native knobs
    the driver loop needs."""

    lifecycle: OperatorLifeCycle = OperatorLifeCycle.ALL_ROUND
    max_epochs: Optional[int] = None
    # "hosted": python epoch loop around a jitted step (listeners, streaming
    #           data, checkpoints). "fused": whole loop on device via
    #           lax.scan/while_loop (no per-epoch host round-trip at all).
    # "auto": fused when there are no listeners/checkpoints/streaming data.
    mode: str = "auto"
    jit: bool = True
    # Donate the state buffers to the jitted step so the feedback pytree is
    # updated in place in HBM (flat memory across epochs).
    donate_state: bool = True
    # Hosted-mode dispatch amortization: scan this many epochs per jit
    # dispatch (device-resident data only).  Listener callbacks,
    # termination-vote syncs, and checkpoint cuts move to CHUNK
    # boundaries; results stay bit-exact vs steps_per_dispatch=1 (a
    # terminated vote freezes the carried state inside the scan).  1 =
    # the classic one-dispatch-per-epoch loop.
    steps_per_dispatch: int = 1

    def __post_init__(self):
        if self.mode not in ("auto", "hosted", "fused"):
            raise ValueError(f"Unknown iteration mode {self.mode!r}")
        if self.steps_per_dispatch < 1:
            raise ValueError(
                f"steps_per_dispatch must be >= 1, got "
                f"{self.steps_per_dispatch}")


@dataclass
class Workset:
    """Device-resident active set riding the iteration carry — the delta-
    iteration workset of Ewen et al. (*Spinning Fast Iterative Data
    Flows*) rebuilt TPU-native: where the reference streams the changed
    elements through a feedback edge each superstep, here the workset is
    a mask over device-resident data that never leaves HBM.

    - ``mask``: per-element activity, float32 0/1 (or bool) arrays — a
      single array or a pytree of them (ALS masks users AND items).  An
      element with mask 0 is *provably settled this round*: the body must
      reuse its cached contribution instead of recomputing it.
    - ``bounds``: optional per-element bound state the body uses to decide
      settlement (Hamerly upper/lower distance bounds for KMeans, cached
      assignments, movement deltas, ...).  Rides the carry — and therefore
      chunk-boundary checkpoints — untouched by the driver.

    The driver terminates when :func:`active_fraction` falls to
    ``workset_tol`` (default exactly zero): an empty workset is the
    reference's empty-workset termination criterion
    (``SharedProgressAligner``'s zero-feedback-records rule applied to the
    delta iteration's solution-set updates).
    """

    mask: Any
    bounds: Any = None


def _workset_flatten(ws: Workset):
    return (ws.mask, ws.bounds), None


def _workset_unflatten(_, children):
    return Workset(*children)


jax.tree_util.register_pytree_node(Workset, _workset_flatten,
                                   _workset_unflatten)


def active_fraction(workset: Workset):
    """Global fraction of active elements, as a traced scalar: total mask
    mass over total element count across every mask leaf.  Under a jitted
    SPMD program with sharded masks XLA inserts the cross-device psum —
    every shard sees the same replicated scalar, so the while_loop exit
    decision is mesh-consistent by construction."""
    import jax.numpy as jnp

    leaves = jax.tree_util.tree_leaves(workset.mask)
    total = sum(x.size for x in leaves)
    if total == 0:
        return jnp.asarray(0.0, jnp.float32)
    act = sum(jnp.sum(x.astype(jnp.float32)) for x in leaves)
    return act / jnp.asarray(float(total), jnp.float32)


@dataclass
class IterationBodyResult:
    """(feedback, outputs, termination) — mirror of
    ``IterationBodyResult.java:28-76``.

    - ``feedback``: next-epoch variable state (pytree).
    - ``outputs``: per-epoch emission (pytree or None).
    - ``termination``: optional scalar vote. Truthy / nonzero means "records
      still flowing — continue"; the iteration terminates on a zero vote,
      mirroring the aligner's zero-feedback-records rule
      (``SharedProgressAligner.java:277-300``).
    """

    feedback: Any
    outputs: Any = None
    termination: Optional[Any] = None


def _result_flatten(res: IterationBodyResult):
    return (res.feedback, res.outputs, res.termination), None


def _result_unflatten(_, children):
    return IterationBodyResult(*children)


jax.tree_util.register_pytree_node(
    IterationBodyResult, _result_flatten, _result_unflatten)


def normalize_body_result(result: Any) -> IterationBodyResult:
    """Accept ``IterationBodyResult`` or a bare state pytree (which may
    itself be a tuple — bare returns are never unpacked: outputs/termination
    require the explicit result type, so a tuple-shaped state can't be
    silently misread as (feedback, outputs))."""
    if isinstance(result, IterationBodyResult):
        return result
    return IterationBodyResult(result)


def with_program_key(body: Callable, *facts) -> Callable:
    """``body``, stating its **program key** ``facts``: a hashable value
    that, together with the shapes of its arguments, determines the
    program a trace of it makes.  ``iterate``'s fused dispatch builds the
    program of a keyed body once a process and key and enqueues that
    executable for every later body of an equal key (``core.py:
    _dispatch_fused``); a body without one (every plain closure) is
    equal to nothing but itself and is traced, lowered and compiled
    again, which is always safe.

    The contract, the factory's to keep: the key names the factory and
    EVERY fact the trace reads from anywhere but the body's arguments:
    the Python values it bakes in, a registry lookup by its result, a
    module constant a test may patch, the mesh.  A fact left out is a
    stale program served silently.  And the key holds no array and
    nothing that holds one (a plan, a route, parameters): the entry
    outlives the ``fit()``.  An array in it is refused here (it does not
    hash); an object that holds one cannot be seen."""
    hash(facts)
    body.program_key = facts
    return body


@dataclass
class EpochContext:
    """Handed to listeners between epochs (hosted mode) — the analog of the
    ``IterationListener.Context`` + Collector pair."""

    epoch: int
    state: Any
    outputs: Any = None
    terminated: bool = False
    side: dict = field(default_factory=dict)

    def output(self, key: str, value: Any) -> None:
        """Side-output channel (the analog of ``ctx.output(OutputTag, v)``)."""
        self.side.setdefault(key, []).append(value)


class IterationListener:
    """Epoch-watermark callbacks (``IterationListener.java:30-74``).

    In hosted mode these fire on the host between jitted epoch steps — the
    exact analog of ``onEpochWatermarkIncremented`` firing after the
    superstep-alignment barrier (which, in SPMD, *is* the jitted step
    boundary)."""

    def on_epoch_watermark_incremented(self, epoch: int,
                                       context: EpochContext) -> None:
        pass

    def on_checkpoint_saved(self, epoch: int,
                            context: EpochContext) -> None:
        """Fires right after a checkpoint cut lands (hosted mode only —
        fused iterations cannot checkpoint mid-run).  THE hook the
        continuous-learning publish listener rides: at this point the
        (state, source cursor) pair is durable, so a publish of exactly
        this state composes with crash recovery into exactly-once —
        a crash after the cut re-publishes the same step idempotently
        (``online/publish.py``)."""
        pass

    def on_iteration_terminated(self, context: EpochContext) -> None:
        pass


class FnListener(IterationListener):
    """Adapter: wrap plain callables as a listener."""

    def __init__(self,
                 on_epoch: Optional[Callable[[int, EpochContext], None]] = None,
                 on_terminated: Optional[Callable[[EpochContext], None]] = None):
        self._on_epoch = on_epoch
        self._on_terminated = on_terminated

    def on_epoch_watermark_incremented(self, epoch, context):
        if self._on_epoch:
            self._on_epoch(epoch, context)

    def on_iteration_terminated(self, context):
        if self._on_terminated:
            self._on_terminated(context)
