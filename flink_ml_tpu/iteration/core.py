"""The iteration runtime: ``iterate`` — jitted SPMD epoch loops.

Re-design of ``Iterations.java:104-286`` + the whole operator/wrapper
machinery it drives.  80% of the reference's 16k-line iteration module exists
to retrofit cycles, BSP epoch alignment, per-round state and exactly-once
checkpointing onto an acyclic streaming engine (SURVEY §7).  On TPU none of
that machinery is needed:

- feedback edge      -> the state pytree stays in HBM (donated jit buffers),
                        replacing FeedbackChannel + Head/Tail operators
- epoch watermark    -> the jitted step boundary *is* the superstep barrier
                        (SPMD alignment is implicit), replacing
                        OperatorEpochWatermarkTracker + SharedProgressAligner
- termination vote   -> a device scalar reduced inside the step (psum over
                        the mesh), replacing SubtaskAlignedEvent/
                        GloballyAlignedEvent RPC
- replayed inputs    -> device-resident arrays are "replayed" for free each
                        epoch (they never left HBM), replacing ReplayOperator's
                        disk cache re-reads
- per-round state    -> functional re-initialisation per epoch, replacing
                        reflective state-backend scrubbing

Two execution modes:
- **fused**: the entire loop compiles to one XLA program (lax.scan or
  lax.while_loop) — zero host round-trips per epoch; listeners can't fire.
- **hosted**: python loop around a jitted step — per-epoch listener
  callbacks, streaming data sources, and checkpoint/resume.
"""

from __future__ import annotations

import dataclasses
import threading

from collections import OrderedDict
from dataclasses import dataclass
from functools import partial
from typing import (Any, Callable, Iterator, NamedTuple, Optional, Sequence,
                    Union)

import jax
import jax.numpy as jnp
import numpy as np

from .body import (
    EpochContext,
    IterationBodyResult,
    IterationConfig,
    IterationListener,
    OperatorLifeCycle,
    Workset,
    active_fraction,
    normalize_body_result,
)
from ..obs.trace import tracer
from ..utils.backend import cache_hit_count
from .checkpoint import CheckpointConfig, CheckpointManager

# what JAX itself keys a trace by: every configuration value a trace
# depends on and a caller can change (x64, the default matmul precision,
# dtype promotion, the mesh and axis context, ...)
from jax._src.config import trace_context as _trace_context

__all__ = ["iterate", "IterationResult", "clear_programs"]

BodyFn = Callable[..., Any]


@dataclass
class IterationResult:
    """Final state + collected outputs (the analog of the iteration's output
    streams after ``OutputOperator`` unwrapping).

    ``workset`` is the final :class:`Workset` of a workset iteration (None
    otherwise).  ``side["epoch_trace"]`` (criteria-driven fused loops and
    per-epoch hosted loops) holds the per-epoch convergence curves —
    ``{"active_fraction": (num_epochs,), "termination": (num_epochs,)}``
    host arrays — that would otherwise die inside the fused while_loop."""

    state: Any
    outputs: Any
    num_epochs: int
    side: dict
    workset: Any = None


def _private_copy(state: Any) -> Any:
    """Copy the caller's state pytree before the loop donates its buffers —
    donation must consume *our* copy, never arrays the caller still holds."""
    return jax.tree_util.tree_map(
        lambda x: x.copy() if isinstance(x, jax.Array) else jnp.asarray(x),
        state)


def _vote_continue(vote: Any) -> bool:
    """Reference rule: continue while the criteria stream is non-empty /
    feedback record count nonzero (``SharedProgressAligner.java:277-300``)."""
    return bool(jax.device_get(vote))


class HandedOver:
    """Marks an initial state whose buffers the caller gives up: a
    donating loop consumes them as they are, without the private copy
    that otherwise protects arrays the caller still holds.  For a state
    that fills a large share of the device (a table with its optimizer
    state), where a second copy would not fit beside the first.  The
    caller must not touch the arrays again."""

    def __init__(self, state: Any):
        self.state = state


class Replayed:
    """Marks a bounded input replayed identically every epoch (the analog of
    ``ReplayableDataStreamList.replay(...)``).  On TPU a replayed input is
    simply device-resident — replay costs nothing."""

    def __init__(self, value: Any):
        self.value = value


class PerEpoch:
    """Marks a per-epoch source: a callable ``f(epoch) -> pytree`` or an
    iterable consumed one item per epoch (the analog of
    ``ReplayableDataStreamList.notReplay(...)`` / an unbounded stream).
    Exhaustion of any PerEpoch iterator ends the iteration."""

    def __init__(self, source: Any):
        self.source = source


class _Feed:
    """One normalized leaf source."""

    def __init__(self, raw: Any):
        self.static = None
        self.fn = None
        self.it: Optional[Iterator] = None
        if callable(raw):
            self.fn = raw
        elif hasattr(raw, "__next__"):
            self.it = raw
        elif hasattr(raw, "__iter__") and not isinstance(raw, (dict, tuple,
                                                              list, str)):
            # keep the original object reachable for snapshot/restore
            self.source = raw
            self.it = iter(raw)
        else:
            self.static = raw
        if not hasattr(self, "source"):
            self.source = raw


class _DataProvider:
    """Adapts the ``data`` argument to a per-epoch feed.

    - None                  -> body gets data=None every epoch
    - pytree of arrays      -> replayed: same device-resident pytree each epoch
    - callable / iterator   -> per-epoch source (exhaustion = stream end)
    - Replayed(x)/PerEpoch(s) markers, possibly MIXED one level deep inside a
      dict/tuple/list — the ``ReplayableDataStreamList`` analog: e.g.
      ``{"train": Replayed(points), "stream": PerEpoch(reader)}``
    """

    def __init__(self, data: Any):
        self.exhausted = False
        self._container: Optional[type] = None
        self._keys = None
        self._feeds = None
        self._single: Optional[_Feed] = None

        data = self._unwrap(data)
        if isinstance(data, _Feed):
            self._single = data
            return
        if isinstance(data, dict) and any(
                isinstance(v, (Replayed, PerEpoch)) for v in data.values()):
            self._container = dict
            self._keys = list(data.keys())
            self._feeds = [self._unwrap(data[k], force=True)
                           for k in self._keys]
            return
        if isinstance(data, (tuple, list)) and any(
                isinstance(v, (Replayed, PerEpoch)) for v in data):
            self._container = type(data)
            self._feeds = [self._unwrap(v, force=True) for v in data]
            return
        # plain pytree (or None): replayed static data
        self._single = _Feed(None)
        self._single.static = data
        self._single.source = data

    @staticmethod
    def _unwrap(value: Any, force: bool = False):
        if isinstance(value, Replayed):
            feed = _Feed(None)
            feed.static = value.value
            feed.source = value.value
            return feed
        if isinstance(value, PerEpoch):
            return _Feed(value.source)
        if force:
            feed = _Feed(None)
            feed.static = value
            feed.source = value
            return feed
        if value is None or isinstance(value, (dict, tuple, list)) \
                or hasattr(value, "shape"):
            return value
        return _Feed(value)

    def _all_feeds(self):
        if self._single is not None:
            return [self._single]
        return self._feeds

    @property
    def is_static(self) -> bool:
        return all(f.fn is None and f.it is None for f in self._all_feeds())

    def _pull(self, feed: _Feed, epoch: int) -> Any:
        if feed.it is not None:
            try:
                return next(feed.it)
            except StopIteration:
                self.exhausted = True
                return None
        if feed.fn is not None:
            return feed.fn(epoch)
        return feed.static

    def __call__(self, epoch: int) -> Any:
        if self._single is not None:
            return self._pull(self._single, epoch)
        values = [self._pull(f, epoch) for f in self._feeds]
        if self.exhausted:
            return None
        if self._container is dict:
            return dict(zip(self._keys, values))
        return self._container(values)

    def snapshot(self) -> Optional[dict]:
        # Single-feed caches keep the source's raw snapshot format (what
        # checkpoints have always stored); multi-feed providers wrap the
        # per-feed snapshots in an index-keyed envelope.
        feeds = self._all_feeds()
        if self._single is not None:
            src = self._single.source
            live = self._single.fn is not None or self._single.it is not None
            return src.snapshot() if live and hasattr(src, "snapshot") else None
        snaps = {}
        for i, feed in enumerate(feeds):
            live = feed.fn is not None or feed.it is not None
            if live and hasattr(feed.source, "snapshot"):
                snaps[str(i)] = feed.source.snapshot()
        return {"__feeds__": snaps} if snaps else None

    def restore(self, snap: dict) -> None:
        if "__feeds__" in snap:
            for i, feed in enumerate(self._all_feeds()):
                sub = snap["__feeds__"].get(str(i))
                if sub is not None and hasattr(feed.source, "restore"):
                    feed.source.restore(sub)
            return
        # raw single-source snapshot (incl. checkpoints from older runs)
        if self._single is not None and hasattr(self._single.source, "restore"):
            self._single.source.restore(snap)


def _call_body(body: BodyFn, state, epoch, data) -> IterationBodyResult:
    if data is None:
        return normalize_body_result(body(state, epoch))
    return normalize_body_result(body(state, epoch, data))


def iterate(
    body: BodyFn,
    initial_state: Any,
    data: Any = None,
    *,
    config: Optional[IterationConfig] = None,
    max_epochs: Optional[int] = None,
    steps_per_dispatch: Optional[int] = None,
    listeners: Sequence[IterationListener] = (),
    per_round_init: Optional[Callable[[], Any]] = None,
    per_round: Optional[Sequence[str]] = None,
    workset: Optional[Workset] = None,
    workset_tol: float = 0.0,
    checkpoint: Optional[Union[CheckpointConfig, CheckpointManager]] = None,
    resume: bool = False,
) -> IterationResult:
    """Run an iteration (the analog of
    ``Iterations.iterateBoundedStreamsUntilTermination``,
    ``Iterations.java:149-170``).

    ``body(state, epoch[, data]) -> IterationBodyResult | state |
    (state[, outputs[, termination]])``.  Epoch semantics mirror
    ``Iterations.java:69-83``: state entering epoch ``e`` produces the state
    for epoch ``e+1`` (the feedback edge increments the epoch).

    **Mixed lifecycle** (``per_round=``): the ``IterationBody.forEachRound``
    analog (``IterationBody.java:73-91``) — name top-level keys of a dict
    state that are re-initialised from ``initial_state`` at the start of
    every epoch while the rest of the state is carried.  Where the reference
    builds a per-round sub-graph whose operators are recreated and scrubbed
    each round (``BoundedMixedLifeCycleStreamIterationITCase``), here the
    named subtree simply re-enters each epoch at its initial value — the
    final result keeps the LAST round's values (what ``forEachRound``'s
    output forwarding yields).  Works in both fused and hosted modes.

    Termination: ``max_epochs`` reached, OR the body's ``termination`` vote
    is zero/false, OR an iterator data source is exhausted, OR — workset
    iterations — the active fraction falls to ``workset_tol``.

    **Workset iterations** (``workset=``): pass the initial
    :class:`Workset` (device-resident active-set mask + optional
    per-element bound state) and a body with the extended signature
    ``body(state, workset, epoch[, data]) ->`` result whose feedback is
    ``(new_state, new_workset)``.  The mask/bounds pytree rides the
    ``lax.scan``/``lax.while_loop`` carry with the state — in hosted mode
    it also rides chunk-boundary checkpoints (GR_STATE_KEY-style), so
    ``resilient_fit`` crash-resume restores mask, bounds, AND the rounds
    run bit-exactly.  The driver terminates when
    :func:`~.body.active_fraction` drops to ``workset_tol`` (default:
    exactly zero — the reference's empty-workset criterion), AND-ed with
    any explicit body vote.  Incompatible with ``per_round=`` and the
    PER_ROUND lifecycle (those re-init state each round; a workset is
    cross-round by definition).

    ``steps_per_dispatch=W`` (hosted mode, device-resident data): scan
    ``W`` epochs per jit dispatch — one host round-trip (and one
    termination-vote sync) per ``W`` epochs instead of per epoch.
    Listener callbacks and checkpoint cuts fire at chunk boundaries
    (``on_epoch_watermark_incremented`` once per chunk, with the last
    completed epoch's context).  Bit-exact vs ``W=1``: a mid-chunk
    termination vote freezes the carried state for the rest of the
    chunk, so the returned state is the voting epoch's feedback exactly
    as in the per-epoch loop.  Ignored (with per-epoch stepping) for
    per-epoch data sources, unjitted bodies, and PER_ROUND lifecycles.

    ``initial_state`` wrapped in :class:`HandedOver` is donated as it
    is: the caller gives its buffers up, and the loop makes no private
    copy of them first.
    """
    handed_over = isinstance(initial_state, HandedOver)
    if handed_over:
        initial_state = initial_state.state
    config = config or IterationConfig()
    if max_epochs is not None:
        config = dataclasses.replace(config, max_epochs=max_epochs)
    if steps_per_dispatch is not None:
        config = dataclasses.replace(config,
                                     steps_per_dispatch=steps_per_dispatch)

    if per_round:
        if not isinstance(initial_state, dict):
            raise TypeError(
                "per_round= names top-level dict keys; state is "
                f"{type(initial_state).__name__}")
        missing = [k for k in per_round if k not in initial_state]
        if missing:
            raise KeyError(f"per_round keys {missing} not in state "
                           f"{list(initial_state)}")
        reset_subtree = {k: _private_copy(initial_state[k])
                        for k in per_round}
        inner_body = body

        def body(state, epoch, *rest):  # noqa: F811
            # Re-entering each epoch at the initial value IS the per-round
            # re-init; at epoch 0 this is a no-op by construction.
            return _call_body(inner_body, {**state, **reset_subtree},
                              epoch, rest[0] if rest else None)

    frac_fn = None
    if workset is not None:
        if not isinstance(workset, Workset):
            raise TypeError(
                f"workset= expects a Workset, got {type(workset).__name__}")
        if per_round or config.lifecycle == OperatorLifeCycle.PER_ROUND:
            raise ValueError(
                "workset iterations are incompatible with per-round "
                "re-initialisation (the workset is cross-round state)")
        ws_body, ws_tol = body, float(workset_tol)

        def body(carry, epoch, *rest):  # noqa: F811
            # The workset rides the carry NEXT TO the user state; the
            # continue-vote is "records still flowing" = active elements
            # remain, AND-ed with any explicit body vote.
            state, ws = carry
            res = normalize_body_result(
                ws_body(state, ws, epoch, *rest) if rest
                else ws_body(state, ws, epoch))
            new_state, new_ws = res.feedback
            cont = active_fraction(new_ws) > ws_tol
            if res.termination is not None:
                cont = jnp.logical_and(
                    cont,
                    jnp.asarray(res.termination).astype(bool).reshape(()))
            return IterationBodyResult((new_state, new_ws), res.outputs,
                                       cont)

        initial_state = (initial_state, workset)
        frac_fn = lambda carry: active_fraction(carry[1])  # noqa: E731

    provider = _DataProvider(data)
    # NOTE: distinct from the per_round= KEY LIST above — this is the
    # whole-state PER_ROUND lifecycle flag from IterationConfig.
    per_round_lifecycle = config.lifecycle == OperatorLifeCycle.PER_ROUND
    if per_round_lifecycle and per_round_init is None:
        # Default per-round re-init: restart every epoch from initial_state.
        init_copy = initial_state
        per_round_init = lambda: init_copy  # noqa: E731

    mode = config.mode
    if mode == "auto":
        fusible = (provider.is_static and not listeners and checkpoint is None
                   and not per_round_lifecycle and config.jit
                   and config.max_epochs is not None)
        if fusible:
            # Criteria-driven fused loops keep only the LAST epoch's outputs
            # (a while_loop can't stack a dynamic number of them) — auto must
            # not silently change output semantics, so probe for a vote and
            # fall back to hosted when one exists.  Explicit mode="fused"
            # opts into last-output semantics.  A workset iteration always
            # votes (the active-fraction criterion), so it stays fusible
            # whenever the body emits NO outputs — then there are no output
            # semantics to lose and the fused while_loop (plus its epoch
            # trace) is the point of the feature.
            # A piece of ``iterate.dispatch`` of its own, in front of the
            # one ``_iterate_fused`` or nothing (the hosted loop) follows
            # with.
            with tracer.span("iterate.dispatch", "fit"), \
                    tracer.span("iterate.dispatch.probe", "fit"):
                probe = jax.eval_shape(
                    lambda s, e: _call_body(body, s, e, provider(0)),
                    initial_state, jax.ShapeDtypeStruct((), jnp.int32))
            fusible = (probe.termination is None
                       or (workset is not None and probe.outputs is None))
        mode = "fused" if fusible else "hosted"

    if mode == "fused":
        result = _iterate_fused(body, initial_state, provider, config,
                                frac_fn=frac_fn, handed_over=handed_over)
    else:
        result = _iterate_hosted(body, initial_state, provider, config,
                                 listeners, per_round_lifecycle,
                                 per_round_init, checkpoint, resume,
                                 frac_fn=frac_fn, handed_over=handed_over)
    if workset is not None:
        final_state, final_ws = result.state
        result = dataclasses.replace(result, state=final_state,
                                     workset=final_ws)
    return result


# ---------------------------------------------------------------------------
# fused: whole loop is one XLA program
# ---------------------------------------------------------------------------

def _iterate_fused(body: BodyFn, initial_state, provider: _DataProvider,
                   config: IterationConfig, *,
                   frac_fn: Optional[Callable[[Any], Any]] = None,
                   handed_over: bool = False) -> IterationResult:
    # ``iterate.dispatch``: what the host does to get the fused program
    # running, its five stages flat children of it (``.probe`` and
    # ``.enqueue`` in ``_dispatch_fused``, ``.trace``, ``.lower`` and
    # ``.compile`` in ``_compile_staged``, or in ``_reuse_staged`` around
    # nothing); it ends when the compiled call returns, not when the
    # device has finished.  ``fit.fetch``: the host blocked on the device.
    with tracer.span("iterate.dispatch", "fit"):
        final_state, outputs, num_epochs, trace = _in_one_chunk(
            _dispatch_fused, body, initial_state, provider, config, frac_fn,
            handed_over)
    if num_epochs is None:
        return IterationResult(final_state, outputs, config.max_epochs, {})
    # on a process-spanning mesh the loop counter comes back as a
    # non-fully-addressable replicated scalar; read this host's replica
    from ..parallel.mesh import fetch_replicated

    with tracer.span("fit.fetch", "fit"):
        n_run = int(np.asarray(fetch_replicated(num_epochs)))
        side = {"epoch_trace": trace.fetch(
            get=lambda v: np.asarray(fetch_replicated(v)))}
    return IterationResult(final_state, outputs, n_run, side)


def _in_one_chunk(fn, *args):
    """``fn(*args)``, the interpreter's frames for it in one piece.

    CPython (3.11 on) keeps a thread's Python frames in chunks of 16 KiB
    and unmaps a chunk the moment its first frame returns, so a call
    sequence that straddles a chunk's end maps and unmaps 16 KiB a call
    (a loop of plain calls runs 150 times slower there).  Tracing and
    lowering recurse hundreds of frames deep, through several such ends,
    and which of their loops straddle one follows from the depth of
    whoever called ``fit``: the same lowering of ALS's program took 0.5 to
    4.4 s on the chip's host over five depths of the caller, and one frame
    more or less in this module moved it by a second (PERF.md section 6,
    PR 37).  A frame that declares 2**16 stack slots fits no such chunk:
    the interpreter maps one of 1 MiB for it, and whatever it calls runs
    in that chunk's other half, no end in reach.  About 10 microseconds a
    call."""
    return fn(*args)


_in_one_chunk.__code__ = _in_one_chunk.__code__.replace(co_stacksize=1 << 16)


class _Program(NamedTuple):
    """What the five stages made of one program key."""

    compiled: Any        # the executable of ``run`` (``jax.stages.Compiled``)
    has_criteria: bool   # the probe: the body votes (a while_loop, no scan)
    drops_outputs: bool  # the probe: a voting body emits (the last is kept)


#: Fused programs a process keeps, least recently used out.
_PROGRAMS_KEPT = 8
_programs: "OrderedDict[tuple, _Program]" = OrderedDict()
_programs_lock = threading.Lock()


def clear_programs() -> None:
    """Forget every fused program ``iterate`` has kept for a keyed body
    (:func:`~.body.with_program_key`): the next dispatch of each builds
    it again.  For tests, and for a caller who changes something a body's
    program reads that its key does not name (a kernel swapped in
    place)."""
    with _programs_lock:
        _programs.clear()


def _kept_program(key: Optional[tuple]) -> Optional[_Program]:
    if key is None:
        return None
    with _programs_lock:
        program = _programs.get(key)
        if program is not None:
            _programs.move_to_end(key)
        return program


def _keep_program(key: Optional[tuple], program: _Program) -> None:
    # two threads that built one key both store: the entries are equal in
    # everything but identity and whole either way, the later one stays.
    # Room is made before the entry goes in, so that a reader outside the
    # lock never counts more than the bound
    if key is None:
        return
    with _programs_lock:
        _programs.pop(key, None)
        while len(_programs) >= _PROGRAMS_KEPT:
            _programs.popitem(last=False)
        _programs[key] = program


def _leaf_key(x) -> tuple:
    """What a jitted call keys one argument by: its abstract value
    (shape, dtype, weak type) and, of an array that lies somewhere, where
    and whether it is committed there."""
    return (jax.typeof(x), getattr(x, "sharding", None),
            getattr(x, "committed", None))


def _program_key(body: BodyFn, state, data, config: IterationConfig,
                 frac_fn) -> Optional[tuple]:
    """Everything the fused program of ``body`` follows from, known before
    any of it is traced, or ``None`` for a body that states no key of its
    own (:func:`~.body.with_program_key`): such a body is equal to
    nothing but itself and is built anew, as ever."""
    stated = getattr(body, "program_key", None)
    if stated is None:
        return None
    leaves, structure = jax.tree_util.tree_flatten((state, data))
    return (stated, config.max_epochs, config.donate_state,
            frac_fn is not None, structure,
            tuple(_leaf_key(x) for x in leaves), _trace_context())


def _dispatch_fused(body: BodyFn, initial_state, provider: _DataProvider,
                    config: IterationConfig,
                    frac_fn: Optional[Callable[[Any], Any]],
                    handed_over: bool = False) -> tuple:
    """Build the fused program, or take the one this process has built
    for the same program key, and enqueue it, nothing fetched:
    ``(final_state, outputs, num_epochs, epoch_trace)``, the last two
    ``None`` where no criteria ask for them.

    A body that states a program key is built once a process and key
    (:func:`_program_key`): every later dispatch asks ``_programs`` in
    the probe's span and goes to the enqueue; the three stages between
    open their spans around nothing, and the compile's notes ``reused``
    1.  A body that states none takes the five stages every time."""
    if not provider.is_static:
        raise ValueError("fused mode requires device-resident (static) data")
    if config.max_epochs is None:
        raise ValueError("fused mode requires max_epochs")
    if config.donate_state and not handed_over:
        initial_state = _private_copy(initial_state)
    data = provider(0)
    key = _program_key(body, initial_state, data, config, frac_fn)

    with tracer.span("iterate.dispatch.probe", "fit"):
        program = _kept_program(key)
        if program is None:
            # Probe the body's output structure without running it.
            probe = jax.eval_shape(
                lambda s, e: _call_body(body, s, e, data),
                initial_state, jax.ShapeDtypeStruct((), jnp.int32))
    if program is not None:
        _reuse_staged()
    else:
        has_criteria = probe.termination is not None
        run = (_while_loop(body, config, frac_fn, probe.outputs)
               if has_criteria else _scan_loop(body, config))
        program = _Program(
            _compile_staged(run, initial_state, data), has_criteria,
            has_criteria and probe.outputs is not None)
        _keep_program(key, program)

    if program.drops_outputs:
        import warnings

        warnings.warn(
            "fused iteration with a termination criterion keeps only the "
            "LAST epoch's outputs (a while_loop cannot stack a dynamic "
            "number of them); use mode='hosted' (or carry a fixed-size "
            "buffer in state) to keep the full per-epoch output log",
            stacklevel=4)
    with tracer.span("iterate.dispatch.enqueue", "fit"):
        out = program.compiled(initial_state, data)
    if not program.has_criteria:
        return (*out, None, None)
    final_state, outputs, num_epochs, _, trace = out
    return final_state, outputs, num_epochs, trace


def _scan_loop(body: BodyFn, config: IterationConfig):
    """Fixed epoch count: lax.scan stacks per-epoch outputs."""
    max_epochs = config.max_epochs

    @partial(jax.jit, donate_argnums=(0,) if config.donate_state else ())
    def run(state, data):
        def scan_step(state, epoch):
            res = _call_body(body, state, epoch, data)
            return res.feedback, res.outputs

        return jax.lax.scan(scan_step, state,
                            jnp.arange(max_epochs, dtype=jnp.int32))

    return run


def _while_loop(body: BodyFn, config: IterationConfig, frac_fn,
                outputs_shape):
    """Criteria-driven: lax.while_loop; keeps only the last outputs (of
    the probe's ``outputs_shape``)."""
    max_epochs = config.max_epochs
    zero_out = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), outputs_shape)

    # Per-epoch convergence curves survive the fused loop in a
    # fixed-size NaN-prefilled StepProbe riding the carry (obs/probe.py
    # — the generalization of the sgd.py loss-log pattern this loop used
    # to hand-roll): a while_loop keeps only its final carry, so
    # anything per-epoch must be indexed into a (max_epochs,) buffer on
    # device.  NaN tail = epochs never run; the probe cursor tracks
    # rounds actually recorded.
    from ..obs.probe import StepProbe

    trace0 = StepProbe.create(("active_fraction", "termination"),
                              max_epochs)

    @partial(jax.jit, donate_argnums=(0,) if config.donate_state else ())
    def run(state, data):
        def cond(carry):
            _, _, epoch, keep_going, _ = carry
            return jnp.logical_and(keep_going, epoch < max_epochs)

        def step(carry):
            state, _, epoch, _, trace = carry
            res = _call_body(body, state, epoch, data)
            vote = jnp.asarray(res.termination)
            keep_going = vote.astype(bool).reshape(())
            frac = (frac_fn(res.feedback) if frac_fn is not None
                    else jnp.asarray(jnp.nan, jnp.float32))
            trace = trace.record_at(
                epoch, active_fraction=frac,
                termination=vote.astype(jnp.float32).reshape(()))
            return res.feedback, res.outputs, epoch + 1, keep_going, trace

        return jax.lax.while_loop(
            cond, step, (state, zero_out, jnp.asarray(0, jnp.int32),
                         jnp.asarray(True), trace0))

    return run


def _compile_staged(run, state, data):
    """The executable of the jitted ``run`` for ``(state, data)`` by the
    stages JAX itself makes of such a call, each under a span: the same
    trace, module, cache key, executable and donation as ``run(state,
    data)``.  The span ``iterate.dispatch.compile`` notes ``cache_hit``
    (1 where XLA compiled nothing for this fit's program: here, where the
    persistent compile cache served the request; 0 where XLA compiled)
    and ``reused`` 0: the stages ran."""
    with tracer.span("iterate.dispatch.trace", "fit"):
        traced = run.trace(state, data)
    with tracer.span("iterate.dispatch.lower", "fit"):
        lowered = traced.lower()
    with tracer.span("iterate.dispatch.compile", "fit") as span:
        hits = cache_hit_count()
        compiled = lowered.compile()
        span.note(cache_hit=cache_hit_count() - hits, reused=0)
    return compiled


def _reuse_staged() -> None:
    """The same three spans around nothing, for a fit whose program this
    process had kept: every fit's dispatch has its five stages, and the
    compile's notes say where the executable came from (``reused`` 1: the
    process's own entry; ``cache_hit`` 1: XLA compiled nothing)."""
    for stage in ("trace", "lower"):
        with tracer.span(f"iterate.dispatch.{stage}", "fit"):
            pass
    with tracer.span("iterate.dispatch.compile", "fit") as span:
        span.note(cache_hit=1, reused=1)


# ---------------------------------------------------------------------------
# hosted: python epoch loop around a jitted step
# ---------------------------------------------------------------------------

def _iterate_hosted(body: BodyFn, initial_state, provider: _DataProvider,
                    config: IterationConfig,
                    listeners: Sequence[IterationListener],
                    per_round_lifecycle: bool, per_round_init,
                    checkpoint, resume: bool, *,
                    frac_fn: Optional[Callable[[Any], Any]] = None,
                    handed_over: bool = False) -> IterationResult:
    donating = (config.jit and config.donate_state
                and not per_round_lifecycle)
    if config.jit:
        # Donating the state argument keeps HBM flat across epochs: the new
        # feedback pytree reuses the old buffers (the in-place feedback edge).
        step = jax.jit(
            lambda s, e, d: _call_body(body, s, e, d),
            donate_argnums=(0,) if donating else ())
    else:
        step = lambda s, e, d: _call_body(body, s, e, d)  # noqa: E731

    # Chunked dispatch (steps_per_dispatch=W > 1): one jitted lax.scan
    # runs W epochs per host round-trip — per-epoch data sources can't
    # chunk (the host pulls between epochs), and unjitted/per-round
    # bodies keep the classic loop.
    W = config.steps_per_dispatch
    chunked = (W > 1 and config.jit and provider.is_static
               and not per_round_lifecycle)
    if chunked:
        @partial(jax.jit, static_argnums=(3,),
                 donate_argnums=(0,) if donating else ())
        def chunk_step(state, e0, data, w: int):
            def scan_step(carry, epoch):
                state, alive = carry
                res = _call_body(body, state, epoch, data)
                # a dead step (post-vote) freezes the carry, so the
                # returned state is the VOTING epoch's feedback — the
                # exact per-epoch-loop semantics
                new_state = jax.tree_util.tree_map(
                    lambda n, o: jnp.where(alive, n, o),
                    res.feedback, state)
                vote = (jnp.asarray(res.termination)
                        .astype(bool).reshape(())
                        if res.termination is not None
                        else jnp.asarray(True))
                return ((new_state, jnp.logical_and(alive, vote)),
                        (res.outputs, alive))
            (state, alive), (outs, ran) = jax.lax.scan(
                scan_step, (state, jnp.asarray(True)),
                e0 + jnp.arange(w, dtype=jnp.int32))
            return state, alive, outs, ran

    manager: Optional[CheckpointManager] = None
    if isinstance(checkpoint, CheckpointManager):
        manager = checkpoint
    elif isinstance(checkpoint, CheckpointConfig):
        manager = CheckpointManager(checkpoint)

    # Does any listener actually consume the checkpoint hook?  Only then
    # must an async save land before the hook fires (its contract is
    # durability); listeners that never override it keep the full
    # async-save overlap.
    wants_ckpt_hook = any(
        type(lst).on_checkpoint_saved
        is not IterationListener.on_checkpoint_saved
        for lst in listeners)

    state = (_private_copy(initial_state) if donating and not handed_over
             else initial_state)
    start_epoch = 0
    resumed_terminated = False
    if manager is not None and resume:
        restored = manager.restore_latest()
        if restored is not None:
            start_epoch, state, meta = restored
            resumed_terminated = bool(meta.get("terminated"))
            snap = meta.get("source_snapshot")
            if snap:
                provider.restore(snap)
    if resumed_terminated:
        # The checkpointed run had already voted to terminate at this epoch:
        # re-running the body would diverge from the uninterrupted run.
        ctx = EpochContext(epoch=start_epoch, state=state, terminated=True)
        for listener in listeners:
            listener.on_iteration_terminated(ctx)
        return IterationResult(state, [], start_epoch,
                               {"termination_reason": "criteria"})

    outputs_log = []
    side: dict = {}
    # Per-epoch convergence curves (per-epoch stepping only): device
    # scalars collected WITHOUT syncing — one batched fetch at the end.
    # Covers the epochs run in THIS call (a resumed run's earlier curve
    # lives with the earlier call).
    trace_frac: list = []
    trace_term: list = []
    epoch = start_epoch
    terminated_reason = "max_epochs"
    from ..robustness.faults import fault_point

    try:
        while config.max_epochs is None or epoch < config.max_epochs:
            # fault seam: lets the chaos suite kill a hosted iteration
            # mid-run at a chosen epoch even when the data is static
            # (stream sources are instead wrapped at the pull —
            # robustness.FaultPlan.wrap_source)
            fault_point("iterate.epoch")
            epoch_data = provider(epoch)
            if provider.exhausted:
                terminated_reason = "stream_end"
                break
            if chunked:
                from ..parallel.mesh import fetch_replicated

                w = (W if config.max_epochs is None
                     else min(W, config.max_epochs - epoch))
                state, alive, outs, ran = chunk_step(
                    state, jnp.asarray(epoch, jnp.int32), epoch_data, w)
                # ONE host sync per chunk: which scan steps ran, and
                # whether the vote says continue
                ran_h = np.asarray(fetch_replicated(ran)).astype(bool)
                alive_h = bool(np.asarray(fetch_replicated(alive)))
                n_run = int(ran_h.sum())
                last_outputs = None
                if outs is not None:
                    for i in range(w):
                        if ran_h[i]:
                            last_outputs = jax.tree_util.tree_map(
                                lambda x, i=i: x[i], outs)
                            outputs_log.append(last_outputs)
                epoch += n_run
                ctx = EpochContext(epoch=epoch - 1, state=state,
                                   outputs=last_outputs, side=side)
                for listener in listeners:
                    listener.on_epoch_watermark_incremented(epoch - 1, ctx)
                stop = not alive_h
                if manager is not None and (
                        stop or any(manager.should_save(e) for e in
                                    range(epoch - n_run + 1, epoch + 1))):
                    extra = {"terminated": stop}
                    snap = provider.snapshot()
                    if snap:
                        extra["source_snapshot"] = snap
                    if getattr(manager.config, "async_save", False):
                        to_save = (_private_copy(state) if donating
                                   else state)
                        manager.save_async(epoch, to_save, extra)
                        if wants_ckpt_hook:
                            manager.wait()   # hook promises durability
                    else:
                        manager.save(epoch, state, extra)
                    if wants_ckpt_hook:
                        for listener in listeners:
                            listener.on_checkpoint_saved(epoch - 1, ctx)
                if stop:
                    terminated_reason = "criteria"
                    break
                continue
            if per_round_lifecycle and epoch > start_epoch:
                state = per_round_init()
            res = step(state, jnp.asarray(epoch, jnp.int32), epoch_data)
            state = res.feedback
            if res.outputs is not None:
                outputs_log.append(res.outputs)
            if frac_fn is not None:
                # Eager tiny op on the fresh feedback buffers — dispatched
                # before the next donating step call, so donation can't
                # invalidate what it reads; no host sync here.
                trace_frac.append(frac_fn(state))
                trace_term.append(res.termination)

            ctx = EpochContext(epoch=epoch, state=state, outputs=res.outputs,
                               side=side)
            for listener in listeners:
                listener.on_epoch_watermark_incremented(epoch, ctx)

            epoch += 1
            stop = (res.termination is not None
                    and not _vote_continue(res.termination))
            if manager is not None and (manager.should_save(epoch) or stop):
                # The vote travels with the checkpoint: resuming from a
                # checkpoint of a terminated run must not re-run the body.
                extra = {"terminated": stop}
                snap = provider.snapshot()
                if snap:
                    extra["source_snapshot"] = snap
                if getattr(manager.config, "async_save", False):
                    # Only copy when the loop donates the live buffers the
                    # background thread would otherwise read.
                    to_save = _private_copy(state) if donating else state
                    manager.save_async(epoch, to_save, extra)
                    if wants_ckpt_hook:
                        manager.wait()   # hook promises durability
                else:
                    manager.save(epoch, state, extra)
                if wants_ckpt_hook:
                    for listener in listeners:
                        listener.on_checkpoint_saved(epoch - 1, ctx)
            if stop:
                terminated_reason = "criteria"
                break
    except BaseException:
        # Land any in-flight async save so the newest checkpoint isn't torn
        # by interpreter exit; swallow its error — the loop's own exception
        # is the one the caller must see.
        if manager is not None:
            try:
                manager.wait()
            except Exception:
                pass
        raise

    if manager is not None:
        manager.wait()  # land any in-flight async save before returning

    final_ctx = EpochContext(epoch=epoch, state=state, terminated=True,
                             side=side)
    for listener in listeners:
        listener.on_iteration_terminated(final_ctx)

    side["termination_reason"] = terminated_reason
    if trace_frac:
        side["epoch_trace"] = {
            "active_fraction": np.asarray(
                jax.device_get(trace_frac), np.float32),
            "termination": np.asarray(
                jax.device_get(trace_term), np.float32),
        }
    return IterationResult(state, outputs_log, epoch, side)
