"""Fused mini-batch SGD trainer over a device mesh.

The TPU-native replacement for the reference's iteration-based model update
path: where flink-ml ships gradients over the network to a reduce operator
and feeds new weights back through the FeedbackChannel, here one epoch is an
inner ``lax.scan`` over mini-batches — the gradient psum over the mesh's data
axis is inserted by XLA and rides ICI — and the whole multi-epoch loop is a
single compiled program via ``iterate`` (fused mode).

Data layout: inputs are host-shuffled once (seeded), padded, and reshaped to
``(steps_per_epoch, batch, ...)`` with the batch dim sharded over the data
axis; weights/optimizer state are replicated.  Shapes are static — no
recompiles across epochs or batch positions.
"""

from __future__ import annotations

import functools
import inspect
import itertools
import time

from collections import OrderedDict
from dataclasses import astuple, dataclass, is_dataclass
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ...data.prefetch import prefetch_to_device
from ...data.replay_cache import (
    DecodedReplayCache,
    batch_fingerprint,
    default_ram_budget,
)
from ...iteration import (
    IterationBodyResult,
    IterationConfig,
    iterate,
    with_program_key,
)
from ...iteration.checkpoint import CheckpointConfig, CheckpointManager
from ...obs.trace import tracer
from ...parallel.mesh import (
    default_mesh,
    assemble_process_local as _assemble_process_local,
    fetch_replicated as _fetch_replicated,
    mesh_process_count as _mesh_process_count,
    put_in_rounds,
    put_sharded as _put_epoch_tensor,
    replicate,
)

__all__ = ["SGDConfig", "sgd_fit", "sgd_fit_params", "sgd_fit_sparse",
           "sgd_fit_mixed", "sgd_fit_outofcore", "LinearState",
           "plan_epoch_layout", "prepare_epoch_tensor"]

LossFn = Callable[[jnp.ndarray, jnp.ndarray, jnp.ndarray], jnp.ndarray]


@dataclass
class SGDConfig:
    learning_rate: float = 0.1
    reg: float = 0.0            # l2 strength (on coefficients, not intercept)
    elastic_net: float = 0.0    # l1 mixing (0 = pure l2)
    #: None/0 = auto: 32 for dense fits; mixed/sparse hashed layouts grow
    #: the batch until the ELL routing layout fits its HBM budget, so the
    #: default product path plans the ELL kernels
    #: (:func:`resolve_global_batch_size`).
    global_batch_size: Optional[int] = None
    max_epochs: int = 20
    tol: float = 1e-6           # epoch-loss-change termination; <=0 disables
    seed: int = 0
    fit_intercept: bool = True
    #: MXU precision of the fused ELL kernels' in-kernel one-hot
    #: contractions.  "default" is one bf16 pass and holds epoch-level
    #: parity with the XLA oracle (rtol=1e-3): the contracted residuals
    #: are batch-normalized, so their ~2^-8 relative truncation lands
    #: below the f32 summation-order noise every ELL path already
    #: carries.  "highest" (multi-pass f32) restores
    #: bit-comparable-to-XLA gathers; neither is timed on the chip.
    ell_precision: str = "default"
    #: How the data-parallel gradient sum is performed
    #: (:class:`~flink_ml_tpu.parallel.grad_reduce.GradReduceConfig`).
    #: ``None`` (default) and ``mode="exact"`` keep the legacy implicit
    #: GSPMD ``lax.psum`` path bit-identically; compressed modes
    #: (``topk`` error-feedback sparsification, ``int8`` block
    #: quantization, hierarchical ICI x DCN composition) route the DENSE
    #: trainers' gradients through an explicit
    #: :func:`~flink_ml_tpu.parallel.grad_reduce.reduce_gradients` —
    #: the EF residual rides the donated scan carry next to the weights
    #: and round-trips through checkpoints with them.
    grad_reduce: Optional[object] = None


#: Classic minibatch default when nothing layout-aware applies.
DEFAULT_GLOBAL_BATCH = 32

#: Auto-sizing never grows the batch past this: a bigger batch changes
#: optimization dynamics more than it buys steps.
_AUTO_BATCH_CAP = 1 << 15


def resolve_global_batch_size(config: "SGDConfig", n: int,
                              num_features: Optional[int] = None,
                              layout_bytes_per_slot: int = 12) -> int:
    """The batch size a fit actually runs.  Explicit user choices pass
    through untouched.  Auto (None/0) resolves to 32 for dense fits; for
    the hashed mixed/sparse layouts it grows the batch (fewer steps) until
    the per-step ELL routing layout stack fits ``_ELL_LAYOUT_BUDGET_BYTES``
    — at a batch of 32, a 1M-row fit needs 32k steps of layout
    (~400 GB at 2^20 features) and :func:`plan_mixed_impl` would fall
    back to XLA in silence.  Deterministic in (n, num_features) only — the
    same fit plans the same batch on any backend."""
    if config.global_batch_size:
        return config.global_batch_size
    if num_features is None:
        return DEFAULT_GLOBAL_BATCH
    max_steps = max(1, _ELL_LAYOUT_BUDGET_BYTES
                    // (num_features * layout_bytes_per_slot))
    min_batch = -(-n // max_steps)
    return min(max(DEFAULT_GLOBAL_BATCH, min_batch), _AUTO_BATCH_CAP)


@dataclass
class LinearState:
    coefficients: np.ndarray    # (d,)
    intercept: float
    #: which update implementation the fit planned ("ell" / "xla" /
    #: "sharded" / "dense" / streaming variants) — surfaced so product
    #: callers can see it; ``lr_criteo.fit`` checks it as its plan.
    #: Not part of persisted model data.
    planned_impl: Optional[str] = None


def plan_epoch_layout(n: int, global_batch_size: int, n_dev: int,
                      seed: int) -> Tuple[int, int, np.ndarray]:
    """Size the (steps, batch) epoch grid — batch divisible by the mesh's
    data axis — and the seeded row shuffle.  THE canonical batch-sizing
    arithmetic: WideDeep consumes it directly; the linear trainers layer
    process-sharding on top via :func:`_plan_epoch_layout_for_mesh`, which
    delegates here so the two can never diverge."""
    batch = max(global_batch_size, n_dev)
    batch += (-batch) % n_dev
    steps = max(1, -(-n // batch))
    perm = np.random.default_rng(seed).permutation(n)
    return steps, batch, perm


def _plan_epoch_layout_for_mesh(n_local: int, global_batch_size: int,
                                mesh, seed: int
                                ) -> Tuple[int, int, np.ndarray]:
    """Mesh-aware :func:`plan_epoch_layout`: on a mesh spanning P processes
    each process prepares its LOCAL (steps, batch/P, ...) slice of the
    global epoch tensor from its own ``n_local`` rows (equal across
    processes — validated below); single-process meshes reduce to the
    classic layout exactly."""
    n_dev = int(mesh.shape["data"])
    procs = _mesh_process_count(mesh)
    steps, batch, perm = plan_epoch_layout(
        n_local, global_batch_size, n_dev, seed)
    if procs == 1:
        return steps, batch, perm
    if batch % procs:
        raise ValueError(
            f"global batch {batch} is not divisible by the mesh's "
            f"{procs} processes (data axis {n_dev}); size the batch and "
            "data axis as multiples of the process count")
    local_batch = batch // procs
    steps = max(1, -(-n_local // local_batch))
    # Unequal per-process layouts would compile different programs on each
    # host and deadlock in the collectives; turn that into an immediate
    # error with one tiny cross-host gather.
    from jax.experimental import multihost_utils

    layouts = np.asarray(multihost_utils.process_allgather(
        np.asarray([steps, local_batch], np.int64)))
    if not np.all(layouts == layouts.reshape(-1, 2)[0]):
        raise ValueError(
            "multi-host fit requires every process to contribute the same "
            f"row count; got per-process (steps, local_batch) = "
            f"{layouts.reshape(-1, 2).tolist()}")
    return steps, local_batch, perm


def prepare_epoch_tensor(arr: np.ndarray, perm: np.ndarray, steps: int,
                         batch: int, pad_value: float = 0.0) -> np.ndarray:
    """Shuffle rows by ``perm``, pad to steps*batch, reshape to
    (steps, batch, ...)."""
    arr = arr[perm]
    total = steps * batch
    if arr.shape[0] < total:
        pad_shape = (total - arr.shape[0],) + arr.shape[1:]
        arr = np.concatenate([arr, np.full(pad_shape, pad_value, arr.dtype)])
    return arr.reshape((steps, batch) + arr.shape[1:])


def _epoch_order(perm: np.ndarray, steps: int, batch: int) -> np.ndarray:
    """The ``(steps, batch)`` row numbers of the epoch tensors
    :func:`prepare_epoch_tensor` makes by ``perm``: ``perm`` itself, then
    ``len(perm)`` for each padding row."""
    order = np.full(steps * batch, len(perm), np.int32)
    order[:len(perm)] = perm
    return order.reshape(steps, batch)


def _one_device(mesh):
    """The mesh's device where it has one and this process holds it: a
    fit there makes its epoch tensors on the device
    (:func:`_epoch_tensors`).  ``None`` on a mesh of several devices or
    processes, whose epoch tensors are made on the host and put sharded."""
    devices = np.ravel(mesh.devices)
    if devices.size != 1 or devices[0].process_index != jax.process_index():
        return None
    return devices[0]


@functools.partial(jax.jit, static_argnames=("widths", "steps"))
def _epoch_tensors(order, flats, widths, steps):
    """The epoch tensors of the columns ``flats`` (each put flat, its row
    ``widths`` given), bit for bit those :func:`prepare_epoch_tensor`
    makes on the host: row ``order[i]`` at position ``i``, a zero row
    where ``order`` holds a number past the last row."""
    return tuple(
        jnp.take(flat.reshape((-1,) + width), order, axis=0, mode="fill",
                 fill_value=0).reshape((steps, -1) + width)
        for flat, width in zip(flats, widths))


def sgd_fit(loss_fn: LossFn, features: np.ndarray, labels: np.ndarray,
            weights: Optional[np.ndarray], config: SGDConfig,
            mesh=None) -> Tuple[LinearState, list]:
    """Train (w, b) minimizing ``loss_fn(margin, labels, weights) +
    reg * penalty(w)``.  Returns the fitted state and the per-epoch loss log.

    The elastic-net penalty matches the classic formulation:
    ``reg * ((1-alpha)/2 ||w||^2 + alpha ||w||_1)`` with the l1 part applied
    via proximal soft-thresholding after each step.
    """
    d = features.shape[1]
    init_params = {"w": jnp.zeros((d,), jnp.float32),
                   "b": jnp.zeros((), jnp.float32)}
    params, loss_log = sgd_fit_params(loss_fn, features, labels, weights,
                                      config, mesh, init_params=init_params)
    return LinearState(np.asarray(params["w"], np.float64),
                       float(params["b"]), planned_impl="dense"), loss_log


def sgd_fit_params(loss_fn: LossFn, features: np.ndarray, labels: np.ndarray,
                   weights: Optional[np.ndarray], config: SGDConfig,
                   mesh=None, *, init_params) -> Tuple[dict, list]:
    """Generic core behind :func:`sgd_fit`: trains any ``{"w", "b"}`` param
    pytree whose score is ``x @ w + b`` (vector w for the binary/regression
    family, a (d, classes) matrix for softmax).  ``loss_fn(scores, labels,
    weights)`` defines the objective; labels ride the epoch tensor as f32
    (exact for class ids < 2^24 — cast back inside the loss)."""
    mesh = mesh or default_mesh()
    n = features.shape[0]
    gr = _active_grad_reduce(config)
    if gr is None:
        batch_axis = "data"
        steps, batch, perm = _plan_epoch_layout_for_mesh(
            n, resolve_global_batch_size(config, n), mesh, config.seed)
    else:
        axes, n_dev_red, batch_axis = _grad_reduce_layout(gr, mesh)
        if axes == ("data",):
            steps, batch, perm = _plan_epoch_layout_for_mesh(
                n, resolve_global_batch_size(config, n), mesh, config.seed)
        else:
            # hierarchical: the batch shards over dcn x data; the fused
            # fit stays single-process (multi-host compressed training
            # rides sgd_fit_outofcore's per-process readers)
            if _mesh_process_count(mesh) > 1:
                raise ValueError(
                    "hierarchical grad_reduce in the fused fit requires a "
                    "single-process mesh; stream multi-host fits through "
                    "sgd_fit_outofcore")
            steps, batch, perm = plan_epoch_layout(
                n, resolve_global_batch_size(config, n), n_dev_red,
                config.seed)

    X = prepare_epoch_tensor(features.astype(np.float32), perm, steps, batch)
    y = prepare_epoch_tensor(labels.astype(np.float32), perm, steps, batch)
    w_host = (weights.astype(np.float32) if weights is not None
              else np.ones((n,), np.float32))
    w = prepare_epoch_tensor(w_host, perm, steps, batch, pad_value=0.0)

    X = _put_epoch_tensor(X, mesh, P(None, batch_axis, None))
    y = _put_epoch_tensor(y, mesh, P(None, batch_axis))
    w = _put_epoch_tensor(w, mesh, P(None, batch_axis))

    if gr is None:
        update = _linear_update(loss_fn, config)
        return _run_minibatch_epochs(
            update, (X, y, w), init_params, steps, config, mesh,
            program_key=_epoch_program_key(_linear_update, loss_fn, config,
                                           mesh, steps))
    from ...parallel import grad_reduce as GR

    update = _linear_update_reduced(loss_fn, config, mesh)
    init_params = dict(init_params)
    init_params[GR_STATE_KEY] = GR.init_state(gr, {
        k: init_params[k] for k in ("w", "b")}, n_dev_red)
    params, loss_log = _run_minibatch_epochs(update, (X, y, w), init_params,
                                             steps, config, mesh)
    gr_state = params.pop(GR_STATE_KEY, None)
    if gr_state is not None and GR.wants_overlap(gr):
        params = _apply_drain(params, gr_state, config)
    return params, loss_log


def _run_minibatch_epochs(update, data: tuple, init_params, steps: int,
                          config: SGDConfig, mesh, *,
                          place_params: bool = True,
                          program_key: Optional[tuple] = None
                          ) -> Tuple[dict, list]:
    """THE shared epoch driver behind sgd_fit / sgd_fit_sparse /
    sgd_fit_mixed: an inner scan of ``update`` over per-step slices of the
    (steps, batch, ...) device tensors in ``data``, wrapped in a fused
    ``iterate`` with tol termination.  One copy of the termination /
    loss-log logic so the three trainers can never diverge.  Multi-host:
    the tol-termination vote is computed identically on every host inside
    the fused while_loop (replicated scalars), so early stopping works
    without any cross-host round-trip per epoch.

    ``program_key`` (:func:`_epoch_program_key`) is the epoch body's
    program key (``iteration/body.py: with_program_key``): fits 2..n of
    a process with an equal key and equal shapes enqueue the program the
    first one built.  ``None`` builds it anew."""

    from ...obs.probe import StepProbe

    def epoch_body(state, epoch, data):
        params, prev_loss, probe = state

        def batch_step(params, i):
            return update(params, *(a[i] for a in data))

        params, losses = jax.lax.scan(
            batch_step, params, jnp.arange(steps, dtype=jnp.int32))
        epoch_loss = jnp.mean(losses)
        # The full loss history rides in the carried state (a StepProbe
        # — obs/probe.py, the generalization of the fixed-size
        # NaN-prefilled buffer this driver used to hand-roll) so the
        # fused while_loop path — which only keeps the LAST epoch's
        # outputs — still yields the complete log in one fetch.
        probe = probe.record_at(epoch, loss=epoch_loss)
        termination = (jnp.abs(prev_loss - epoch_loss) > config.tol
                       if config.tol > 0 else None)
        return IterationBodyResult(
            feedback=(params, epoch_loss, probe), termination=termination)

    if program_key is not None:
        epoch_body = with_program_key(epoch_body, _run_minibatch_epochs,
                                      program_key)
    with tracer.span("fit.upload", "fit"):
        init_state = (replicate(init_params, mesh) if place_params
                      else init_params,
                      jnp.asarray(jnp.inf, jnp.float32),
                      StepProbe.create(("loss",), config.max_epochs))

    result = iterate(
        epoch_body, init_state, data,
        max_epochs=config.max_epochs,
        config=IterationConfig(mode="fused"),
    )
    params, _final_loss, probe = result.state
    with tracer.span("fit.fetch", "fit"):
        params = _fetch_replicated(params)
        loss_log = list(probe.fetch(
            get=lambda v: _fetch_replicated(v))["loss"][:result.num_epochs])
    return params, loss_log


def _epoch_program_key(factory: Callable, loss_fn, config: SGDConfig, mesh,
                       steps: int, ell_rows: Optional[int] = None):
    """What the fused program of :func:`_run_minibatch_epochs` over the
    update ``factory`` builds follows from beyond its arguments' shapes:
    the factory and the loss, every config field its trace reads
    (``_step_program_key``'s, then ``tol`` and ``max_epochs``), the steps
    of an epoch, the mesh and the backend, the registry's answers for the
    ELL ops at ``ell_rows`` table rows, and the module functions the
    trace calls, by what their names hold now.  ``None`` where a fact
    does not hash."""
    from ...kernels.registry import lookup

    kernels = () if ell_rows is None else tuple(
        (entry.backend, entry.fn) for entry in (
            lookup(op, sig=(ell_rows,))
            for op in ("ell_margin", "ell_scatter_apply")))
    return _step_program_key(
        (factory, int(steps), float(config.tol), int(config.max_epochs),
         jax.default_backend(), kernels, _finish_sparse_step,
         _gather_weights, _scatter_add_weights, _ell_margin,
         _apply_ell_categorical, _extended_r),
        loss_fn, config, mesh)


def _linear_update(loss_fn: LossFn, config: SGDConfig):
    """THE single-batch update — l2-regularized gradient step + l1 proximal
    soft-threshold — shared by the fused (sgd_fit) and streaming
    (sgd_fit_outofcore) paths so the two can never diverge.  Unjitted;
    callers place it inside their own compiled program."""
    lr = config.learning_rate
    reg, alpha = config.reg, config.elastic_net
    l2 = reg * (1.0 - alpha)
    l1 = reg * alpha

    def objective(params, xb, yb, wb):
        margin = xb @ params["w"] + params["b"]
        return loss_fn(margin, yb, wb) + 0.5 * l2 * jnp.sum(
            jnp.square(params["w"]))

    grad_fn = jax.value_and_grad(objective)

    def update(params, xb, yb, wb):
        value, grads = grad_fn(params, xb, yb, wb)
        new_w = params["w"] - lr * grads["w"]
        if l1 > 0:
            # proximal soft-threshold for the l1 part
            new_w = jnp.sign(new_w) * jnp.maximum(
                jnp.abs(new_w) - lr * l1, 0.0)
        new_b = params["b"] - (lr * grads["b"]
                               if config.fit_intercept else 0.0)
        return {"w": new_w, "b": new_b}, value

    return update


#: Reserved params-pytree key the compressed-reduction trainers use to
#: carry reducer state (EF residual / rounding key / the wire-protocol
#: tier's fill-in + union accounting) in the SAME donated scan carry as
#: the weights — which is exactly what makes it ride every existing
#: checkpoint cut and restore untouched.
GR_STATE_KEY = "_gr"


def _active_grad_reduce(config: SGDConfig):
    """The grad-reduce config IF it changes anything: ``None`` (and
    ``mode="exact"``) keep the legacy implicit-psum path — the unchanged,
    bit-identical default."""
    gr = config.grad_reduce
    if gr is None or gr.mode == "exact":
        return None
    return gr


def _grad_reduce_layout(gr, mesh):
    """(reduction axes, participant count, batch PartitionSpec entry) for
    a compressed fit on ``mesh`` — the shared
    :func:`~flink_ml_tpu.parallel.grad_reduce.mesh_layout` validation."""
    from ...parallel import grad_reduce as GR

    return GR.mesh_layout(gr, mesh)


def _linear_update_reduced(loss_fn: LossFn, config: SGDConfig, mesh):
    """Explicit-reduction twin of :func:`_linear_update` for the dense
    layout: per-device gradients of the GLOBAL weighted-mean loss are
    computed inside ``shard_map`` over the reduction axes and summed
    through :func:`~flink_ml_tpu.parallel.grad_reduce.reduce_gradients`
    (topk-EF / int8 / hierarchical per ``config.grad_reduce``).  The
    reducer state travels in ``params[GR_STATE_KEY]`` with a leading
    participant dim sharded over the reduction axes.

    Same regularization algebra as the exact path: the local weighted
    mean is re-normalized to the global denominator (the
    ``_mixed_update_sharded`` stance), the l2 term applies as exact
    decay on the replicated weight AFTER the reduction (it needs no
    communication, so it is never compressed), and l1 stays the proximal
    soft-threshold.

    ``config.grad_reduce.overlap`` swaps in the one-step-stale pipelined
    schedule (:func:`~flink_ml_tpu.parallel.grad_reduce.pipelined_reduce`):
    this step's gradient goes into the carried ``pending`` buffer and the
    PREVIOUS step's pending is reduced and applied — the reduction's
    bucket collectives have no data dependence on this step's
    forward/backward, so XLA's scheduler overlaps them.  The fit-end
    drain of the last pending gradient (+ EF residual) is the adopting
    fits' job (:func:`_apply_drain`)."""
    from ...parallel import grad_reduce as GR

    gr = config.grad_reduce
    lr = config.learning_rate
    reg, alpha = config.reg, config.elastic_net
    l2 = reg * (1.0 - alpha)
    l1 = reg * alpha
    overlap = GR.wants_overlap(gr)
    axes, _, batch_axis = _grad_reduce_layout(gr, mesh)
    x_spec = P(batch_axis, None)
    v_spec = P(batch_axis)
    st_spec = P(batch_axis)

    def device_fn(w, b, gr_state, xb, yb, wb):
        margin = xb @ w + b
        value_local, pull = jax.vjp(lambda m: loss_fn(m, yb, wb), margin)
        (r,) = pull(jnp.ones_like(value_local))
        # re-normalize the loss_fn's LOCAL weighted mean to the global
        # denominator so the objective equals the single-program one
        denom_local = jnp.maximum(jnp.sum(wb), 1e-12)
        denom = jax.lax.psum(denom_local, axes)
        value = jax.lax.psum(value_local * denom_local, axes) / denom
        r = r * (denom_local / denom)
        grads = {"w": jnp.tensordot(xb, r, axes=((0,), (0,))),
                 "b": jnp.sum(r, axis=0)}
        if overlap:
            red, new_state = GR.pipelined_reduce(
                grads, GR.squeeze_state(gr_state), gr)
        else:
            red, new_state = GR.reduce_gradients(
                grads, GR.squeeze_state(gr_state), gr)
        if l2 > 0:
            value = value + 0.5 * l2 * jnp.sum(jnp.square(w))
            w = w * (1.0 - lr * l2)
        new_w = w - lr * red["w"]
        if l1 > 0:
            new_w = jnp.sign(new_w) * jnp.maximum(
                jnp.abs(new_w) - lr * l1, 0.0)
        new_b = b - (lr * red["b"] if config.fit_intercept else 0.0)
        return new_w, new_b, GR.unsqueeze_state(new_state), value

    fn = _shard_map(
        device_fn, mesh,
        in_specs=(P(), P(), st_spec, x_spec, v_spec, v_spec),
        out_specs=(P(), P(), st_spec, P()))

    def update(params, xb, yb, wb):
        w, b, st, value = fn(params["w"], params["b"],
                             params[GR_STATE_KEY], xb, yb, wb)
        return {"w": w, "b": b, GR_STATE_KEY: st}, value

    return update


def _apply_drain(params: dict, gr_state: dict, config: SGDConfig) -> dict:
    """Fit-end drain of an overlapped run: one exact host-side apply of
    the participant-summed ``pending`` gradient plus the EF residual
    (:func:`~flink_ml_tpu.parallel.grad_reduce.drain_pending`) — the
    same decay / step / prox / bias tail as one in-loop update, so the
    overlapped trajectory ends with zero unsent mass instead of dropping
    its last gradient.  Runs AFTER the final loss log entry; checkpoint
    cuts never include it (resume re-runs the fit and drains at ITS
    end, which is what keeps crash+resume bit-exact vs uninterrupted)."""
    from ...parallel import grad_reduce as GR

    drain = GR.drain_pending(gr_state)
    lr = config.learning_rate
    reg, alpha = config.reg, config.elastic_net
    l2 = reg * (1.0 - alpha)
    l1 = reg * alpha
    w = np.asarray(params["w"], np.float32)
    if l2 > 0:
        w = w * np.float32(1.0 - lr * l2)
    w = w - np.float32(lr) * drain["w"]
    if l1 > 0:
        w = np.sign(w) * np.maximum(np.abs(w) - lr * l1, 0.0)
    b = np.asarray(params["b"], np.float32)
    if config.fit_intercept:
        b = b - np.float32(lr) * drain["b"]
    return {**params, "w": jnp.asarray(w), "b": jnp.asarray(b)}


# TPU random access is per-DMA-transaction bound, not bandwidth bound:
# an elementwise gather costs ~6-7 ns/element regardless of table size
# (measured honestly on v5e — loop-carried, nothing hoistable), while
# fetching whole lane-aligned rows and selecting the lane amortises the
# transaction: 512B rows (128 lanes f32) reach ~2.5 ns/slot and 1KB rows
# (256 lanes) ~1.7 ns/slot.  Gathers therefore use the widest row (256
# lanes) the weight size divides.  Scatter RMW does NOT benefit the same
# way (measured ~even with elementwise), so the scatter keeps 128-lane
# rows; the real scatter fix is the ELL kernel (`ops/ell_scatter.py`).
# The arithmetic is identical — blocked and elementwise paths produce
# bitwise-equal weights.
_BLOCK_LANES = 128
_GATHER_LANES = 256


# the blocked-gather half lives in ops/ell_scatter.py now (the kernel
# layer owns device-kernel helpers; model code imports DOWN, never the
# reverse) — re-bound here under the historical names for the updates
# below and for tests that exercise them through this module
from ...ops.ell_scatter import (  # noqa: E402
    blocked_gather as _blocked_gather,
    gather_weights as _gather_weights,
    use_blocked as _use_blocked,
)


def _blocked_scatter_add(w: jnp.ndarray, idx: jnp.ndarray,
                         updates_flat: jnp.ndarray) -> jnp.ndarray:
    """``w.at[idx.ravel()].add(updates_flat)`` via 128-lane row-scatter."""
    flat = idx.reshape(-1)
    hi, lo = flat // _BLOCK_LANES, flat % _BLOCK_LANES
    onehot = lo[:, None] == jnp.arange(_BLOCK_LANES, dtype=lo.dtype)[None, :]
    w2 = w.reshape(-1, _BLOCK_LANES).at[hi].add(
        updates_flat[:, None] * onehot)
    return w2.reshape(-1)


def _scatter_add_weights(w: jnp.ndarray, idx: jnp.ndarray,
                         updates_flat: jnp.ndarray) -> jnp.ndarray:
    if _use_blocked(w.shape[0]):
        return _blocked_scatter_add(w, idx, updates_flat)
    return w.at[idx.reshape(-1)].add(updates_flat)


def _finish_sparse_step(config: SGDConfig, *, sumsq=None, rsum=None):
    """Shared l2/apply/l1-prox/bias tail of the manual-gradient updates:
    the regularization algebra lives in ONE place so the sparse, mixed,
    ELL, and model-sharded paths stay identical to the dense autodiff
    semantics (l2 decay = ``w*(1-lr*l2)`` before the sparse gradient,
    exactly grad-of-``loss + l2/2 ||w||^2``; l1 via proximal
    soft-threshold after).

    ``sumsq``/``rsum`` override the two REDUCTIONS (||w||^2 and sum(r))
    for callers whose w/r are device-local shards needing a psum — the
    elementwise algebra never forks."""
    lr = config.learning_rate
    reg, alpha = config.reg, config.elastic_net
    l2 = reg * (1.0 - alpha)
    l1 = reg * alpha
    sumsq = sumsq or (lambda w: jnp.sum(jnp.square(w)))
    rsum = rsum or jnp.sum

    def finish(w, b, value, r, apply_grad):
        """``apply_grad(w)`` must add ``-lr * grad_loss`` to the (possibly
        l2-decayed) weight; ``r`` is dloss/dmargin for the bias step."""
        if l2 > 0:
            value = value + 0.5 * l2 * sumsq(w)
            w = w * (1.0 - lr * l2)
        w = apply_grad(w)
        if l1 > 0:
            w = jnp.sign(w) * jnp.maximum(jnp.abs(w) - lr * l1, 0.0)
        b = b - (lr * rsum(r) if config.fit_intercept else 0.0)
        return {"w": w, "b": b}, value

    return finish


def _sparse_update(loss_fn: LossFn, config: SGDConfig):
    """Single-batch update for hashed/sparse features ``(indices, values)``
    of fixed active count per row: the score is one gather + row reduce
    (``sum(values * w[indices])``) — the TPU-native replacement for a CSR
    SpMV.

    The weight gradient is applied as a direct in-place scatter-add of
    ``-lr * values * dloss/dmargin`` into the carried weight rather than by
    autodiff of the gather: ``jax.grad`` would materialise a dense (d,)
    cotangent (zero-fill + scatter + dense subtract = three O(d) HBM passes
    per step), while this form touches only the O(batch*nnz) active slots
    when unregularized.  ``loss_fn`` stays generic: dloss/dmargin comes
    from a vjp over the margin alone.  Gather/scatter go through the
    128-lane blocked views (see ``_BLOCK_LANES``) when the weight size
    allows.  l2 decay and the l1 proximal step are inherently dense and
    only cost their O(d) passes when enabled."""
    lr = config.learning_rate
    finish = _finish_sparse_step(config)

    def update(params, idx, vals, yb, wb):
        w, b = params["w"], params["b"]
        margin = jnp.sum(vals * _gather_weights(w, idx), axis=-1) + b
        value, pull = jax.vjp(lambda m: loss_fn(m, yb, wb), margin)
        (r,) = pull(jnp.ones_like(value))          # dloss/dmargin, (batch,)
        return finish(w, b, value, r, lambda w: _scatter_add_weights(
            w, idx, -lr * (vals * r[:, None]).reshape(-1)))

    return update


def _mixed_update(loss_fn: LossFn, config: SGDConfig):
    """Single-batch update for the Criteo-native layout: ``dense`` features
    occupying weight slots ``[0, dense.shape[-1])`` plus hashed ``cat``
    indices with implicit value 1.0 anywhere in ``[0, d)``.  The dense
    slots score and update through a tiny matvec (no gather/scatter at all
    — on TPU the random access IS the cost, measured ~8 ns/element), so
    only the categorical slots pay it; their gradient is just
    ``dloss/dmargin`` per slot.  Overlapping indices are handled exactly:
    both contributions simply add."""
    lr = config.learning_rate
    finish = _finish_sparse_step(config)

    def update(params, dense, cat, yb, wb):
        w, b = params["w"], params["b"]
        n_dense, n_cat = dense.shape[-1], cat.shape[-1]
        with jax.named_scope("linear.margin"):
            margin = (dense @ w[:n_dense]
                      + jnp.sum(_gather_weights(w, cat), axis=-1) + b)
        value, pull = jax.vjp(lambda m: loss_fn(m, yb, wb), margin)
        (r,) = pull(jnp.ones_like(value))

        def apply_grad(w):
            with jax.named_scope("linear.apply"):
                w = _scatter_add_weights(w, cat, jnp.repeat(-lr * r, n_cat))
                return w.at[:n_dense].add(-lr * (r @ dense))

        return finish(w, b, value, r, apply_grad)

    return update


def _ext_len(batch: int) -> int:
    """Length of the extended per-sample tables (:func:`_extended_r` and
    the ELL margin accumulator): batch plus a nonempty zero pad rounding
    up to whole 256-lane rows (pad slots carry ``src == batch``)."""
    return batch + (_GATHER_LANES - (batch % _GATHER_LANES)
                    or _GATHER_LANES)


def _extended_r(r: jnp.ndarray) -> jnp.ndarray:
    """r with a zero pad: padding slots carry ``src == batch`` and the pad
    rounds the gather table up to a whole number of 256-lane rows."""
    batch = r.shape[0]
    return jnp.concatenate(
        [r, jnp.zeros((_ext_len(batch) - batch,), jnp.float32)])


def _ell_margin(backend, precision, w, batch, src, pos, mask, ovf_idx,
                ovf_src, heavy_idx, heavy_cnt, val_ell=None, ovf_val=None):
    """Per-sample categorical margin ``sum_j v_j * w[idx_j]`` computed
    over the SAME ELL routing the scatter uses — the forward half of the
    kernel plan (the Mosaic margin kernel replaces the ``w[cat]`` gather
    with one-hot MXU contractions).  The in-grid implementation resolves from the
    kernel registry (op ``ell_margin``: the fused Mosaic kernel on TPU
    grids divisible into 8-row blocks, the XLA twin otherwise;
    ``backend`` forces one — tests pass ``"xla"`` for the oracle).
    Overflow via a tiny gather + extended-table scatter-add (pad entries
    carry ``ovf_src == batch`` and land in the discarded pad), heavy
    hitters via one ``(H,) @ (H, batch)`` matvec."""
    from ...kernels.registry import lookup

    entry = lookup("ell_margin", sig=(int(src.shape[0]),), backend=backend)
    mext = entry.fn(w, src, pos, mask, m_len=_ext_len(batch),
                    val=val_ell, precision=precision)
    o = w[ovf_idx] if ovf_val is None else ovf_val * w[ovf_idx]
    mext = mext.at[ovf_src].add(o, mode="drop")
    return mext[:batch] + w[heavy_idx] @ heavy_cnt.astype(jnp.float32)


def _apply_ell_categorical(backend, precision, lr, w, r, r_ext, src,
                           pos, mask, ovf_idx, ovf_src, heavy_idx,
                           heavy_cnt, val_ell=None, ovf_val=None):
    """THE single copy of the ELL gradient application shared by the
    mixed (implicit value 1.0) and generic sparse (explicit values)
    update builders: slot gather -> kernel scatter -> overflow scatter ->
    heavy-hitter matvec ((H, batch) @ (batch,) replaces thousands of
    per-slot updates; padding entries carry zero counts and add 0 at
    w[0]).

    The in-grid implementation resolves from the kernel registry (op
    ``ell_scatter_apply``): on TPU the slot gather + scatter run as ONE
    fused Mosaic kernel — the r4 ablation measured the standalone XLA
    u-gather as the dominant step cost (~5.6 ms of a 7.79 ms step;
    fused step 6.53 ms vs 8.92 ms XLA oracle) — with the gather +
    scatter-kernel pair as the registered fallback when the grid
    doesn't divide into the fused kernel's 8-row blocks, and the pure
    XLA lowering off TPU (``backend`` forces one)."""
    from ...kernels.registry import lookup

    entry = lookup("ell_scatter_apply", sig=(int(src.shape[0]),),
                   backend=backend)
    w = entry.fn(w, r_ext, src, pos, mask, lr=lr, val=val_ell,
                 precision=precision)
    o = r_ext[ovf_src] if ovf_val is None else ovf_val * r_ext[ovf_src]
    w = w.at[ovf_idx].add((-lr) * o)
    return w.at[heavy_idx].add((-lr) * (heavy_cnt.astype(jnp.float32) @ r))


def _mixed_update_ell(loss_fn: LossFn, config: SGDConfig,
                      backend=None):
    """Kernel-planned twin of :func:`_mixed_update`: same loss/
    regularization algebra, but BOTH halves of the categorical work —
    the forward margin gather and the backward scatter — go through the
    static ELL routing's fused Mosaic kernels (``ops/ell_scatter.py``)
    instead of XLA's per-element gather/scatter.  The extra batch arguments (src, pos,
    mask, ovf_idx, ovf_src, heavy_idx, heavy_cnt) are the per-step
    layout stacks produced by ``ell_layout`` at fit time — the raw
    ``cat`` tensor itself is not an input; results differ from the XLA
    path only in f32 summation order (plus the documented
    ``ell_precision`` truncation of the one-hot contractions).

    The device's work of a step is under two scopes, as in
    :func:`_mixed_update`: ``linear.margin`` (the dense matvec, the ELL
    margin kernel, the overflow gather-add, the heavy matvec) and
    ``linear.apply`` (the ELL scatter kernel, the overflow scatter, the
    heavy update, the dense update)."""
    lr = config.learning_rate
    finish = _finish_sparse_step(config)

    def update(params, dense, src, pos, mask, ovf_idx, ovf_src,
               heavy_idx, heavy_cnt, yb, wb):
        w, b = params["w"], params["b"]
        n_dense = dense.shape[-1]
        with jax.named_scope("linear.margin"):
            margin = (dense @ w[:n_dense]
                      + _ell_margin(backend, config.ell_precision,
                                    w, dense.shape[0], src, pos,
                                    mask, ovf_idx, ovf_src, heavy_idx,
                                    heavy_cnt) + b)
        value, pull = jax.vjp(lambda m: loss_fn(m, yb, wb), margin)
        (r,) = pull(jnp.ones_like(value))
        r_ext = _extended_r(r)

        def apply_grad(w):
            with jax.named_scope("linear.apply"):
                w = _apply_ell_categorical(
                    backend, config.ell_precision, lr, w, r, r_ext, src,
                    pos, mask, ovf_idx, ovf_src, heavy_idx, heavy_cnt)
                return w.at[:n_dense].add(-lr * (r @ dense))

        return finish(w, b, value, r, apply_grad)

    return update


def _shard_map(fn, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the repo's default flag — one shared copy
    in ``parallel/collectives.py`` (the varying-axes check is off, since
    pallas_call out_shapes carry no varying-mesh-axes annotation)."""
    from ...parallel.collectives import shard_map_fn

    return shard_map_fn(fn, mesh, in_specs=in_specs, out_specs=out_specs)


def _mixed_update_ell_sharded(loss_fn: LossFn, config: SGDConfig, mesh,
                              num_features: int, backend=None):
    """Data-parallel twin of :func:`_mixed_update_ell` (VERDICT r3 task 4:
    the pod-scale ELL path).  Each device routes only ITS batch shard's
    categorical slots through a device-LOCAL ELL grid — the layout stacks
    carry a leading device dim sharded over ``data``, with slot sources
    numbered inside the local shard — and emits a local delta over the
    full weight; one ``psum`` rides ICI to complete the scatter, exactly
    like the dense gradient's contraction.  Scatter compute and layout
    HBM both scale 1/D with the data axis; summation order differs from
    the single-device kernel only by the per-device partial-sum split."""
    lr = config.learning_rate
    finish = _finish_sparse_step(config)
    d_spec = P("data")
    layout_specs = ((P("data", None, None),) * 3 + (P("data", None),) * 3
                    + (P("data", None, None),))

    def _local_delta(r_l, src, pos, mask, ovf_idx, ovf_src, heavy_idx,
                     heavy_cnt):
        # layout blocks arrive as (1, ...) local slices: squeeze the
        # device dim; r_l is this device's residual shard
        r_ext = _extended_r(r_l)
        delta = _apply_ell_categorical(
            backend, config.ell_precision, lr,
            jnp.zeros((num_features,), jnp.float32), r_l,
            r_ext, src[0], pos[0], mask[0], ovf_idx[0], ovf_src[0],
            heavy_idx[0], heavy_cnt[0])
        return jax.lax.psum(delta, "data")

    ell_delta = _shard_map(
        _local_delta, mesh,
        in_specs=(d_spec,) + layout_specs,
        out_specs=P())

    def _local_margin(w, src, pos, mask, ovf_idx, ovf_src, heavy_idx,
                      heavy_cnt):
        # per-device margins of the device's own batch shard: its layout
        # slots cover exactly its samples (local src numbering), w is
        # replicated — no collective needed, margins reassemble over
        # 'data' (the local batch size is heavy_cnt's trailing dim)
        return _ell_margin(
            backend, config.ell_precision, w, heavy_cnt.shape[-1],
            src[0], pos[0], mask[0], ovf_idx[0], ovf_src[0],
            heavy_idx[0], heavy_cnt[0])

    ell_margin_sm = _shard_map(
        _local_margin, mesh,
        in_specs=(P(),) + layout_specs,
        out_specs=d_spec)

    def update(params, dense, src, pos, mask, ovf_idx, ovf_src,
               heavy_idx, heavy_cnt, yb, wb):
        w, b = params["w"], params["b"]
        n_dense = dense.shape[-1]
        margin = (dense @ w[:n_dense]
                  + ell_margin_sm(w, src, pos, mask, ovf_idx, ovf_src,
                                  heavy_idx, heavy_cnt) + b)
        value, pull = jax.vjp(lambda m: loss_fn(m, yb, wb), margin)
        (r,) = pull(jnp.ones_like(value))

        def apply_grad(w):
            w = w + ell_delta(r, src, pos, mask, ovf_idx, ovf_src,
                              heavy_idx, heavy_cnt)
            return w.at[:n_dense].add(-lr * (r @ dense))

        return finish(w, b, value, r, apply_grad)

    return update


def _sparse_update_ell_sharded(loss_fn: LossFn, config: SGDConfig, mesh,
                               num_features: int, backend=None):
    """Values-aware twin of :func:`_mixed_update_ell_sharded` for the
    generic (indices, values) layout — the same device-local-grid + psum
    scatter, with per-slot updates ``-lr * value * r`` carried by the
    layout's value arrays."""
    lr = config.learning_rate
    finish = _finish_sparse_step(config)
    layout_specs = ((P("data", None, None),) * 4 + (P("data", None),) * 4
                    + (P("data", None, None),))

    def _local_delta(r_l, src, pos, mask, val, ovf_idx, ovf_src, ovf_val,
                     heavy_idx, heavy_cnt):
        r_ext = _extended_r(r_l)
        delta = _apply_ell_categorical(
            backend, config.ell_precision, lr,
            jnp.zeros((num_features,), jnp.float32), r_l,
            r_ext, src[0], pos[0], mask[0], ovf_idx[0], ovf_src[0],
            heavy_idx[0], heavy_cnt[0], val_ell=val[0], ovf_val=ovf_val[0])
        return jax.lax.psum(delta, "data")

    ell_delta = _shard_map(
        _local_delta, mesh,
        in_specs=(P("data"),) + layout_specs,
        out_specs=P())

    def _local_margin(w, src, pos, mask, val, ovf_idx, ovf_src, ovf_val,
                      heavy_idx, heavy_cnt):
        # same stance as _mixed_update_ell_sharded: local layout covers
        # local samples, w replicated, margins reassemble over 'data'
        return _ell_margin(
            backend, config.ell_precision, w, heavy_cnt.shape[-1],
            src[0], pos[0], mask[0], ovf_idx[0], ovf_src[0],
            heavy_idx[0], heavy_cnt[0], val_ell=val[0],
            ovf_val=ovf_val[0])

    ell_margin_sm = _shard_map(
        _local_margin, mesh,
        in_specs=(P(),) + layout_specs,
        out_specs=P("data"))

    def update(params, src, pos, mask, val_ell, ovf_idx,
               ovf_src, ovf_val, heavy_idx, heavy_cnt, yb, wb):
        w, b = params["w"], params["b"]
        margin = ell_margin_sm(w, src, pos, mask, val_ell, ovf_idx,
                               ovf_src, ovf_val, heavy_idx, heavy_cnt) + b
        value, pull = jax.vjp(lambda m: loss_fn(m, yb, wb), margin)
        (r,) = pull(jnp.ones_like(value))

        def apply_grad(w):
            return w + ell_delta(r, src, pos, mask, val_ell, ovf_idx,
                                 ovf_src, ovf_val, heavy_idx, heavy_cnt)

        return finish(w, b, value, r, apply_grad)

    return update


def sgd_fit_sparse(loss_fn: LossFn, indices: np.ndarray, values: np.ndarray,
                   labels: np.ndarray, weights: Optional[np.ndarray],
                   num_features: int, config: SGDConfig,
                   mesh=None) -> Tuple[LinearState, list]:
    """Sparse-feature variant of :func:`sgd_fit`: rows are ``(indices
    (n, nnz) int32, values (n, nnz) f32)`` pairs (the
    :func:`flink_ml_tpu.linalg.stack_sparse_vectors` / hashed-FeatureHasher
    form) scored against a dense ``(num_features,)`` weight living in HBM.
    This is the Criteo-shaped path: 2^20+ hashed dims never materialise as a
    dense matrix; only the weight (4 MiB at 2^20 f32) is dense."""
    from .linear import check_sparse_indices

    check_sparse_indices(indices, num_features)
    mesh = mesh or default_mesh()
    n = indices.shape[0]
    steps, batch, perm = _plan_epoch_layout_for_mesh(
        n, resolve_global_batch_size(config, n, num_features,
                                     layout_bytes_per_slot=16),
        mesh, config.seed)

    idx = prepare_epoch_tensor(indices.astype(np.int32), perm, steps, batch)
    vals = prepare_epoch_tensor(values.astype(np.float32), perm, steps, batch)
    y = prepare_epoch_tensor(labels.astype(np.float32), perm, steps, batch)
    w_host = (weights.astype(np.float32) if weights is not None
              else np.ones((n,), np.float32))
    w = prepare_epoch_tensor(w_host, perm, steps, batch, pad_value=0.0)

    # the values-aware layout adds a fourth f32 grid (val): 16 B/slot/step
    impl = plan_mixed_impl(num_features, mesh, steps,
                           layout_bytes_per_slot=16, allow_sharded=True)
    n_dev_data = int(mesh.shape.get("data", 1))
    ell_sharded = impl == "ell" and n_dev_data > 1
    if ell_sharded:
        # per-device shard layouts, same stance as sgd_fit_mixed
        from ...ops.ell_scatter import ell_layout

        local = batch // n_dev_data
        lay = ell_layout(
            idx.reshape(steps * n_dev_data, local, idx.shape[-1]),
            num_features,
            values=vals.reshape(steps * n_dev_data, local, vals.shape[-1]))

        def dev_stack(a):
            return a.reshape((steps, n_dev_data) + a.shape[1:])

        extra = tuple(dev_stack(a) for a in (
            lay.src, lay.pos, lay.mask, lay.val, lay.ovf_idx, lay.ovf_src,
            lay.ovf_val, lay.heavy_idx, lay.heavy_cnt))
        factory = _sparse_update_ell_sharded
        update = factory(loss_fn, config, mesh, num_features)
    elif impl == "ell":
        from ...ops.ell_scatter import ell_layout

        layout = ell_layout(idx, num_features, values=vals)
        extra = (layout.src, layout.pos, layout.mask, layout.val,
                 layout.ovf_idx, layout.ovf_src, layout.ovf_val,
                 layout.heavy_idx, layout.heavy_cnt)
        factory = _sparse_update_ell
        update = factory(loss_fn, config)
    else:
        extra = ()
        factory = _sparse_update
        update = factory(loss_fn, config)

    y = _put_epoch_tensor(y, mesh, P(None, "data"))
    w = _put_epoch_tensor(w, mesh, P(None, "data"))
    if ell_sharded:
        specs = ([P(None, "data", None, None)] * 4
                 + [P(None, "data", None)] * 4
                 + [P(None, "data", None, None)])
        extra = tuple(_put_epoch_tensor(a, mesh, s)
                      for a, s in zip(extra, specs))
    elif impl == "ell":
        extra = tuple(jax.device_put(a) for a in extra)  # single-device
    if impl in ("ell",):
        # margins and scatters both ride the layout: the raw
        # (steps, batch, nnz) idx/vals epoch tensors stay host-side
        epoch_args = extra + (y, w)
    else:
        idx = _put_epoch_tensor(idx, mesh, P(None, "data", None))
        vals = _put_epoch_tensor(vals, mesh, P(None, "data", None))
        epoch_args = (idx, vals) + extra + (y, w)

    params, loss_log = _run_minibatch_epochs(
        update, epoch_args,
        {"w": jnp.zeros((num_features,), jnp.float32),
         "b": jnp.zeros((), jnp.float32)}, steps, config, mesh,
        program_key=_epoch_program_key(
            factory, loss_fn, config, mesh, steps,
            num_features // _BLOCK_LANES if impl == "ell" else None))
    return LinearState(np.asarray(params["w"], np.float64),
                       float(params["b"]), planned_impl=impl), loss_log


# The ELL layout costs ~12 bytes per weight slot PER STEP (src + pos i32
# + mask f32 over a (num_features/128, 128) grid), independent of batch
# size.  Cap its device footprint: beyond this, many-step fits (small
# batches or huge hash spaces) would OOM HBM where the XLA path runs fine.
_ELL_LAYOUT_BUDGET_BYTES = 2 << 30


def plan_mixed_impl(num_features: int, mesh, steps: int = 1,
                    layout_bytes_per_slot: int = 12,
                    allow_sharded: bool = False,
                    allow_multiprocess: bool = False) -> str:
    """Which categorical-scatter implementation :func:`sgd_fit_mixed`
    runs: ``"ell"`` (the Pallas static-routing kernel,
    ``ops/ell_scatter.py``) on TPU when the weight size tiles into
    128-lane rows and the ``steps``-deep layout stack fits the per-device
    HBM budget, else ``"xla"``.

    ``allow_sharded=True`` (what ``sgd_fit_mixed`` passes) additionally
    admits data-axis meshes: each device routes its own batch shard
    through a device-local grid and one psum completes the scatter
    (:func:`_mixed_update_ell_sharded`) — the layout budget is
    per-device, so the check does not change with the axis size.
    ``allow_multiprocess=True`` extends that to process-spanning meshes —
    only for callers whose layout build is per-process-local (the
    STREAMING fit, whose decode workers build each host's own device
    stacks); the fused fit builds the whole global batch's layout in one
    process and stays single-process."""
    import jax as _jax

    from ...ops.ell_scatter import supported as _ell_supported

    try:
        n_dev = int(np.prod(list(mesh.shape.values())))
    except Exception:
        n_dev = len(mesh.devices.flat)
    data_only = n_dev == int(mesh.shape.get("data", 0))
    procs_ok = _mesh_process_count(mesh) == 1 or allow_multiprocess
    mesh_ok = n_dev == 1 or (allow_sharded and data_only and procs_ok)
    if (_jax.default_backend() == "tpu" and mesh_ok
            and _ell_supported(num_features)
            and steps * num_features * layout_bytes_per_slot
            <= _ELL_LAYOUT_BUDGET_BYTES):
        return "ell"
    return "xla"


def _sparse_update_ell(loss_fn: LossFn, config: SGDConfig,
                       backend=None):
    """Kernel-planned twin of :func:`_sparse_update` for the generic
    (indices, values) layout: per-slot updates are ``-lr * value * r``,
    carried by the layout's value arrays (``EllLayout.val`` /
    ``ovf_val`` / value-sum ``heavy_cnt``).  Same algebra as the XLA
    path up to f32 summation order."""
    lr = config.learning_rate
    finish = _finish_sparse_step(config)

    def update(params, src, pos, mask, val_ell, ovf_idx,
               ovf_src, ovf_val, heavy_idx, heavy_cnt, yb, wb):
        w, b = params["w"], params["b"]
        margin = _ell_margin(backend, config.ell_precision, w,
                             yb.shape[0], src, pos, mask,
                             ovf_idx, ovf_src, heavy_idx, heavy_cnt,
                             val_ell=val_ell, ovf_val=ovf_val) + b
        value, pull = jax.vjp(lambda m: loss_fn(m, yb, wb), margin)
        (r,) = pull(jnp.ones_like(value))
        r_ext = _extended_r(r)

        def apply_grad(w):
            return _apply_ell_categorical(
                backend, config.ell_precision, lr, w, r, r_ext, src,
                pos, mask, ovf_idx, ovf_src, heavy_idx, heavy_cnt,
                val_ell=val_ell, ovf_val=ovf_val)

        return finish(w, b, value, r, apply_grad)

    return update


def _place_zeros(shape: tuple, mesh, spec: P) -> jnp.ndarray:
    """A zero f32 array laid out under ``spec`` — built shard-by-shard via
    ``make_array_from_callback`` so it works identically on single-host
    and process-spanning meshes (where ``device_put`` to a
    non-fully-addressable sharding is not available)."""
    sharding = NamedSharding(mesh, spec)
    return jax.make_array_from_callback(
        shape, sharding,
        lambda idx: np.zeros(sharding.shard_shape(shape), np.float32))


def _mixed_update_sharded(loss_fn: LossFn, config: SGDConfig, mesh,
                          num_features: int, n_dense: int):
    """dp x model-parallel twin of :func:`_mixed_update`: the weight is
    SHARDED over the mesh's ``model`` axis (each device owns a contiguous
    ``num_features / M`` block) so 2^24+ hash spaces never replicate —
    the embedding-table pattern of
    ``widedeep.py::build_sharded_train_step`` applied to the flat LR
    weight.  Communication per step is three small collectives, all on
    per-batch vectors, never on the weight:

    - ``psum("model")`` of each shard's partial margins (batch,)
    - ``psum("data")`` of the weighted-loss numerator/denominator pair
      (the loss_fn's weighted mean re-normalized globally, so the result
      matches the replicated path exactly)
    - ``psum("data")`` of the owned-slot update block (shard-sized; this
      is the data-parallel gradient reduction)

    Each device scatters only the categorical slots it OWNS (masked
    local indices); the dense block lives on model-rank 0's shard.
    """
    M = int(mesh.shape["model"])
    if num_features % M:
        raise ValueError(
            f"num_features={num_features} must divide the model axis "
            f"({M}); pad the hash space")
    shard = num_features // M
    if n_dense > shard:
        raise ValueError(
            f"n_dense={n_dense} exceeds the per-device weight shard "
            f"{shard}; use fewer model shards")
    lr = config.learning_rate
    finish = _finish_sparse_step(
        config,
        sumsq=lambda w: jax.lax.psum(jnp.sum(jnp.square(w)), "model"),
        rsum=lambda r: jax.lax.psum(jnp.sum(r), "data"))

    def device_fn(w_shard, b, dense, cat, yb, wb):
        # w_shard (shard,) this device's block; batch args are LOCAL rows
        mrank = jax.lax.axis_index("model")
        off = mrank * shard
        loc = cat - off
        owned = (loc >= 0) & (loc < shard)
        locc = jnp.clip(loc, 0, shard - 1)
        gathered = jnp.where(owned, w_shard[locc], 0.0)
        margin_part = jnp.sum(gathered, axis=-1)
        on0 = (mrank == 0).astype(jnp.float32)
        margin_part = margin_part + on0 * (dense @ w_shard[:n_dense])
        margin = jax.lax.psum(margin_part, "model") + b

        value_local, pull = jax.vjp(lambda m: loss_fn(m, yb, wb), margin)
        (r_local,) = pull(jnp.ones_like(value_local))
        # re-normalize the loss_fn's LOCAL weighted mean to the global
        # denominator so sharded == replicated bit-for-bit in exact math
        denom_local = jnp.maximum(jnp.sum(wb), 1e-12)
        denom = jax.lax.psum(denom_local, "data")
        value = jax.lax.psum(value_local * denom_local, "data") / denom
        r = r_local * (denom_local / denom)

        def apply_grad(w_shard):
            delta = jnp.zeros_like(w_shard).at[locc.reshape(-1)].add(
                jnp.where(owned, -lr * r[:, None], 0.0).reshape(-1))
            delta = delta.at[:n_dense].add(on0 * (-lr) * (r @ dense))
            return w_shard + jax.lax.psum(delta, "data")

        return finish(w_shard, b, value, r, apply_grad)

    fn = _shard_map(
        device_fn, mesh,
        in_specs=(P("model"), P(), P("data", None), P("data", None),
                  P("data"), P("data")),
        out_specs=({"w": P("model"), "b": P()}, P()))

    def update(params, dense, cat, yb, wb):
        return fn(params["w"], params["b"], dense, cat, yb, wb)

    return update


def sgd_fit_mixed(loss_fn: LossFn, dense_features: np.ndarray,
                  cat_indices: np.ndarray, labels: np.ndarray,
                  weights: Optional[np.ndarray], num_features: int,
                  config: SGDConfig, mesh=None) -> Tuple[LinearState, list]:
    """Criteo-native variant of :func:`sgd_fit_sparse`: ``dense_features``
    (n, n_dense) occupy weight slots ``[0, n_dense)`` and ``cat_indices``
    (n, n_cat) are hashed slots with implicit value 1.0.  The dense slots
    never pay the per-element random-access cost (see
    :func:`_mixed_update`), which is why this layout is the fastest LR
    path on TPU for mixed dense/categorical data.

    On a mesh of one device the columns are put flat and the device lays
    them out in the epoch order (:func:`_epoch_tensors`); the ELL plan's
    layout reads the categorical rows through that order on the host
    (``ell_layout(order=...)``), and every array goes to the device in
    rounds (``parallel/mesh.py: put_in_rounds``).  A fit does the same
    work every time and keeps nothing across fits.

    Multi-host: pass a process-spanning mesh (``distributed.global_mesh``)
    and call from EVERY process with that process's own equal-sized row
    shard; the global batch is the concatenation over processes and the
    gradient reduction rides ICI/DCN.  The same contract applies to
    :func:`sgd_fit` / :func:`sgd_fit_sparse`."""
    from .linear import check_sparse_indices

    mesh = mesh or default_mesh()
    n = dense_features.shape[0]
    with tracer.span("fit.gather", "fit"):
        check_sparse_indices(cat_indices, num_features)
        n_dense = dense_features.shape[1]
        if n_dense > num_features:
            raise ValueError(f"n_dense={n_dense} exceeds "
                             f"num_features={num_features}")

    with tracer.span("fit.arrange", "fit"):
        steps, batch, perm = _plan_epoch_layout_for_mesh(
            n, resolve_global_batch_size(config, n, num_features), mesh,
            config.seed)
        order = _epoch_order(perm, steps, batch)
        model_sharded = int(mesh.shape.get("model", 1)) > 1
        impl = ("sharded" if model_sharded
                else plan_mixed_impl(num_features, mesh, steps,
                                     allow_sharded=True))
        device = _one_device(mesh)
        # the columns a step reads as epoch tensors: dense, labels,
        # weights, and the categorical indices where no ELL layout
        # carries them
        columns = [np.asarray(dense_features, np.float32),
                   np.asarray(labels, np.float32),
                   np.ones((n,), np.float32) if weights is None
                   else np.asarray(weights, np.float32)]
        if impl != "ell":
            columns.append(np.asarray(cat_indices, np.int32))
        if device is None:
            with tracer.span("fit.arrange.permute", "fit"):
                tensors = [prepare_epoch_tensor(c, perm, steps, batch)
                           for c in columns]
    if device is not None:
        # the columns travel and are laid out while the host builds the
        # layout below; the flat copies are freed once laid out
        with tracer.span("fit.upload", "fit"):
            order_put, *flats = put_in_rounds(
                [order.reshape(-1)] + [c.reshape(-1) for c in columns],
                device)
        with tracer.span("fit.arrange", "fit"), \
                tracer.span("fit.arrange.permute", "fit"):
            tensors = _epoch_tensors(
                order_put, tuple(flats),
                widths=tuple(c.shape[1:] for c in columns), steps=steps)
        del order_put, flats

    with tracer.span("fit.arrange", "fit"):
        place_params = True
        init_params = {"w": jnp.zeros((num_features,), jnp.float32),
                       "b": jnp.zeros((), jnp.float32)}
        extra = ()
        if impl == "ell":
            # one-time static routing of every step's categorical slots
            # (amortised over max_epochs replays of the same epoch
            # order).  Off one device each device routes its own shard:
            # slot sources are numbered inside its local (batch/n_dev)-row
            # shard, and the stacks gain a device dim sharded over 'data'
            from ...ops.ell_scatter import ell_layout

            shards = 1 if device is not None else int(mesh.shape["data"])
            with tracer.span("fit.arrange.ell_layout", "fit") as span:
                lay = ell_layout(cat_indices, num_features, device=False,
                                 order=order.reshape(steps * shards, -1))
                span.note(need_ovf=int(lay.need_ovf.max()),
                          need_heavy=int(lay.need_heavy.max()))
            extra = (lay.src, lay.pos, lay.mask, lay.ovf_idx, lay.ovf_src,
                     lay.heavy_idx, lay.heavy_cnt)
            if device is not None:
                factory = _mixed_update_ell
                update = factory(loss_fn, config)
            else:
                extra = tuple(a.reshape((steps, shards) + a.shape[1:])
                              for a in extra)
                factory = _mixed_update_ell_sharded
                update = factory(loss_fn, config, mesh, num_features)
        elif impl == "sharded":
            # weight sharded over the model axis (2^24+ hash spaces never
            # replicate); see _mixed_update_sharded
            factory = _mixed_update_sharded
            update = factory(loss_fn, config, mesh, num_features, n_dense)
            init_params = {
                "w": _place_zeros((num_features,), mesh, P("model")),
                "b": _place_zeros((), mesh, P()),
            }
            place_params = False
        else:
            factory = _mixed_update
            update = factory(loss_fn, config)

    with tracer.span("fit.upload", "fit"):
        if device is not None:
            extra = tuple(put_in_rounds(extra, device))
        else:
            row_specs = [P(None, "data", None), P(None, "data"),
                         P(None, "data"), P(None, "data", None)]
            tensors = [_put_epoch_tensor(t, mesh, spec)
                       for t, spec in zip(tensors, row_specs)]
            layout_specs = ([P(None, "data", None, None)] * 3
                            + [P(None, "data", None)] * 3
                            + [P(None, "data", None, None)])
            extra = tuple(_put_epoch_tensor(a, mesh, spec)
                          for a, spec in zip(extra, layout_specs))
    dense, y, w = tensors[:3]
    params, loss_log = _run_minibatch_epochs(
        update, (dense,) + tuple(tensors[3:]) + extra + (y, w),
        init_params, steps, config, mesh, place_params=place_params,
        program_key=_epoch_program_key(
            factory, loss_fn, config, mesh, steps,
            num_features // _BLOCK_LANES if impl == "ell" else None))
    return LinearState(np.asarray(params["w"], np.float64),
                       float(params["b"]), planned_impl=impl), loss_log


def _reader_for_epoch(make_reader: Callable, epoch: int,
                      retry_policy=None):
    """Call the per-epoch reader factory, passing ``epoch=`` when the
    factory accepts it.  Per-epoch shuffled readers
    (``data.datacache.ShuffledCacheReader``) need the ACTUAL epoch number
    — a call-counting closure would desynchronize on checkpoint resume,
    which restarts mid-training at an arbitrary epoch.  Zero-arg
    factories keep working unchanged.

    ``retry_policy`` wraps the returned reader so transient pull
    failures retry with backoff.  The wrap happens HERE — at the raw
    reader, below the fit's generator adapters — because a generator
    that propagates an exception is dead forever: retrying above one
    would turn a healed transient into a silently truncated epoch
    (``robustness.retry.RetryingIterator``)."""

    def build():
        try:
            sig = inspect.signature(make_reader)
        except (TypeError, ValueError):
            return make_reader()
        for p in sig.parameters.values():
            # only an explicitly named, keyword-passable `epoch` opts in:
            # a bare **kwargs factory must NOT be force-fed an argument it
            # merely forwards, and a positional-only `epoch` cannot take
            # the keyword call
            if p.name == "epoch" and p.kind in (
                    inspect.Parameter.POSITIONAL_OR_KEYWORD,
                    inspect.Parameter.KEYWORD_ONLY):
                return make_reader(epoch=epoch)
        return make_reader()

    reader = build()
    if retry_policy is None:
        return reader
    from ...robustness.retry import RetryingIterator

    return RetryingIterator(reader, retry_policy)


def _has_cursor(reader) -> bool:
    """The DataCacheReader cursor protocol: seekable, fixed batch size,
    known length — the contract ``sgd_fit_outofcore`` relies on for
    checkpoint fast-forward and decoded-replay eligibility."""
    return (hasattr(reader, "seek") and hasattr(reader, "batch_rows")
            and hasattr(reader, "total_rows"))


def _seek_or_skip(reader, k: int):
    """Position a fresh reader ``k`` batches in: seek when it speaks the
    cursor protocol, else discard batches.  Returns an iterator."""
    if hasattr(reader, "seek") and hasattr(reader, "batch_rows"):
        rows = k * reader.batch_rows
        total = getattr(reader, "total_rows", None)
        reader.seek(rows if total is None else min(rows, total))
        return iter(reader)
    it = iter(reader)
    for _ in range(k):
        try:
            next(it)
        except StopIteration:
            break
    return it


# ---------------------------------------------------------------------------
# Step-program compile cache.  Crash->resume, elastic resize, and A/B
# refits re-enter sgd_fit_outofcore many times per process with the same
# (loss, config, mesh, layout); the update/chunk closures are pure
# functions of those inputs, so re-jitting a fresh closure per call pays
# the full XLA compile again for a program that cannot differ.  Keyed by
# value (SGDConfig is mutable — hash its field tuple, recursing into the
# frozen GradReduceConfig) plus the mesh's axis extents and device ids;
# an unhashable key (exotic loss object, custom grad_reduce) just skips
# the cache.  Bounded LRU so a long-lived trainer cycling many configs
# does not retain every executable forever.
_STEP_PROGRAM_CACHE: "OrderedDict" = OrderedDict()
_STEP_PROGRAM_CACHE_CAP = 64


def _step_program_key(kind: tuple, loss_fn, config: SGDConfig, mesh):
    """Hashable identity of a compiled step program, or None to skip.

    Only the config fields the update closures consume participate —
    host-loop knobs (max_epochs, tol, seed, batch size) must NOT
    fragment the key, or a refit at a different epoch budget would
    recompile an identical program.
    """
    gr = config.grad_reduce
    try:
        key = (kind, loss_fn,
               float(config.learning_rate), float(config.reg),
               float(config.elastic_net), bool(config.fit_intercept),
               str(config.ell_precision),
               type(gr).__name__, astuple(gr) if is_dataclass(gr) else gr,
               tuple(str(a) for a in mesh.axis_names),
               tuple(int(mesh.shape[a]) for a in mesh.axis_names),
               tuple(int(d.id) for d in np.ravel(mesh.devices)))
        hash(key)
    except Exception:
        return None
    return key


def _cached_step_program(key, build: Callable):
    """Return the cached jitted callable for ``key``, building on miss.

    Reusing the jit wrapper (not just the traced program) keeps XLA's
    per-shape executable cache attached to it, so a cache hit skips both
    the re-trace and the re-compile; donation semantics are per-call and
    unaffected by reuse.
    """
    if key is None:
        return build()
    fn = _STEP_PROGRAM_CACHE.get(key)
    if fn is None:
        fn = build()
        _STEP_PROGRAM_CACHE[key] = fn
        if len(_STEP_PROGRAM_CACHE) > _STEP_PROGRAM_CACHE_CAP:
            _STEP_PROGRAM_CACHE.popitem(last=False)
    else:
        _STEP_PROGRAM_CACHE.move_to_end(key)
    return fn


def sgd_fit_outofcore(loss_fn: LossFn, make_reader: Callable, *,
                      num_features: int, config: SGDConfig, mesh=None,
                      features_key: str = "features",
                      label_key: str = "label",
                      weight_key: Optional[str] = None,
                      indices_key: Optional[str] = None,
                      values_key: Optional[str] = None,
                      dense_key: Optional[str] = None,
                      prefetch_depth: int = 2,
                      prefetch_workers: int = 1,
                      prefetch_put_workers: int = 1,
                      prefetch_stats=None,
                      steps_per_dispatch: int = 8,
                      cache_decoded="auto",
                      decoded_ram_budget: Optional[int] = None,
                      stream_info: Optional[dict] = None,
                      ell_ovf_cap: Optional[int] = None,
                      ell_heavy_cap: int = 16,
                      checkpoint=None,
                      checkpoint_every_steps: int = 0,
                      resume: bool = False,
                      retry_policy=None,
                      publish_cb: Optional[Callable] = None,
                      step_probe: bool = False,
                      membership=None
                      ) -> Tuple[LinearState, list]:
    """Out-of-core variant of :func:`sgd_fit`: the dataset never has to fit
    in host RAM or HBM (the Criteo-1TB shape, BASELINE.md north star).

    ``make_reader()`` is called once per epoch and must return a fresh
    iterator of host batch dicts with fixed row count per batch (e.g.
    ``DataCacheReader(..., batch_rows=B)`` re-seeked to 0 — its fadvise
    readahead covers the disk side).  Batches are padded to the first
    batch's row count (padding rows carry weight 0), transferred via
    :func:`prefetch_to_device` so the host read/decode and the HBM transfer
    of batch N+1 overlap the jitted step on batch N, and consumed by one
    compiled update program — static shapes, zero recompiles across the
    epoch.

    With ``indices_key``/``values_key`` set the reader feeds **sparse**
    batches — ``(rows, nnz)`` hashed index/value pairs scored against the
    dense ``(num_features,)`` weight (the :func:`sgd_fit_sparse` layout);
    ``features_key`` is ignored.  With ``dense_key``+``indices_key`` the
    reader feeds the **mixed** Criteo-native layout instead — a dense
    block plus hashed categorical indices with implicit value 1.0 (the
    :func:`sgd_fit_mixed` layout, the fastest LR path on TPU).  Either
    way 2^20+ dims stream from disk without ever densifying.  On a
    single TPU device the mixed path plans the ELL scatter kernel: each
    batch's static routing builds in the prefetch decode workers
    (overlapping the device step) with fixed capacities
    (``ell_ovf_cap``/``ell_heavy_cap`` — one compiled program for every
    batch; an over-cap batch raises with sizing guidance).  The default
    ``ell_ovf_cap`` is deliberately generous (``max(1024, batch)``)
    because the cap cannot change mid-stream; the XLA overflow
    scatter's cost scales with the STATIC cap (~0.2 us per cap slot per
    step, r4 chip run), so deployments whose collision rate
    is known should pass a tight ``ell_ovf_cap`` — in-memory fits size
    it from the measured need automatically.

    Unlike :func:`sgd_fit`, the READER owns the data layout:
    ``config.global_batch_size`` and ``config.seed`` are inert here — batch
    size is the reader's ``batch_rows`` and any shuffling must happen in the
    reader (e.g. shuffle when writing the cache, or shuffle segment order
    per epoch).

    **Chunked dispatch** (``steps_per_dispatch=W``, default 8): ``W``
    consecutive prefetched batches are stacked on the host into one
    device chunk (the prefetch pipeline's ``chunks=W`` mode — the
    ``device_put`` of chunk N+1 overlaps compute on chunk N) and one
    jitted ``lax.scan`` with a donated carry runs all ``W`` optimizer
    steps, so an epoch costs ``ceil(n_batches / W)`` dispatches instead
    of ``n_batches`` — the fixed per-dispatch host cost amortizes
    ``W``-fold (how large that cost is on a local TPU is unverified:
    ROADMAP S1).  The final
    short chunk pads with a validity mask whose dead steps freeze the
    carry, so results are BIT-EXACT vs ``W=1`` (asserted in tests);
    mid-epoch checkpoint cuts land at chunk boundaries.  Process-
    spanning meshes force ``W=1`` (chunk assembly is per-process-local).
    The pipeline runs at ``ceil(prefetch_depth / W)`` CHUNKS of depth,
    floored at ONE — so chunked mode keeps at least ``W`` batches
    staged (plus the ``W``-batch chunk in compute), a ~``W/3``-fold
    device-staging increase over the classic per-batch pipeline at the
    default ``prefetch_depth=2``; memory-constrained deployments bound
    the footprint by lowering ``steps_per_dispatch`` (``W=1``
    reproduces the old footprint), and host-side assembly stages up to
    ``W`` decoded batches per in-flight chunk.  Dead (padded) steps
    COMPUTE and discard — the price of one compiled program for every
    chunk — so keep ``W`` well under the epoch's batch count: a 4-batch
    epoch at ``W=8`` runs 8 steps' compute for 4 batches' progress.

    A factory that accepts an ``epoch`` keyword is called
    with the actual epoch number — pair it with
    :class:`~...data.datacache.ShuffledCacheReader` for per-epoch
    reshuffling that stays exact across checkpoint resume (a
    call-counting closure would desynchronize, since resume restarts at
    an arbitrary epoch).

    **Multi-host** (r4): pass a process-spanning mesh and call from EVERY
    process with a reader over THAT process's data shard (the reference's
    parallelism-P source posture — each TaskManager reads its own split).
    The global batch is the per-step concatenation over processes in
    process order, assembled inside the prefetch pipeline
    (``make_array_from_process_local_data``); the gradient reduction rides
    the mesh like the in-memory fits.  SPMD contract: every process must
    deliver the SAME number of equal-sized batches per epoch — mismatched
    readers deadlock in the collectives.  The ELL streaming path works
    across processes too: each host's decode workers build the layouts
    for its OWN devices' row blocks, and the assembled global stacks
    drive the device-local-grid + psum update.

    **Decoded replay cache** (r4): multi-epoch streams pay the host decode
    (pad + casts + ELL routing build) once, not once per epoch — the first
    full epoch tees each decoded batch into host RAM up to
    ``decoded_ram_budget`` bytes (default: 25% of available RAM, capped at
    32 GiB), and later epochs replay the cached prefix straight into the
    ``device_put`` stage, re-decoding only the tail that did not fit.
    This is the TPU-native analog of the reference's replay path — round 0
    writes while passing through, later rounds re-read instead of
    re-running the upstream (``iteration/operator/ReplayOperator.java:62-311``)
    — lifted from raw records to *decoded* batches because on this host
    the decode, not the read, dominates.  ``cache_decoded="auto"`` (default) engages only
    when the reader speaks the cursor protocol (``seek``/``batch_rows``/
    ``total_rows``), and every replay epoch re-reads the FIRST raw batch
    and compares its digest against the recorded epoch's — a reader that
    legitimately varies its stream per epoch (re-shuffled segment order,
    per-epoch sampling) drops the cache and decodes normally instead of
    silently training on frozen epoch-0 data.  The guard is one batch
    deep: a reader that keeps batch 0 identical while reordering the
    rest defeats it — such readers should either declare
    ``epoch_varying = True`` or be run with ``False``.  Epoch-varying
    readers that are also BLOCK-ADDRESSABLE (``block_order`` — the
    :class:`ShuffledCacheReader` protocol) get the best of both:
    entries are keyed by block id, every epoch serves cached blocks in
    that epoch's fresh permutation and decodes+offers the misses, so
    reshuffling and decode-once compose (one raw-digest contract check
    per epoch on an anchor block catches readers whose block content
    drifts).  Epoch-varying readers WITHOUT ``block_order`` are simply
    never cached under "auto".  ``True`` forces
    caching for any reader with no probe (the caller owns the
    determinism guarantee), ``False`` disables.  A tripped guard latches
    recording off for the rest of the fit (a varying reader would just
    be dropped again every epoch).  Recording retains the decode
    outputs zero-copy; disk-backed views (memmap slices that pass
    through the decode uncopied) are materialized into RAM at tee time
    so the budget counts real RAM and replay never faults to disk.  ``stream_info`` (a dict, filled in place) reports the planned
    impl, cached batch count/bytes, and per-epoch wall seconds so callers
    can attribute record vs replay epochs.

    **Mid-epoch checkpoints** (``checkpoint`` + ``checkpoint_every_steps``):
    on a 1TB pass one epoch is hours, so an epoch-boundary-only cut (the
    ``iterate`` default) loses the whole pass on a crash — the reference
    checkpoints *inside* a superstep for the same reason
    (``checkpoint/Checkpoints.java:43-211``,
    ``operator/HeadOperator.java:323-335``).  Every
    ``checkpoint_every_steps`` batches the (params, loss accumulator,
    reader cursor) triple is cut; ``resume=True`` restarts exactly at that
    batch: the reader is re-seeked (``seek``/``batch_rows`` protocol — the
    ``DataCacheReader`` surface — or by skipping batches) and the epoch
    continues as if never interrupted — deterministic-replay exactness is
    asserted in tests/test_checkpoint.py.  Checkpoint cuts are validated
    (CRC manifest + commit marker): on resume a torn/corrupt newest cut
    is quarantined and the fit falls back to the previous valid one
    (``CheckpointManager.latest()``); ``robustness.resilient_fit`` wraps
    this fit to make the whole crash->restore->replay loop automatic.

    **Chunk-boundary publishes** (``publish_cb``): called as
    ``publish_cb(global_step, params_fn)`` at every cut point — each
    ``checkpoint_every_steps`` crossing and each epoch boundary, right
    AFTER the checkpoint save when a manager is attached, so the
    published state is never ahead of the durable one.  ``params_fn``
    is a ZERO-ARG thunk returning the cut's host ``{"w", "b"}`` pytree
    (reducer state stripped): the device->host fetch (a dispatch-stream
    fence) is paid only when the callback actually publishes, not at
    cuts its cadence policy skips.  The thunk must be consumed INSIDE
    the callback — the underlying buffers are donated to the next
    dispatch.  The train-while-serve driver
    (``flink_ml_tpu/online/driver.py``) encodes the result as a param
    delta and swaps it into the live serving generation.
    With an overlapped ``grad_reduce`` the published cut intentionally
    excludes the fit-end drain (the in-loop trajectory — the same state
    a checkpoint of that cut holds, which is what keeps crash->resume->
    republish bit-exact).

    **Retry** (``retry_policy``, a ``robustness.retry.RetryPolicy``):
    each epoch's reader is wrapped in a ``RetryingIterator`` — the wrap
    sits at the RAW reader, below the fit's generator adapters, so a
    healed transient can never kill the stream — and classified-
    transient pull failures cost a backoff sleep on the prefetch reader
    thread instead of the epoch; fatal errors still propagate (and then
    checkpoint-based recovery is the healing layer, not retry).  The
    reader must not consume a batch on a failed pull, or be idempotent
    at the failed position (seekable readers are).

    **Elastic membership** (``membership=``, an
    :class:`~flink_ml_tpu.parallel.elastic.ElasticCoordinator`): the
    fleet becomes a runtime input.  Once per chunk boundary the fit
    calls ``membership.poll(global_step)`` — the seam injected
    ``preempt``/``join`` faults and lease expiry flow through — and
    when membership moved, it cuts a boundary checkpoint (carrying
    mesh-shape metadata) and raises
    :class:`~flink_ml_tpu.parallel.elastic.ResizeRequested`:
    ``resilient_fit(elastic=...)`` rebuilds the mesh at the new dcn
    extent and re-enters with ``resume=True``, where the restore below
    re-shards the whole carry (params replicate; participant-stacked
    reducer state — EF residual, pending overlap buffer, adaptive
    policy, rounding keys, and the wire-protocol tier's per-round
    fill-in/union accounting — routes through
    :func:`~flink_ml_tpu.parallel.grad_reduce.reshard_state`).  A
    resize at a chunk boundary is bit-exact vs a fixed fleet of the
    new size restoring the same cut (same reduce order); a worker
    death mid-chunk degrades to the crash path and resumes onto the
    surviving fleet.  Elastic fits are single-process and dense-layout
    (the mixed/sparse ELL paths keep their fixed meshes for now); with
    no ``grad_reduce`` the batch shards over EVERY mesh axis jointly
    (dcn x data — exact data parallelism over the whole fleet), with a
    hierarchical ``grad_reduce`` the existing dcn-composed layout
    already does.

    **Step probe** (``step_probe=True``, ISSUE 13): a
    :class:`~flink_ml_tpu.obs.StepProbe` rides the donated chunk carry
    recording the per-step ``loss`` — zero host sync inside the scan
    (the probe is frozen on dead padded steps like the state, so the
    series is W-independent) and ONE batched device->host transfer per
    chunk boundary.  The concatenated per-step series lands in
    ``stream_info["step_trace"]`` (``{"loss": np.ndarray}``).  Chunked
    single-process fits only — the per-batch multi-host loop already
    fetches per step, so a probe would add nothing there (raises).
    """
    from ...parallel.mesh import local_axis_multiple

    mesh = mesh or default_mesh()
    n_dev = int(mesh.shape["data"])
    procs = _mesh_process_count(mesh)
    # each PROCESS runs its own reader over its own data shard; the
    # global batch is the concatenation over processes (the reference's
    # parallelism-P source posture).  Local rows pad to the local device
    # multiple along the DATA axis (clear errors for bad layouts live in
    # local_axis_multiple); every process must deliver the SAME batch
    # count per epoch (the SPMD contract — mismatches deadlock in the
    # collectives).
    n_local_dev = local_axis_multiple(mesh, "data")
    mixed = dense_key is not None and indices_key is not None
    sparse = indices_key is not None and not mixed
    if sparse and values_key is None:
        raise ValueError("indices_key requires values_key (or dense_key "
                         "for the mixed layout)")
    if dense_key is not None and indices_key is None:
        raise ValueError("dense_key requires indices_key")
    # mixed batches on a TPU data mesh route through the ELL kernel: the
    # per-batch routing builds in the PREFETCH decode workers, so the
    # host sort overlaps the device step like any other decode work.
    # Caps are static (one compiled program for every batch).  On a
    # multi-device data axis the decode builds PER-DEVICE shard layouts
    # and the update is the device-local-grid + psum variant (same
    # stance as the fused sgd_fit_mixed, r4).
    gr = _active_grad_reduce(config)
    if gr is not None and (mixed or sparse):
        # categorical/sparse layouts already ship sparse gradients by
        # construction (scatter supports bounded by the batch's slots);
        # compressing them again would pay EF state for nothing
        raise ValueError(
            "grad_reduce compression applies to the dense streaming "
            "layout; the sparse/mixed paths' gradients are already "
            "sparse by construction — drop grad_reduce or use the dense "
            "features layout")
    gr_batch_axis = "data"
    n_dev_red = n_dev
    if gr is not None:
        gr_axes, n_dev_red, gr_batch_axis = _grad_reduce_layout(gr, mesh)
        if gr_axes != ("data",):
            if procs > 1:
                raise ValueError(
                    "hierarchical grad_reduce streaming is single-process "
                    "for now; multi-host hybrid meshes reduce over the "
                    "data axis per host")
            # the batch shards over every reduction axis jointly
            n_local_dev = n_dev_red
    if membership is not None:
        if procs > 1:
            raise ValueError(
                "elastic membership is single-process: the coordinator "
                "owns the device pool of THIS process (multi-host "
                "elasticity needs a control plane, not a mesh reshape)")
        if mixed or sparse:
            raise ValueError(
                "elastic membership supports the dense streaming layout; "
                "the mixed/sparse ELL paths bake per-device routing into "
                "their compiled programs and keep a fixed mesh for now")
        if gr is None and len(mesh.axis_names) > 1:
            # exact data parallelism over the whole fleet: the batch
            # shards over every mesh axis jointly (dcn x data), so a
            # resized dcn extent changes the shard count, not the math
            gr_batch_axis = tuple(str(a) for a in mesh.axis_names)
            n_local_dev = int(np.prod([int(mesh.shape[a])
                                       for a in mesh.axis_names]))
            n_dev_red = n_local_dev
        elif gr is not None and membership.dcn_axis in mesh.shape \
                and membership.dcn_axis not in gr_axes:
            # a flat compressed config on an elastic (dcn, data) mesh
            # would silently REPLICATE the batch over the resizable
            # axis — every worker doing identical work, no elasticity
            raise ValueError(
                f"elastic membership with grad_reduce must reduce over "
                f"the elastic axis {membership.dcn_axis!r}: set "
                f"dcn_axis={membership.dcn_axis!r} (hierarchical) on "
                "the GradReduceConfig, or drop grad_reduce for the "
                "exact joint-sharded path")
    stream_ell = (mixed and plan_mixed_impl(
        num_features, mesh, allow_sharded=True,
        allow_multiprocess=True) == "ell")
    stream_sharded = stream_ell and n_dev > 1
    stream_impl = ("ell-stream" if stream_ell
                   else ("xla-stream" if (mixed or sparse)
                         else ("dense-stream-reduced" if gr is not None
                               else "dense-stream")))
    if stream_sharded:
        update = _mixed_update_ell_sharded(
            loss_fn, config, mesh, num_features)
    elif stream_ell:
        update = _mixed_update_ell(loss_fn, config)
    elif gr is not None:
        update = _linear_update_reduced(loss_fn, config, mesh)
    else:
        update = (_mixed_update(loss_fn, config) if mixed
                  else (_sparse_update if sparse
                        else _linear_update)(loss_fn, config))
    # mixed and sparse both plan "xla-stream" but build different update
    # closures, so the layout flags join the key alongside the impl name
    layout_sig = (stream_impl, bool(mixed), bool(sparse), num_features)
    step_key = _step_program_key(("outofcore-batch",) + layout_sig,
                                 loss_fn, config, mesh)
    batch_step = _cached_step_program(
        step_key, lambda: jax.jit(update, donate_argnums=0))

    manager: Optional[CheckpointManager] = None
    if isinstance(checkpoint, CheckpointManager):
        manager = checkpoint
    elif isinstance(checkpoint, CheckpointConfig):
        manager = CheckpointManager(checkpoint)
    if membership is not None and manager is None:
        raise ValueError(
            "elastic membership requires a checkpoint manager: a resize "
            "IS a restore onto the new mesh, so without durable cuts "
            "there is nothing to resize from")

    x_p = P(gr_batch_axis, None)
    v_p = P(gr_batch_axis)
    if stream_sharded:
        # layout stacks carry a leading device dim sharded over 'data'
        g3, g2 = P("data", None, None), P("data", None)
        specs = (x_p, g3, g3, g3, g2, g2, g2, g3, v_p, v_p)
    elif stream_ell:
        r_p = P()  # layout grids: single device
        # (dense, src, pos, mask, ovf_idx, ovf_src, heavy_idx,
        #  heavy_cnt, y, w) — the raw cat tensor never ships: margins
        # and scatters both ride the layout (r4)
        specs = (x_p, r_p, r_p, r_p, r_p, r_p, r_p, r_p, v_p, v_p)
    else:
        specs = ((x_p, x_p, v_p, v_p) if (sparse or mixed)
                 else (x_p, v_p, v_p))
    # process-spanning mesh: each process's decoded batch is its LOCAL
    # slice; assemble the global (non-fully-addressable) batch arrays
    put_fn = _assemble_process_local if procs > 1 else None

    # Chunked dispatch: W batches stack into one device chunk and run as
    # one donated-carry lax.scan — one dispatch per W steps.  W=1 is the
    # exact-equivalence fallback: one batch per dispatch through the
    # SAME scan program, so any two W values are bit-exact on the same
    # stream (XLA compiles the per-batch jit and the scan body slightly
    # differently, so sameness of the PROGRAM, not just the math, is
    # what the guarantee rides on).  Chunk assembly is per-process-
    # local, so process-spanning meshes keep the classic per-batch loop.
    W = max(1, int(steps_per_dispatch))
    chunked = procs == 1
    if step_probe and not chunked:
        raise ValueError(
            "step_probe=True needs the chunked single-process path: the "
            "per-batch multi-host loop dispatches per step already, so "
            "a probe would only duplicate what the host loop sees")
    if chunked:
        from ...data.prefetch import chunk_consumer_plan, masked_chunk_scan

        sharding, chunk_depth = chunk_consumer_plan(mesh, specs, W,
                                                    prefetch_depth)
        chunk_key = _step_program_key(
            ("outofcore-chunk",) + layout_sig + (bool(step_probe),),
            loss_fn, config, mesh)
        if step_probe:
            # the probe joins the donated carry (argnums 0-2): each
            # chunk's returned probe is fetched ONCE at the boundary and
            # a reset() probe (fresh buffers) feeds the next dispatch,
            # so donation never aliases a buffer the host still reads
            chunk_step = _cached_step_program(chunk_key, lambda: jax.jit(
                lambda params, loss_sum, probe, chunk, mask:
                masked_chunk_scan(update, params, loss_sum, chunk, mask,
                                  probe=probe),
                donate_argnums=(0, 1, 2)))
        else:
            chunk_step = _cached_step_program(chunk_key, lambda: jax.jit(
                lambda params, loss_sum, chunk, mask: masked_chunk_scan(
                    update, params, loss_sum, chunk, mask),
                donate_argnums=(0, 1)))
    else:
        W = 1
        sharding = tuple(NamedSharding(mesh, p) for p in specs)

    from ...utils.padding import FixedRowBatcher

    batcher = FixedRowBatcher(n_local_dev)   # shared fixed-row protocol

    def to_host_batch(batch):
        if sparse or mixed:
            from .linear import check_sparse_indices

            idx = np.asarray(batch[indices_key], np.int32)
            check_sparse_indices(idx, num_features)
            if mixed:
                feats = (np.asarray(batch[dense_key], np.float32), idx)
            else:
                feats = (idx, np.asarray(batch[values_key], np.float32))
        else:
            feats = (np.asarray(batch[features_key], np.float32),)
        y = np.asarray(batch[label_key], np.float32)
        w = (np.asarray(batch[weight_key], np.float32) if weight_key
             else np.ones((y.shape[0],), np.float32))
        # final partial batch: pad, weight 0 (batcher pins thread-safely)
        padded = batcher.pad(feats + (y, w), have=y.shape[0])
        if stream_ell:
            from ...ops.ell_scatter import ell_layout

            dense_p, cat_p = padded[0], padded[1]
            n_valid = y.shape[0]
            if n_valid < batcher.rows:
                # padding rows' indices become sentinels the layout
                # drops (zero-pads would fabricate a heavy index 0);
                # their margins are dense-part-only and carry weight 0
                cat_p = cat_p.copy()
                cat_p[n_valid:] = num_features
            if stream_sharded:
                # per-device shard layouts: slot sources numbered inside
                # each device's contiguous local row block (P("data")
                # shards dim 0 the same way)
                local = batcher.rows // n_local_dev
                cap = (ell_ovf_cap if ell_ovf_cap is not None
                       else max(1024, local))
                lay = ell_layout(
                    cat_p.reshape(n_local_dev, local, cat_p.shape[-1]),
                    num_features, pad_ovf_cap=cap,
                    pad_heavy_cap=ell_heavy_cap, device=False)
                return (dense_p,
                        lay.src, lay.pos, lay.mask, lay.ovf_idx,
                        lay.ovf_src, lay.heavy_idx,
                        lay.heavy_cnt) + padded[2:]
            cap = (ell_ovf_cap if ell_ovf_cap is not None
                   else max(1024, batcher.rows))
            lay = ell_layout(cat_p[None], num_features,
                             pad_ovf_cap=cap,
                             pad_heavy_cap=ell_heavy_cap, device=False)
            return (dense_p,
                    lay.src[0], lay.pos[0], lay.mask[0], lay.ovf_idx[0],
                    lay.ovf_src[0], lay.heavy_idx[0],
                    lay.heavy_cnt[0]) + padded[2:]
        return padded

    if cache_decoded not in (True, False, "auto"):
        raise ValueError('cache_decoded must be True, False, or "auto", '
                         f"got {cache_decoded!r}")
    replay_cache: Optional[DecodedReplayCache] = None
    guard_tripped = False       # replay guard found an epoch-varying reader
    recorded_epochs = 0
    _rec_cache: list = [None]   # this epoch's recording target (closure slot)
    # block-keyed mode (epoch-varying + block-addressable readers, e.g.
    # ShuffledCacheReader): reshuffle every epoch AND amortize decode —
    # the cache keys entries by BLOCK id, serving hits and
    # decoding+offering misses, with no record/replay phase boundary.
    # `block_mode` is decided once, at the fit's first reader.
    block_mode: Optional[bool] = None
    block_cache: Optional[DecodedReplayCache] = None

    def route(item):
        """Prefetch transform over tagged source items: ``("dec", t)`` is
        an already-decoded replay batch, ``("rec", i, b)`` decodes + tees
        into the recording cache, ``("raw", b)`` just decodes."""
        tag = item[0]
        if tag == "dec":
            return item[1]
        if tag == "blk":
            bid, raw = item[1], item[2]
            cached = block_cache.get(bid)
            if cached is not None:
                if bid == block_cache.anchor_key:
                    # per-block-determinism contract check, one block
                    # per epoch: a reader whose block content drifts
                    # between epochs must fail loudly, not train on
                    # stale decode outputs
                    if batch_fingerprint(raw) != block_cache.fingerprint:
                        raise ValueError(
                            f"block-addressable reader violated the "
                            f"block_order contract: block {bid}'s "
                            f"content changed between epochs; pass "
                            f"cache_decoded=False for such readers")
                return cached
            host = to_host_batch(raw)
            if block_cache.anchor_key is None:
                # digest only until an anchor exists — hashing every
                # miss would tax the decode path the cache shrinks
                block_cache.set_anchor(bid, batch_fingerprint(raw))
            block_cache.offer(bid, host)
            return host
        if tag == "rec":
            if item[1] == 0:
                # digest the raw (pre-decode) batch: the replay guard
                # re-reads batch 0 on later epochs and compares
                _rec_cache[0].fingerprint = batch_fingerprint(item[2])
            elif item[1] & (item[1] - 1) == 0:
                # power-of-two indices: cheap (log n hashes) mid-stream
                # anchors for the seekable replay guard's second probe
                _rec_cache[0].probe_fingerprints[item[1]] = \
                    batch_fingerprint(item[2])
            host = to_host_batch(item[2])
            _rec_cache[0].offer(item[1], host)
            return host
        return to_host_batch(item[1])

    init_params = {"w": jnp.zeros((num_features,), jnp.float32),
                   "b": jnp.zeros((), jnp.float32)}
    if gr is not None:
        from ...parallel import grad_reduce as GR

        # reducer state (EF residual / rounding key) joins the params
        # carry: every mid-epoch checkpoint cut and restore below
        # round-trips it with the weights for free
        init_params[GR_STATE_KEY] = GR.init_state(
            gr, {"w": init_params["w"], "b": init_params["b"]}, n_dev_red)
    params = replicate(init_params, mesh)
    loss_log: list = []
    prev_loss = float("inf")
    start_epoch = 0
    skip_steps = 0          # batches already consumed in start_epoch
    resume_loss_sum = None  # their accumulated loss
    resume_n_batches = 0
    global_step = 0         # checkpoint tick: total batches over all epochs
    add = jax.jit(jnp.add)

    if manager is not None and resume:
        restored = manager.restore_latest()
        if restored is not None:
            # NOTE: restored[0] is meta["epoch"] — the manager's save-slot
            # key, which our "train_epoch" meta key deliberately does NOT
            # collide with: the slot key is the global step, so post-resume
            # saves keep ascending and GC never deletes newer checkpoints.
            global_step, saved, meta = restored
            saved_params = saved["params"]
            if gr is not None and isinstance(saved_params, dict):
                from ...iteration.checkpoint import require_fleet_compat
                from ...parallel import grad_reduce as GR

                n_saved = GR.state_participants(
                    saved_params.get(GR_STATE_KEY))
                if n_saved is not None and n_saved != n_dev_red:
                    # resize-as-restore: the cut came from a different
                    # fleet — legal only when it says which one
                    # (mesh-shape metadata); the participant-stacked
                    # reducer state re-shards onto the new extent
                    require_fleet_compat(
                        meta, saved_participants=n_saved,
                        current_participants=n_dev_red,
                        path=manager.config.directory)
                    ici = (int(mesh.shape[gr.axis])
                           if gr.dcn_axis is not None else 1)
                    saved_params = dict(saved_params)
                    saved_params[GR_STATE_KEY] = GR.reshard_state(
                        saved_params[GR_STATE_KEY], n_dev_red,
                        ici_size=ici)
            params = replicate(jax.tree_util.tree_map(jnp.asarray,
                                                      saved_params), mesh)
            start_epoch = int(meta["train_epoch"])
            skip_steps = int(meta["step_in_epoch"])
            resume_n_batches = int(meta["n_batches"])
            if resume_n_batches:
                resume_loss_sum = jnp.asarray(saved["loss_sum"], jnp.float32)
            prev_loss = float(meta["prev_loss"])
            loss_log = list(meta["loss_log"])
            if meta.get("converged"):
                # The checkpointed run had already hit the tol stop:
                # continuing would train past the converged answer.
                host = jax.device_get(saved["params"])
                host_gr = host.pop(GR_STATE_KEY, None)
                if gr is not None and host_gr is not None:
                    from ...parallel import grad_reduce as GR

                    if GR.wants_overlap(gr):
                        # the original run drained at ITS return; a
                        # converged resume must reproduce that return
                        host = _apply_drain(host, host_gr, config)
                return LinearState(np.asarray(host["w"], np.float64),
                                   float(host["b"]),
                                   planned_impl=stream_impl), loss_log

    def _publish_params(params):
        """Host copy of the cut's params for ``publish_cb`` — reducer
        state (EF residual / pending) is trainer-internal, never
        served."""
        host = jax.device_get(_fetch_replicated(params))
        if isinstance(host, dict):
            host = {k: v for k, v in host.items() if k != GR_STATE_KEY}
        return host

    def _save(epoch, step_in_epoch, loss_sum, n_batches, converged=False):
        from ...iteration.checkpoint import mesh_shape_meta

        manager.save(global_step, {
            "params": params,
            "loss_sum": (loss_sum if loss_sum is not None
                         else jnp.zeros((), jnp.float32)),
        }, {
            "train_epoch": epoch, "step_in_epoch": step_in_epoch,
            "n_batches": n_batches, "prev_loss": prev_loss,
            "loss_log": loss_log, "converged": converged,
            # fleet identity: what a restore onto a DIFFERENT mesh
            # (elastic resize) needs to know it is re-sharding from
            **mesh_shape_meta(mesh, participant_count=n_dev_red),
        })

    epoch_secs: list = []
    dispatch_log: list = []   # jitted-step dispatches per epoch
    probe = None
    step_trace: Dict[str, list] = {}
    if step_probe:
        from ...obs.probe import StepProbe

        probe = StepProbe.create(("loss",), W)
    for epoch in range(start_epoch, config.max_epochs):
        t_epoch = time.perf_counter()
        rec_cache = None
        reader = None
        if block_mode is None and cache_decoded in (True, "auto") \
                and config.max_epochs > 1:
            reader = _reader_for_epoch(make_reader, epoch, retry_policy)
            block_mode = (getattr(reader, "epoch_varying", False)
                          and hasattr(reader, "block_order")
                          and hasattr(reader, "batch_rows"))
        if block_mode and cache_decoded in (True, "auto"):
            if reader is None:
                reader = _reader_for_epoch(make_reader, epoch, retry_policy)
            if block_cache is None:
                block_cache = DecodedReplayCache(
                    decoded_ram_budget if decoded_ram_budget is not None
                    else default_ram_budget())
            order = list(reader.block_order)
            skip = skip_steps if epoch == start_epoch else 0
            # resume mid-epoch: the reader's own (seed, epoch)
            # permutation is reconstructed by the factory; trim the
            # visit order to match the skipped position
            trimmed = order[skip:] if skip else order
            if batcher.rows is None:
                batcher.pin(int(reader.batch_rows))
            if hasattr(reader, "seek") and hasattr(reader, "read_batch"):
                # seekable: cache hits consult NO disk — only misses
                # and the once-per-epoch anchor contract check read raw
                def block_source(reader=reader, trimmed=trimmed,
                                 skip=skip):
                    anchor_checked = False
                    for i, bid in enumerate(trimmed):
                        cached = block_cache.get(bid)
                        if cached is not None:
                            if (bid == block_cache.anchor_key
                                    and not anchor_checked):
                                anchor_checked = True
                            else:
                                yield ("dec", cached)
                                continue
                        reader.seek((skip + i) * reader.batch_rows)
                        yield ("blk", bid, reader.read_batch())

                source = block_source()
            else:
                # seekless block reader: sequential read + discard for
                # hits (the protocol does not require seek).  The count
                # check makes a short epoch loud (ADVICE r4): zip would
                # silently truncate if the reader yields fewer batches
                # than block_order promises.
                def counted_blocks(reader=reader, trimmed=trimmed,
                                   skip=skip):
                    n = 0
                    for bid, b in zip(trimmed, _seek_or_skip(reader, skip)):
                        n += 1
                        yield ("blk", bid, b)
                    if n < len(trimmed):
                        raise ValueError(
                            f"block-addressable reader yielded {n} "
                            f"batches but block_order promises "
                            f"{len(trimmed)}; the epoch would silently "
                            "train on fewer blocks")

                source = counted_blocks()
        else:
            replay_ok = replay_cache is not None and replay_cache.ready
            if replay_ok and cache_decoded == "auto":
                # Replay guard: "auto" engaged on the cursor protocol, but the
                # protocol does not promise epoch-determinism (a reader may
                # legitimately re-shuffle segment order per epoch).  Re-read
                # the first raw batch and compare its digest against the
                # recorded epoch's; on mismatch drop the cache and decode
                # normally.  (``cache_decoded=True`` skips the probe — the
                # caller owns the determinism guarantee.)
                reader = _reader_for_epoch(make_reader, epoch, retry_policy)
                probe_it = iter(reader)
                probe_first = next(probe_it, None)
                probe_mismatch = False
                # re-position the probed reader at batch 0 either way
                if hasattr(reader, "seek") and hasattr(reader, "batch_rows"):
                    # seekable: also probe a deterministic MID-STREAM
                    # batch (ADVICE r4) — the largest power-of-two index
                    # the recorder digested.  A one-batch guard misses a
                    # reader that keeps batch 0 stable but shuffles the
                    # rest; seek makes the second probe nearly free.
                    mid_candidates = [
                        i for i in replay_cache.probe_fingerprints
                        if replay_cache.n_batches is None
                        or i < replay_cache.n_batches]
                    if mid_candidates:
                        mid = max(mid_candidates)
                        reader.seek(mid * int(reader.batch_rows))
                        probe_mid = next(iter(reader), None)
                        probe_mismatch = (
                            probe_mid is None
                            or batch_fingerprint(probe_mid)
                            != replay_cache.probe_fingerprints[mid])
                    reader.seek(0)
                else:
                    # generator-shaped reader: re-chain the consumed batch
                    reader = itertools.chain(
                        [] if probe_first is None else [probe_first], probe_it)
                if (probe_mismatch or probe_first is None
                        or replay_cache.fingerprint is None
                        or batch_fingerprint(probe_first)
                        != replay_cache.fingerprint):
                    # one-way latch: this reader varies per epoch, so a
                    # re-recorded cache would just be dropped again next
                    # epoch — stop paying the tee (RAM + hash) for the
                    # rest of the fit
                    replay_cache = None
                    replay_ok = False
                    guard_tripped = True
            if replay_ok and replay_cache.prefix_batches == replay_cache.n_batches:
                # the decoded cache holds the WHOLE epoch: the reader's disk
                # is not consulted (beyond the guard's one-batch probe)
                source = (("dec", t) for t in replay_cache.replay())
            else:
                if reader is None:
                    reader = _reader_for_epoch(make_reader, epoch, retry_policy)
                if epoch == start_epoch and skip_steps:
                    # fast-forward to the checkpointed cursor
                    reader = _seek_or_skip(reader, skip_steps)
                if batcher.rows is None and hasattr(reader, "batch_rows"):
                    batcher.pin(int(reader.batch_rows))
                if replay_ok:
                    # partial prefix: replay what fit, re-decode the tail
                    tail = _seek_or_skip(reader, replay_cache.prefix_batches)
                    source = itertools.chain(
                        (("dec", t) for t in replay_cache.replay()),
                        (("raw", b) for b in tail))
                else:
                    # readers that DECLARE per-epoch variance (e.g.
                    # ShuffledCacheReader.epoch_varying) are never recorded
                    # under "auto": a one-batch digest guard cannot prove a
                    # permutation identical (same first block != same
                    # order), so recording would be either wasted (guard
                    # trips) or silently wrong (1-in-n-blocks collision
                    # replays a frozen epoch and breaks resume exactness)
                    record = (config.max_epochs - epoch > 1
                              and not guard_tripped
                              and not (epoch == start_epoch and skip_steps)
                              and (cache_decoded is True
                                   or (cache_decoded == "auto"
                                       and _has_cursor(reader)
                                       and not getattr(reader, "epoch_varying",
                                                       False))))
                    if record:
                        rec_cache = DecodedReplayCache(
                            decoded_ram_budget if decoded_ram_budget is not None
                            else default_ram_budget())
                        _rec_cache[0] = rec_cache
                        source = (("rec", i, b) for i, b in enumerate(reader))
                    else:
                        source = (("raw", b) for b in reader)

        # Running on-device sum: memory stays flat over millions of batches
        # (a list of live per-batch scalars would grow O(n_batches)).
        loss_sum = resume_loss_sum
        n_batches = resume_n_batches
        step_in_epoch = skip_steps
        n_dispatches = 0
        resume_loss_sum, resume_n_batches, skip_steps = None, 0, 0
        # The pipeline generator is closed EXPLICITLY on every exit
        # (normal or exception): its teardown stops + joins the reader
        # threads, so a supervised restart (resilient_fit) never races a
        # zombie reader for the shared live source.  Relying on GC would
        # not do — the exception traceback pins the frames in a cycle
        # and the close happens arbitrarily late.
        if chunked:
            pipeline = prefetch_to_device(
                source, depth=chunk_depth,
                transform=route, sharding=sharding,
                workers=prefetch_workers,
                put_workers=prefetch_put_workers, stats=prefetch_stats,
                chunks=W)
        else:
            pipeline = prefetch_to_device(
                source, depth=prefetch_depth,
                transform=route, sharding=sharding,
                workers=prefetch_workers,
                put_workers=prefetch_put_workers, stats=prefetch_stats,
                put_fn=put_fn)
        try:
            if chunked:
                for chunk, mask, n_valid in pipeline:
                    # (retry_policy wraps the READER, not this pipeline: the
                    # source here is a generator chain, which dies on a
                    # propagated exception — a pipeline-level retry of it
                    # would read StopIteration and silently truncate)
                    if loss_sum is None:
                        loss_sum = jnp.zeros((), jnp.float32)
                    with tracer.span("train_chunk", cat="train",
                                     step=global_step + n_valid,
                                     epoch=epoch):
                        # span = dispatch wall (async): completion is
                        # fenced by the probe fetch below / the epoch-end
                        # loss fetch, never inside the loop
                        if probe is not None:
                            params, loss_sum, probe_out = chunk_step(
                                params, loss_sum, probe, chunk, mask)
                        else:
                            params, loss_sum = chunk_step(
                                params, loss_sum, chunk, mask)
                    if probe is not None:
                        # ONE batched transfer at the chunk boundary —
                        # the only fence the probe ever costs
                        for k, v in probe_out.fetch().items():
                            step_trace.setdefault(k, []).append(v)
                        probe = probe_out.reset()
                    n_batches += n_valid
                    step_in_epoch += n_valid
                    global_step += n_valid
                    n_dispatches += 1
                    # mid-epoch cuts land at chunk boundaries: save when the
                    # chunk crossed a checkpoint_every_steps multiple (and
                    # publish AFTER the save — never serve ahead of durable)
                    cut_done = False
                    if (checkpoint_every_steps > 0
                            and (manager is not None or publish_cb is not None)
                            and step_in_epoch // checkpoint_every_steps
                            > (step_in_epoch - n_valid)
                            // checkpoint_every_steps):
                        if manager is not None:
                            _save(epoch, step_in_epoch, loss_sum, n_batches)
                            cut_done = True
                        if publish_cb is not None:
                            publish_cb(global_step,
                                       lambda p=params: _publish_params(p))
                    # elastic membership: one poll per chunk boundary —
                    # injected preempt/join faults and lease expiry land
                    # here; a changed fleet cuts a boundary checkpoint
                    # and hands the resize to the supervisor (restore
                    # onto the new mesh)
                    if membership is not None \
                            and membership.poll(global_step):
                        if manager is not None and not cut_done:
                            _save(epoch, step_in_epoch, loss_sum,
                                  n_batches)
                        from ...parallel.elastic import ResizeRequested

                        raise ResizeRequested(
                            step=global_step,
                            fleet_size=membership.fleet_size,
                            membership_epoch=membership.membership_epoch)
            else:
                for dev_batch in pipeline:
                    params, value = batch_step(params, *dev_batch)
                    loss_sum = value if loss_sum is None else add(loss_sum, value)
                    n_batches += 1
                    step_in_epoch += 1
                    global_step += 1
                    n_dispatches += 1
                    if (checkpoint_every_steps > 0
                            and (manager is not None or publish_cb is not None)
                            and step_in_epoch % checkpoint_every_steps == 0):
                        if manager is not None:
                            _save(epoch, step_in_epoch, loss_sum, n_batches)
                        if publish_cb is not None:
                            publish_cb(global_step,
                                       lambda p=params: _publish_params(p))
        finally:
            pipeline.close()
        if loss_sum is None:
            raise ValueError("make_reader() returned an empty epoch")
        dispatch_log.append(n_dispatches)
        if rec_cache is not None:
            rec_cache.finish(step_in_epoch)
            replay_cache = rec_cache
            recorded_epochs += 1
            _rec_cache[0] = None
        t_now = time.perf_counter()
        epoch_secs.append(t_now - t_epoch)
        if tracer.enabled:
            tracer.add("train_epoch", t_epoch, t_now, cat="train",
                       epoch=epoch, step=global_step)
        epoch_loss = float(
            np.asarray(_fetch_replicated(loss_sum))) / n_batches
        loss_log.append(epoch_loss)
        stop = config.tol > 0 and abs(prev_loss - epoch_loss) <= config.tol
        if not stop:
            prev_loss = epoch_loss
        if manager is not None:
            _save(epoch + 1, 0, None, 0, converged=stop)  # epoch-boundary cut
        if publish_cb is not None:
            publish_cb(global_step, lambda p=params: _publish_params(p))
        if stop:
            break
    params = _fetch_replicated(params)
    final_gr_state = params.pop(GR_STATE_KEY, None)
    if gr is not None and final_gr_state is not None:
        from ...parallel import grad_reduce as GR

        if GR.wants_overlap(gr):
            params = _apply_drain(params, final_gr_state, config)
    if stream_info is not None:
        stream_info["impl"] = stream_impl
        stream_info["steps_per_dispatch"] = W
        stream_info["dispatches_per_epoch"] = dispatch_log
        if step_probe:
            stream_info["step_trace"] = {
                k: (np.concatenate(v) if v else np.zeros((0,), np.float32))
                for k, v in step_trace.items()}
        if block_cache is not None:
            stream_info["decoded_cache_mode"] = "block"
            stream_info["decoded_cache_batches"] = len(block_cache)
            stream_info["decoded_cache_bytes"] = block_cache.cached_bytes
        else:
            cached = (replay_cache.prefix_batches
                      if replay_cache is not None and replay_cache.ready
                      else 0)
            stream_info["decoded_cache_batches"] = cached
            stream_info["decoded_cache_recorded_epochs"] = recorded_epochs
            if guard_tripped:
                stream_info["decoded_cache_guard_tripped"] = True
            if cached:
                stream_info["decoded_cache_bytes"] = \
                    replay_cache.cached_bytes
                stream_info["decoded_cache_total_batches"] = \
                    replay_cache.n_batches
        stream_info["epoch_seconds"] = [round(s, 4) for s in epoch_secs]
    return LinearState(np.asarray(params["w"], np.float64),
                       float(params["b"]),
                       planned_impl=stream_impl), loss_log
