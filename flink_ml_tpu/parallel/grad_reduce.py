"""Configurable gradient reduction: exact / sparse / quantized / hierarchical.

The reference's entire scale-out story is its network shuffle layer; the
TPU-native analog has so far been the implicit all-reduce GSPMD inserts
for data-parallel gradients.  This module makes that reduction an explicit,
configurable operator so gradient bytes-on-wire become a first-class,
measured quantity (the SparCML/SwitchML posture — arXiv:1802.08021,
arXiv:1903.06701):

- ``mode="exact"``   — ``lax.psum``; adopters keep their legacy implicit
  path when the config is absent or exact, so the default is bit-identical
  to the pre-reducer code.
- ``mode="topk"``    — per-leaf top-|g| sparsification at ``density`` with
  **error feedback**: the unsent residual is carried in reducer state
  (EF-SGD semantics — what was not sent this step is added to the next
  step's gradient, so the compression error stays bounded instead of
  accumulating).  The reduce itself is the all-gather form of a sparse
  all-reduce: each participant contributes ``k`` (index, value) pairs and
  every participant scatter-adds the gathered pairs locally.
- ``mode="int8"``    — block-quantized reduce: per-``block_size`` max-abs
  scales, **stochastic rounding** (unbiased — no residual needed; the
  rounding key is carried in reducer state), int8 payloads + f32 scales
  all-gathered and dequantized-summed locally.
- hierarchical (``dcn_axis`` set) — the two-tier composition for
  :func:`~flink_ml_tpu.parallel.distributed.hybrid_mesh`:
  ``reduce_scatter`` over the fast ICI axis first (exact), the compressed
  all-reduce over the slow ``dcn`` axis on the 1/I-sized shard, then
  ``all_gather`` back over ICI — only the inter-host hop pays for (or
  benefits from) compression.

All reduction functions must run inside an SPMD context (``shard_map``)
with the named axes bound; reducer state is per-participant — adopters
carry it with a leading participant dim sharded over the reduction axes
(see :func:`init_state`) so it rides scan carries and checkpoints like any
other optimizer state.

r11 (communication-scheduled training) adds three orthogonal knobs:

- ``bucket_count=B`` — the flattened gradient is cut into B size-balanced
  **buckets**, each reduced by its own independent collective
  (:func:`plan_buckets`).  Exact mode is bit-identical bucketed or not
  (psum is elementwise); compressed modes select top-k per bucket instead
  of per leaf.  Independent bucket collectives are what XLA's
  latency-hiding scheduler can overlap with compute.
- ``overlap=True`` — adopters run the **one-step-stale pipelined apply**
  (:func:`pipelined_reduce`): the previous step's gradient buckets are
  reduced while the current step's forward/backward runs (the two are
  data-independent), and the optimizer applies each bucket's reduced
  value as it lands.  Legal under error feedback: the EF residual absorbs
  the one-step staleness exactly as it absorbs sparsification (MLFabric's
  scheduling posture).  ``exact`` mode keeps a fence — overlap is ignored
  and the path stays bit-identical to the blocking psum.
- ``adaptive=True`` — per-leaf **variable-rate compression** (SparCML's
  variable-sparsity case): the carried residual-norm/gradient-norm ratio
  (EMA, reducer state) selects a rung of ``density_ladder`` — a density,
  or an ``"int8"``/``"exact"`` fallback — per leaf every
  ``adaptive_window`` steps.  Selection is computed from psum'd norms, so
  every participant takes the same ``lax.switch`` branch.

r20 (topology-aware wire protocol) replaces the all-gather transport of
the compressed hop:

- ``wire_protocol`` — ``"auto"`` (default) runs the top-k family's
  sparse all-reduce as **recursive halving/doubling**
  (:func:`~.collectives.sparse_all_reduce_rd`) whenever the compressed
  hop spans a single named axis (the dcn hop of every hierarchical
  config, and flat single-axis reductions), falling back to the legacy
  all-gather form for multi-axis hops; ``"rd"`` / ``"allgather"`` force
  one or the other.  Per-round fill-in lands in the ``fill`` /
  ``union`` reducer-state leaves and :func:`payload_bytes` turns it
  into measured bytes-on-wire next to the analytic best/worst bounds.
  Exact mode never routes through the sparse protocol, so it stays
  bit-identical to the legacy path.
- ``dcn_schedule="earliest"`` — hierarchical bucketed reduces chain an
  ``optimization_barrier`` token through the buckets in consumption
  order (earliest-needed bucket first, MLFabric's schedule), so the dcn
  collectives issue in the order the overlap pipeline applies them
  instead of racing; ``"free"`` keeps the unordered launch.  The
  barrier is the identity, so the two schedules are bit-identical in
  value (asserted in tests) — only issue order changes.
- ``int8_accum="fixed"`` — the int8 hop quantizes against a SHARED
  (pmax'd) per-block scale and accumulates int32 per hop
  (:func:`~.collectives.fixed_point_all_reduce` — SwitchML pool
  semantics), one rounding per participant no matter the hop count;
  ``"dequant"`` keeps the legacy dequantize-to-f32-then-sum.
"""

from __future__ import annotations

import math

from dataclasses import dataclass
from typing import Any, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .collectives import (
    FILL_DOUBLING_BASE,
    FILL_POSTFOLD_SLOT,
    FILL_PREFOLD_SLOT,
    FILL_ROUND_SLOTS,
    FILL_SWITCH_SLOT,
    FILL_UNION_SLOT,
    FILL_VEC_LEN,
    rd_topology,
)

__all__ = [
    "BucketPlan",
    "GradReduceConfig",
    "MODES",
    "bucket_report",
    "drain_pending",
    "effective_ladder",
    "hop_axis",
    "init_state",
    "mesh_layout",
    "needs_state",
    "payload_bytes",
    "pipelined_reduce",
    "plan_buckets",
    "reduce_gradients",
    "reduction_axes",
    "reshard_state",
    "resolved_wire_protocol",
    "squeeze_state",
    "state_participants",
    "unsqueeze_state",
    "wants_overlap",
]

MODES = ("exact", "topk", "int8")
WIRE_PROTOCOLS = ("auto", "rd", "allgather")
INT8_ACCUMS = ("dequant", "fixed")
DCN_SCHEDULES = ("earliest", "free")

AxisSpec = Union[str, Tuple[str, ...]]


@dataclass(frozen=True)
class GradReduceConfig:
    """How data-parallel gradients are summed across the mesh.

    ``axis`` is the (fast/ICI) reduction axis; ``dcn_axis`` — when set —
    selects the hierarchical composition: exact reduce-scatter over
    ``axis``, the configured compression over ``dcn_axis`` only, gather
    back.  With ``dcn_axis=None`` the compression applies to the whole
    flat reduce over ``axis``.

    ``density`` (topk) is the fraction of each leaf's elements sent per
    step (``k = max(1, floor(density * n))`` — floor, so the advertised
    compression ratio is a lower bound).  ``block_size`` (int8) is the
    elements-per-scale quantization granule; ``seed`` feeds the stochastic
    rounding stream.

    ``bucket_count=B`` cuts the flat gradient into B size-balanced
    buckets, each reduced by its own independent collective (0 keeps the
    legacy per-leaf reduce).  ``overlap=True`` asks adopters for the
    one-step-stale pipelined apply (fenced off — ignored — in ``exact``
    mode, which stays bit-identical to the blocking psum).
    ``adaptive=True`` (topk only) re-selects each leaf's rung of
    ``density_ladder`` — a density in (0, 1], or the strings ``"int8"`` /
    ``"exact"`` — every ``adaptive_window`` steps from the carried
    residual/gradient norm ratio: above ``adaptive_target`` the leaf
    climbs one rung toward fidelity, below half the target it descends
    one rung toward thrift.  An empty ladder defaults to
    ``(density / 4, density, "exact")``.

    ``wire_protocol`` selects the sparse transport of the top-k family:
    ``"auto"`` (recursive halving/doubling on single-named-axis hops,
    all-gather otherwise), ``"rd"``, or ``"allgather"``.
    ``int8_accum`` selects the int8 hop's accumulator: ``"dequant"``
    (legacy f32 dequantize-then-sum) or ``"fixed"`` (shared scales,
    int32 per-hop accumulation).  ``dcn_schedule`` orders hierarchical
    bucket transfers: ``"earliest"`` (consumption order, default) or
    ``"free"`` (unordered launch).
    """

    mode: str = "exact"
    density: float = 0.1
    block_size: int = 256
    axis: AxisSpec = "data"
    dcn_axis: Optional[str] = None
    seed: int = 0
    bucket_count: int = 0
    overlap: bool = False
    adaptive: bool = False
    adaptive_window: int = 8
    adaptive_target: float = 0.5
    density_ladder: Tuple = ()
    wire_protocol: str = "auto"
    int8_accum: str = "dequant"
    dcn_schedule: str = "earliest"

    def __post_init__(self):
        if self.wire_protocol not in WIRE_PROTOCOLS:
            raise ValueError(f"wire_protocol must be one of "
                             f"{WIRE_PROTOCOLS}, got {self.wire_protocol!r}")
        if self.int8_accum not in INT8_ACCUMS:
            raise ValueError(f"int8_accum must be one of {INT8_ACCUMS}, "
                             f"got {self.int8_accum!r}")
        if self.dcn_schedule not in DCN_SCHEDULES:
            raise ValueError(f"dcn_schedule must be one of "
                             f"{DCN_SCHEDULES}, got {self.dcn_schedule!r}")
        single_hop = self.dcn_axis is not None or \
            isinstance(self.axis, str) or len(tuple(self.axis)) == 1
        if self.wire_protocol == "rd" and not single_hop:
            raise ValueError(
                "wire_protocol='rd' runs pairwise ppermute rounds over ONE "
                "named axis; this config's compressed hop spans "
                f"axis={self.axis!r} — set a dcn_axis or use 'allgather'")
        if self.int8_accum == "fixed" and not single_hop:
            raise ValueError(
                "int8_accum='fixed' accumulates int32 over ONE named axis; "
                f"this config's hop spans axis={self.axis!r}")
        if self.mode not in MODES:
            raise ValueError(f"mode must be one of {MODES}, got {self.mode!r}")
        if self.mode == "topk" and not 0.0 < self.density <= 1.0:
            raise ValueError(f"density must be in (0, 1], got {self.density}")
        if self.mode == "int8" and self.block_size <= 0:
            raise ValueError(
                f"block_size must be positive, got {self.block_size}")
        if self.dcn_axis is not None and not isinstance(self.axis, str):
            raise ValueError(
                "hierarchical reduction needs a single ICI axis name; got "
                f"axis={self.axis!r}")
        if self.bucket_count < 0:
            raise ValueError(
                f"bucket_count must be >= 0, got {self.bucket_count}")
        if self.adaptive:
            if self.mode != "topk":
                raise ValueError(
                    "adaptive density is a topk-family policy (the ladder "
                    "may contain int8/exact fallback rungs); set "
                    f"mode='topk', got mode={self.mode!r}")
            if self.adaptive_window < 1:
                raise ValueError("adaptive_window must be >= 1, got "
                                 f"{self.adaptive_window}")
            if self.adaptive_target <= 0:
                raise ValueError("adaptive_target must be positive, got "
                                 f"{self.adaptive_target}")
            for spec in self.density_ladder:
                if isinstance(spec, str):
                    if spec not in ("exact", "int8"):
                        raise ValueError(
                            "ladder rungs are densities in (0, 1] or "
                            f"'exact'/'int8', got {spec!r}")
                elif not 0.0 < float(spec) <= 1.0:
                    raise ValueError(
                        f"ladder density {spec!r} not in (0, 1]")
        elif self.density_ladder:
            raise ValueError("density_ladder requires adaptive=True")


def effective_ladder(config: GradReduceConfig) -> Tuple:
    """The adaptive rung ladder, ordered cheapest -> highest fidelity.
    Rung selection moves +1 (toward the end / exact) when the residual
    ratio runs hot and -1 when it runs cold."""
    if config.density_ladder:
        return tuple(config.density_ladder)
    return (max(config.density / 4.0, 1e-4), config.density, "exact")


def _initial_rung(config: GradReduceConfig) -> int:
    """Start every leaf at the configured density's rung (the middle of
    the default ladder) so the first window behaves like plain topk."""
    lad = effective_ladder(config)
    for i, spec in enumerate(lad):
        if not isinstance(spec, str) and float(spec) == config.density:
            return i
    return len(lad) // 2


def wants_overlap(config: Optional[GradReduceConfig]) -> bool:
    """True when adopters should run the one-step-stale pipelined apply.
    ``exact`` mode keeps the fence: overlap is ignored so the default
    path stays bit-identical to the blocking psum."""
    return (config is not None and config.overlap
            and config.mode != "exact")


def _carries_ef(config: GradReduceConfig) -> bool:
    return config.mode == "topk" or config.adaptive


def _bucketed(config: GradReduceConfig) -> bool:
    """Whether the reduce routes through the bucket planner (explicit
    buckets, or adaptive — which needs per-leaf transport units)."""
    return config.bucket_count > 0 or config.adaptive


def reduction_axes(config: GradReduceConfig) -> Tuple[str, ...]:
    """Every mesh axis the reduction sums over (ICI axes + the dcn axis)."""
    axes = (config.axis,) if isinstance(config.axis, str) else tuple(
        config.axis)
    if config.dcn_axis is not None:
        axes = (config.dcn_axis,) + axes
    return axes


def hop_axis(config: GradReduceConfig) -> Optional[str]:
    """The single named axis the COMPRESSED hop runs over — the dcn axis
    of a hierarchical config, or the flat reduction axis when it is one
    name — or ``None`` when the flat hop spans multiple axes (pairwise
    rounds need one ring of partners)."""
    if config.dcn_axis is not None:
        return config.dcn_axis
    if isinstance(config.axis, str):
        return config.axis
    axes = tuple(config.axis)
    return axes[0] if len(axes) == 1 else None


def resolved_wire_protocol(config: GradReduceConfig) -> str:
    """The sparse transport the top-k family actually runs:
    ``wire_protocol="auto"`` resolves to recursive halving/doubling
    (``"rd"``) whenever :func:`hop_axis` names a single axis, and to the
    legacy ``"allgather"`` for multi-axis flat hops (config validation
    already rejects forcing ``"rd"`` there)."""
    if config.wire_protocol == "allgather":
        return "allgather"
    return "rd" if hop_axis(config) is not None else "allgather"


def _rd_engaged(config: GradReduceConfig) -> bool:
    """Whether this config's reduce carries per-round fill-in state —
    i.e. a top-k-family transport runs the recursive-doubling protocol."""
    return (config.mode == "topk" or config.adaptive) and \
        resolved_wire_protocol(config) == "rd"


def needs_state(config: GradReduceConfig) -> bool:
    return config.mode in ("topk", "int8")


def mesh_layout(config: GradReduceConfig, mesh) -> Tuple[Tuple[str, ...],
                                                         int, Any]:
    """(reduction axes, participant count, batch PartitionSpec entry) for
    running this config on ``mesh`` — THE one copy of the axis validation
    every adopter (sgd, widedeep) shares, with the loud error for axes
    the mesh does not have."""
    axes = reduction_axes(config)
    missing = [a for a in axes if a not in mesh.shape]
    if missing:
        raise ValueError(
            f"grad_reduce axes {missing} not in mesh {list(mesh.shape)}; "
            "build the mesh with the reduction axes (e.g. "
            "distributed.hybrid_mesh for a dcn axis)")
    n_participants = int(np.prod([mesh.shape[a] for a in axes]))
    return axes, n_participants, (axes if len(axes) > 1 else axes[0])


def _topk_k(n: int, density: float) -> int:
    return max(1, int(n * density))


def init_state(config: GradReduceConfig, grads_like: Any,
               n_participants: int) -> dict:
    """Per-participant reducer state, stacked over a leading participant
    dim of size ``n_participants`` (the product of the reduction axes'
    sizes) — adopters shard that dim over the reduction axes and squeeze
    it inside ``shard_map`` (:func:`squeeze_state`).

    ``topk`` carries the error-feedback residual (zeros-like every
    gradient leaf); ``int8`` carries one PRNG key per participant for the
    stochastic-rounding stream.  ``exact`` needs no state (``{}``).

    ``adaptive`` adds the policy state — per-leaf ratio EMA (``ema``),
    chosen rung (``rung``), and the step ``tick``; ``overlap`` adds
    ``pending``, the zeros-initialized one-step-stale gradient buffer
    (the first pipelined step reduces zeros, a deterministic no-op).
    All of it rides the same participant-stacked layout, so adopters'
    checkpoints round-trip the whole schedule for free.

    When the recursive-doubling wire protocol is engaged
    (:func:`resolved_wire_protocol`), two accounting leaves ride along:
    ``fill`` — the last step's per-transport-unit fill-in vector (the
    per-round sent-entry counts, union size, switchover flag and fold
    traffic of :func:`~.collectives.sparse_all_reduce_rd`, raw so
    ``payload_bytes(fill=...)`` reports calibrated measured bytes) —
    and ``union`` — a smoothed (EMA) union-density per unit, the
    switchover statistic.  Both have fleet-size-independent trailing
    shapes, so elastic resizes re-seat them without reshaping.
    """

    def stack(g):
        return jnp.zeros((n_participants,) + np.shape(g), jnp.float32)

    state: dict = {}
    lad = effective_ladder(config) if config.adaptive else ()
    if _carries_ef(config):
        state["ef"] = jax.tree_util.tree_map(stack, grads_like)
    if config.mode == "int8" or "int8" in lad:
        base = jax.random.PRNGKey(config.seed)
        state["key"] = jax.vmap(
            lambda i: jax.random.fold_in(base, i))(
                jnp.arange(n_participants, dtype=jnp.int32))
    if config.adaptive:
        n_leaves = len(jax.tree_util.tree_leaves(grads_like))
        state["ema"] = jnp.zeros((n_participants, n_leaves), jnp.float32)
        state["rung"] = jnp.full((n_participants, n_leaves),
                                 _initial_rung(config), jnp.int32)
        state["tick"] = jnp.zeros((n_participants,), jnp.int32)
    if _rd_engaged(config):
        n_units = _fill_units(grads_like, config)
        state["fill"] = jnp.zeros((n_participants, n_units, FILL_VEC_LEN),
                                  jnp.float32)
        state["union"] = jnp.zeros((n_participants, n_units), jnp.float32)
    if wants_overlap(config):
        state["pending"] = jax.tree_util.tree_map(stack, grads_like)
    return state


def _fill_units(grads_like: Any, config: GradReduceConfig) -> int:
    """Transport units the fill accounting is keyed on: buckets when the
    reduce is bucketed/adaptive, leaves otherwise — exactly the units
    :func:`_transport_units` accounts."""
    if _bucketed(config):
        return len(plan_buckets(grads_like, config).ranges)
    return len(jax.tree_util.tree_leaves(grads_like))


def squeeze_state(state: dict) -> dict:
    """Drop the leading participant dim of the local (1, ...) state slices
    inside ``shard_map``."""
    return jax.tree_util.tree_map(lambda a: a[0], state)


def unsqueeze_state(state: dict) -> dict:
    """Restore the leading participant dim on the way out of ``shard_map``."""
    return jax.tree_util.tree_map(lambda a: a[None], state)


def state_participants(state: Optional[dict]) -> Optional[int]:
    """The participant count a stacked reducer state was built for (the
    leading dim every leaf shares), or ``None`` for empty/absent state."""
    leaves = jax.tree_util.tree_leaves(state or {})
    if not leaves:
        return None
    return int(np.shape(leaves[0])[0])


def reshard_state(state: dict, n_new: int, *,
                  ici_size: int = 1) -> dict:
    """Re-shard participant-stacked reducer state onto a fleet of
    ``n_new`` participants — THE resize-as-restore mapping (elastic PR).
    The mapping depends only on the state's leaf keys and the (fixed)
    ICI extent, never on the reduce mode — which is why there is no
    config parameter.

    Mass-carrying leaves (``ef`` residual, ``pending`` overlap buffer)
    are **total-preserving**: the old participants' contributions are
    summed — per ICI position for the hierarchical layout, so each
    shard-domain residual stays embedded at its own slice exactly as
    :func:`_embed_shard` placed it — and the total is seated on the new
    fleet's first dcn group, the rest zero-initialized.  Policy state
    (``ema``/``rung``/``tick``) is replicated content by construction
    and broadcasts from participant 0; rounding ``key`` rows re-derive
    deterministically by folding the new participant index into
    participant 0's carried key.

    Deterministic and host-side: an elastic resize AND a fixed fleet of
    the new size restoring the same cut both route through this
    function, which is what makes the two bit-exact from the boundary
    onward (the fit-level contract asserted in tests/test_faults.py).

    The wire-protocol accounting leaves resize by their own rules:
    ``fill`` (last-step per-round sent counts) measures the OLD fleet's
    round structure — a different participant count has a different
    core/rounds/fold layout, so carrying the numbers over would
    misattribute bytes; it re-seats as zeros and the first post-resize
    step repopulates it.  ``union`` (union-density EMA) describes the
    gradient, not the fleet — psum-uniform within each dcn hop group,
    varying only across ICI columns — so it broadcasts from participant
    0 like the other policy leaves (a smoothed-statistic re-seed the
    next steps re-diverge, not an exact invariant).  Both have
    fleet-size-independent trailing shapes by construction, so the same
    rule applies at any resize.
    """
    n_old = state_participants(state)
    if n_old is None or n_old == n_new:
        return state
    if ici_size < 1 or n_old % ici_size or n_new % ici_size:
        raise ValueError(
            f"cannot reshard reducer state from {n_old} to {n_new} "
            f"participants at ici_size={ici_size}: both fleet sizes must "
            "be multiples of the (fixed) ICI extent")
    d_new = n_new // ici_size

    def collapse(a):
        a = np.asarray(a, np.float32)
        tail = a.shape[1:]
        total = a.reshape((n_old // ici_size, ici_size) + tail).sum(axis=0)
        out = np.zeros((d_new, ici_size) + tail, np.float32)
        out[0] = total
        return out.reshape((n_new,) + tail)

    def broadcast0(a):
        a = np.asarray(a)
        return np.broadcast_to(a[:1], (n_new,) + a.shape[1:]).copy()

    out: dict = {}
    for key, value in state.items():
        if key in ("ef", "pending"):
            out[key] = jax.tree_util.tree_map(collapse, value)
        elif key in ("ema", "rung", "tick", "union"):
            out[key] = broadcast0(value)
        elif key == "fill":
            a = np.asarray(value, np.float32)
            out[key] = np.zeros((n_new,) + a.shape[1:], np.float32)
        elif key == "key":
            base = jnp.asarray(np.asarray(value)[0])
            out[key] = np.asarray(jax.vmap(
                lambda i: jax.random.fold_in(base, i))(
                    jnp.arange(n_new, dtype=jnp.int32)))
        else:
            raise ValueError(
                f"unknown reducer-state leaf {key!r}: teach reshard_state "
                "its resize semantics before restoring it onto a "
                "different fleet")
    return out


# ---------------------------------------------------------------------------
# bucket planning (host side, static)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class BucketPlan:
    """Static transport plan: the flat concatenation of all gradient
    leaves cut into size-balanced contiguous ranges.  ``bucket_leaves``
    maps each bucket to the leaf indices it overlaps (a bucket is either
    a slice of one big leaf or a group of whole small leaves — or, at
    cut points, a tail+head pair; the adaptive rung of a bucket is the
    max — highest-fidelity — rung of its leaves)."""

    ranges: Tuple[Tuple[int, int], ...]
    leaf_offsets: Tuple[int, ...]
    leaf_sizes: Tuple[int, ...]
    leaf_shapes: Tuple[Tuple[int, ...], ...]
    bucket_leaves: Tuple[Tuple[int, ...], ...]

    @property
    def total(self) -> int:
        return self.leaf_offsets[-1]

    @property
    def bucket_sizes(self) -> Tuple[int, ...]:
        return tuple(hi - lo for lo, hi in self.ranges)


def plan_buckets(grads_like: Any, config: GradReduceConfig) -> BucketPlan:
    """Cut the flat gradient into ``config.bucket_count`` equal ranges
    (cut points ``round(i * total / B)`` — perfectly size-balanced, leaf
    boundaries not respected: transport is flat).  ``bucket_count=0``
    (the adaptive-only case) degrades to one bucket per leaf, the
    per-leaf transport the policy state is keyed on."""
    shapes = [tuple(np.shape(g))
              for g in jax.tree_util.tree_leaves(grads_like)]
    sizes = [int(np.prod(s, dtype=np.int64)) if s else 1 for s in shapes]
    offsets = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)
    total = int(offsets[-1])
    B = int(config.bucket_count)
    if B <= 0:
        ranges = [(int(offsets[i]), int(offsets[i + 1]))
                  for i in range(len(sizes))]
    else:
        B = max(1, min(B, total))
        cuts = [int(round(i * total / B)) for i in range(B + 1)]
        ranges = [(cuts[i], cuts[i + 1]) for i in range(B)
                  if cuts[i + 1] > cuts[i]]
    bucket_leaves = []
    for lo, hi in ranges:
        bucket_leaves.append(tuple(
            i for i in range(len(sizes))
            if offsets[i] < hi and offsets[i + 1] > lo))
    return BucketPlan(tuple(ranges), tuple(int(o) for o in offsets),
                      tuple(sizes), tuple(shapes), tuple(bucket_leaves))


# ---------------------------------------------------------------------------
# per-leaf compressed all-reduces (SPMD context)
# ---------------------------------------------------------------------------


def _topk_allreduce(flat: jnp.ndarray, axes: AxisSpec, density: float,
                    protocol: str = "allgather",
                    uniform_axes: Optional[Tuple[str, ...]] = None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Sparse all-reduce of one flat leaf: every participant contributes
    its top-k (index, value) pairs.  ``protocol="rd"`` routes the pairs
    through recursive halving/doubling over the (single) named axis;
    ``"allgather"`` keeps the legacy every-participant-receives-all
    form.  Returns ``(reduced, unsent, fill)`` where ``unsent`` is this
    participant's residual (its accumulated gradient with the sent
    entries zeroed) and ``fill`` is the per-round fill-in vector (zeros
    under allgather, which has no rounds to account).  ``uniform_axes``
    (every axis of the enclosing shard_map, hierarchical callers pass
    :func:`reduction_axes`) keeps the rd switchover predicate
    mesh-uniform — see :func:`~.collectives.sparse_all_reduce_rd`."""
    from .collectives import sparse_all_reduce, sparse_all_reduce_rd

    k = _topk_k(flat.size, density)
    _, idx = lax.top_k(jnp.abs(flat), k)
    vals = flat[idx]
    unsent = flat.at[idx].set(0.0)
    if protocol == "rd":
        ax = axes if isinstance(axes, str) else tuple(axes)[0]
        reduced, fill = sparse_all_reduce_rd(idx, vals, flat.size, ax,
                                             uniform_axes=uniform_axes)
    else:
        reduced = sparse_all_reduce(idx, vals, flat.size, axes)
        fill = jnp.zeros((FILL_VEC_LEN,), jnp.float32)
    return reduced, unsent, fill


def _int8_allreduce(flat: jnp.ndarray, axes: AxisSpec, block: int,
                    key: jnp.ndarray,
                    accum: str = "dequant") -> jnp.ndarray:
    """Block-quantized all-reduce of one flat leaf: per-block max-abs
    scales, stochastic rounding (``floor(x/scale + u)``, u~U[0,1) — the
    unbiased round).  ``accum="dequant"`` (legacy) all-gathers int8
    payload + f32 scales and dequantize-sums locally — P dequantized
    roundings meet in f32, so worst-case error grows with P.
    ``accum="fixed"`` shares ONE pmax'd scale per block across the hop,
    accumulates the int32 codes in-fabric
    (:func:`~.collectives.fixed_point_all_reduce`) and dequantizes the
    exact integer total once — error stays one rounding per participant
    independent of P (the SwitchML posture)."""
    from .collectives import fixed_point_all_reduce, quantized_all_reduce

    n = flat.size
    n_pad = -(-n // block) * block
    padded = jnp.concatenate(
        [flat, jnp.zeros((n_pad - n,), flat.dtype)]) if n_pad > n else flat
    blocks = padded.reshape(-1, block)
    scale = jnp.maximum(jnp.max(jnp.abs(blocks), axis=1, keepdims=True)
                        / 127.0, 1e-12)
    if accum == "fixed":
        ax = axes if isinstance(axes, str) else tuple(axes)[0]
        scale = lax.pmax(scale, ax)
        u = jax.random.uniform(key, blocks.shape)
        q = jnp.clip(jnp.floor(blocks / scale + u),
                     -127, 127).astype(jnp.int32)
        total_q = fixed_point_all_reduce(q, ax)
        return (total_q.astype(jnp.float32) * scale).reshape(-1)[:n]
    u = jax.random.uniform(key, blocks.shape)
    q = jnp.clip(jnp.floor(blocks / scale + u), -127, 127).astype(jnp.int8)
    total = quantized_all_reduce(q, scale, axes)
    return total.reshape(-1)[:n]


def _hier_scatter(flat: jnp.ndarray, ici_axis: str
                  ) -> Tuple[jnp.ndarray, int]:
    """Exact reduce-scatter of one flat leaf over the ICI axis: returns
    (per-participant shard summed over ICI, padded length)."""
    from .collectives import axis_size

    ici = axis_size(ici_axis)
    n = flat.size
    n_pad = -(-n // ici) * ici
    if n_pad > n:
        flat = jnp.concatenate([flat, jnp.zeros((n_pad - n,), flat.dtype)])
    shard = lax.psum_scatter(flat, ici_axis, scatter_dimension=0, tiled=True)
    return shard, n_pad


def _hier_gather(shard: jnp.ndarray, ici_axis: str, n: int,
                 shape) -> jnp.ndarray:
    return lax.all_gather(shard, ici_axis, tiled=True)[:n].reshape(shape)


def _embed_shard(shard: jnp.ndarray, ici_axis: str, n: int,
                 n_pad: int) -> jnp.ndarray:
    """Place this participant's shard-domain residual back in the full
    gradient domain (zeros outside its own slice) so reducer state keeps
    one uniform per-leaf shape in every mode.  At the next step the
    reduce-scatter routes each participant's slice back into exactly its
    shard — the shard-domain EF recursion, carried full-size."""
    i = lax.axis_index(ici_axis)
    full = jnp.zeros((n_pad,), shard.dtype)
    full = lax.dynamic_update_slice(full, shard, (i * shard.size,))
    return full[:n]


def _mode_spec(config: GradReduceConfig):
    """The single rung a non-adaptive config runs every bucket at."""
    return config.density if config.mode == "topk" else config.mode


def _segment_reducer(spec, config: GradReduceConfig):
    """Build ``branch(acc, key) -> (reduced, unsent, fill)`` for one flat
    segment at one rung — a density (EF top-k), ``"int8"`` (unbiased, the
    accumulated residual is fully consumed, so ``unsent = 0``) or
    ``"exact"`` (likewise).  Hierarchical configs wrap the rung's
    compressed hop in the ICI reduce-scatter / all-gather pair; the
    top-k rung's unsent comes back embedded in the full segment domain
    (:func:`_embed_shard`).  Every rung shares the signature so the
    adaptive ``lax.switch`` can select among them; exact/int8 rungs
    return a zero fill vector (no sparse rounds to account)."""
    axes = reduction_axes(config)
    hier = config.dcn_axis is not None
    proto = resolved_wire_protocol(config)

    def no_fill():
        return jnp.zeros((FILL_VEC_LEN,), jnp.float32)

    if spec == "exact":
        def branch(acc, key):
            if not hier:
                return lax.psum(acc, axes), jnp.zeros_like(acc), no_fill()
            shard, _ = _hier_scatter(acc, config.axis)
            shard = lax.psum(shard, config.dcn_axis)
            return (_hier_gather(shard, config.axis, acc.size, (acc.size,)),
                    jnp.zeros_like(acc), no_fill())
    elif spec == "int8":
        def branch(acc, key):
            if not hier:
                return (_int8_allreduce(acc, axes, config.block_size, key,
                                        config.int8_accum),
                        jnp.zeros_like(acc), no_fill())
            shard, _ = _hier_scatter(acc, config.axis)
            shard = _int8_allreduce(shard, config.dcn_axis,
                                    config.block_size, key,
                                    config.int8_accum)
            return (_hier_gather(shard, config.axis, acc.size, (acc.size,)),
                    jnp.zeros_like(acc), no_fill())
    else:
        density = float(spec)

        def branch(acc, key):
            if not hier:
                return _topk_allreduce(acc, axes, density, proto)
            shard, n_pad = _hier_scatter(acc, config.axis)
            red_s, unsent_s, fill = _topk_allreduce(
                shard, config.dcn_axis, density, proto,
                uniform_axes=reduction_axes(config))
            return (_hier_gather(red_s, config.axis, acc.size, (acc.size,)),
                    _embed_shard(unsent_s, config.axis, acc.size, n_pad),
                    fill)
    return branch


def _concat_flat(leaves) -> jnp.ndarray:
    if len(leaves) == 1:
        return leaves[0].reshape(-1)
    return jnp.concatenate([l.reshape(-1) for l in leaves])


def _split_flat(flat: jnp.ndarray, plan: BucketPlan):
    return [flat[plan.leaf_offsets[i]:plan.leaf_offsets[i + 1]].reshape(
        plan.leaf_shapes[i]) for i in range(len(plan.leaf_sizes))]


def _rd_padded(n: int, ici: int, core: int) -> int:
    """Elements of one ``n``-element transport unit as seen by the
    compressed hop: the ICI-scattered shard, padded to a multiple of
    the recursive-doubling core (the n_pad of sparse_all_reduce_rd)."""
    m = -(-n // max(ici, 1))
    return -(-m // core) * core


def _update_fill_state(new_state: dict, state: dict, fill_parts,
                       unit_sizes, config: GradReduceConfig) -> None:
    """Seat this step's per-unit fill-in vectors in reducer state:
    ``fill`` keeps the RAW last-step vectors (so payload_bytes reports
    calibrated measured bytes, not a warm-up-biased EMA), ``union``
    smooths the union density — the switchover statistic — with the
    adaptive machinery's EMA idiom.  Runs inside the SPMD context
    (axis sizes are static there)."""
    from .collectives import axis_size

    fills = jnp.stack(fill_parts)            # (n_units, FILL_VEC_LEN)
    new_state["fill"] = fills
    p = axis_size(hop_axis(config))
    core = rd_topology(p)[0]
    ici = axis_size(config.axis) if config.dcn_axis is not None else 1
    denom = jnp.asarray([_rd_padded(int(n), ici, core)
                         for n in unit_sizes], jnp.float32)
    new_state["union"] = 0.9 * state["union"] + 0.1 * (
        fills[:, FILL_UNION_SLOT] / denom)


def _reduce_bucketed(grads: Any, state: dict, config: GradReduceConfig
                     ) -> Tuple[Any, dict]:
    """Bucketed (and/or adaptive) reduce of the whole gradient tree: the
    flat concatenation is cut per :func:`plan_buckets` and each bucket
    runs its own independent collective — the schedulable unit the
    overlap pipeline rides.  With ``adaptive``, each bucket's rung is the
    max (highest-fidelity) rung of its leaves, selected by ``lax.switch``
    — the rung indices are derived from psum'd norms, so every
    participant takes the same branch and the collectives stay matched.

    Hierarchical compressed configs with ``dcn_schedule="earliest"``
    thread an ``optimization_barrier`` token through the buckets in
    index order — bucket ``i`` holds the flat range the optimizer apply
    consumes ``i``-th, so issue order matches consumption order
    (MLFabric's earliest-needed-first schedule) instead of leaving B
    same-priority dcn collectives to race.  The barrier is the
    identity: values are bit-identical to ``"free"`` (asserted in
    tests); only the dependency chain — and so XLA's issue order —
    changes.
    """
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    plan = plan_buckets(grads, config)
    axes = reduction_axes(config)
    has_ef = _carries_ef(config)
    lad = effective_ladder(config) if config.adaptive else ()
    new_state = dict(state)

    flat = _concat_flat(leaves)
    if has_ef:
        acc_flat = flat + _concat_flat(
            jax.tree_util.tree_leaves(state["ef"]))
    else:
        acc_flat = flat

    n_buckets = len(plan.ranges)
    if config.mode == "int8" or "int8" in lad:
        key, use = jax.random.split(state["key"])
        bucket_keys = jax.random.split(use, n_buckets)
        new_state["key"] = key
    else:
        bucket_keys = [jax.random.PRNGKey(0)] * n_buckets

    if config.adaptive:
        rungs = state["rung"]                            # (n_leaves,) i32
        branches = [_segment_reducer(spec, config) for spec in lad]
    chain = (config.dcn_axis is not None and config.mode != "exact"
             and config.dcn_schedule == "earliest" and n_buckets > 1)
    token = acc_flat[:1]
    out_parts, unsent_parts, fill_parts = [], [], []
    for bi, (lo, hi) in enumerate(plan.ranges):
        acc = acc_flat[lo:hi]
        if chain:
            acc, token = lax.optimization_barrier((acc, token))
        if config.adaptive:
            b_rung = jnp.max(rungs[np.asarray(plan.bucket_leaves[bi])])
            red, unsent, fill = lax.switch(b_rung, branches, acc,
                                           bucket_keys[bi])
        else:
            red, unsent, fill = _segment_reducer(
                _mode_spec(config), config)(acc, bucket_keys[bi])
        if chain:
            token = red[:1]
        out_parts.append(red)
        unsent_parts.append(unsent)
        fill_parts.append(fill)
    if "fill" in state:
        _update_fill_state(new_state, state, fill_parts,
                           plan.bucket_sizes, config)

    out_leaves = _split_flat(jnp.concatenate(out_parts) if n_buckets > 1
                             else out_parts[0], plan)
    if has_ef:
        ef_leaves = _split_flat(jnp.concatenate(unsent_parts)
                                if n_buckets > 1 else unsent_parts[0], plan)
        new_state["ef"] = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(state["ef"]), ef_leaves)

    if config.adaptive:
        # policy update: psum'd per-leaf norms -> ratio EMA -> windowed
        # rung step (identical on every participant by construction).
        # ONE batched psum for all 2*n_leaves scalars — per-collective
        # launch latency sits in the hot path this module optimizes.
        eps = 1e-12
        n_leaves = len(leaves)
        local_n2 = jnp.stack(
            [jnp.sum(jnp.square(l)) for l in leaves]
            + [jnp.sum(jnp.square(e)) for e in ef_leaves])
        summed_n2 = lax.psum(local_n2, axes)
        g_n2, r_n2 = summed_n2[:n_leaves], summed_n2[n_leaves:]
        ratio = jnp.sqrt(r_n2 / (g_n2 + eps))
        beta = 1.0 - 1.0 / config.adaptive_window
        ema = beta * state["ema"] + (1.0 - beta) * ratio
        tick = state["tick"] + 1
        up = (ema > config.adaptive_target).astype(jnp.int32)
        down = (ema < 0.5 * config.adaptive_target).astype(jnp.int32)
        proposed = jnp.clip(state["rung"] + up - down, 0, len(lad) - 1)
        new_state["rung"] = jnp.where(tick % config.adaptive_window == 0,
                                      proposed, state["rung"])
        new_state["ema"] = ema
        new_state["tick"] = tick

    return jax.tree_util.tree_unflatten(treedef, out_leaves), new_state


def reduce_gradients(grads: Any, state: dict, config: GradReduceConfig
                     ) -> Tuple[Any, dict]:
    """Sum ``grads`` across the mesh's reduction axes under ``config``.

    MUST run inside an SPMD context (``shard_map``) with
    ``reduction_axes(config)`` bound; ``grads`` are this participant's
    local contributions (their sum over participants is the quantity being
    approximated), ``state`` is this participant's squeezed reducer state
    (:func:`squeeze_state`).  Returns ``(reduced, new_state)``.
    ``mode="exact"`` is a plain per-leaf ``lax.psum`` over all reduction
    axes (hierarchical exact differs from the flat psum only in f32
    summation order).

    ``bucket_count > 0`` (or ``adaptive``) routes through the bucketed
    transport (:func:`_reduce_bucketed`): exact stays bit-identical
    (psum is elementwise — asserted in tests), compressed modes select
    top-k per bucket instead of per leaf.
    """
    if _bucketed(config):
        return _reduce_bucketed(grads, state, config)
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    axes = reduction_axes(config)
    hier = config.dcn_axis is not None

    if config.mode == "exact":
        if not hier:
            return (jax.tree_util.tree_unflatten(
                treedef, [lax.psum(g, axes) for g in leaves]), state)
        out = []
        for g in leaves:
            shard, _ = _hier_scatter(g.reshape(-1), config.axis)
            shard = lax.psum(shard, config.dcn_axis)
            out.append(_hier_gather(shard, config.axis, g.size, g.shape))
        return jax.tree_util.tree_unflatten(treedef, out), state

    if config.mode == "topk":
        proto = resolved_wire_protocol(config)
        ef_leaves = jax.tree_util.tree_leaves(state["ef"])
        out, new_ef, fills = [], [], []
        for g, res in zip(leaves, ef_leaves):
            if not hier:
                acc = (g + res).reshape(-1)
                reduced, unsent, fill = _topk_allreduce(
                    acc, axes, config.density, proto)
                out.append(reduced.reshape(g.shape))
                new_ef.append(unsent.reshape(g.shape))
                fills.append(fill)
                continue
            # hierarchical: residual lives in the full gradient domain but
            # is nonzero only in this participant's own ICI slice, so the
            # reduce-scatter below re-injects it into exactly its shard.
            acc = (g + res).reshape(-1)
            shard, n_pad = _hier_scatter(acc, config.axis)
            reduced, unsent, fill = _topk_allreduce(
                shard, config.dcn_axis, config.density, proto,
                uniform_axes=reduction_axes(config))
            out.append(_hier_gather(reduced, config.axis, g.size, g.shape))
            new_ef.append(_embed_shard(unsent, config.axis, g.size,
                                       n_pad).reshape(g.shape))
            fills.append(fill)
        new_state = dict(state)
        new_state["ef"] = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(state["ef"]), new_ef)
        if "fill" in state:
            _update_fill_state(new_state, state, fills,
                               [g.size for g in leaves], config)
        return jax.tree_util.tree_unflatten(treedef, out), new_state

    # int8: one fresh rounding key per step, split per leaf
    key, use = jax.random.split(state["key"])
    leaf_keys = jax.random.split(use, max(len(leaves), 1))
    out = []
    for li, g in enumerate(leaves):
        if not hier:
            out.append(_int8_allreduce(g.reshape(-1), axes,
                                       config.block_size, leaf_keys[li],
                                       config.int8_accum).reshape(g.shape))
            continue
        shard, _ = _hier_scatter(g.reshape(-1), config.axis)
        shard = _int8_allreduce(shard, config.dcn_axis, config.block_size,
                                leaf_keys[li], config.int8_accum)
        out.append(_hier_gather(shard, config.axis, g.size, g.shape))
    new_state = dict(state)
    new_state["key"] = key
    return jax.tree_util.tree_unflatten(treedef, out), new_state


# ---------------------------------------------------------------------------
# overlap pipeline (SPMD context) + host-side drain
# ---------------------------------------------------------------------------


def pipelined_reduce(grads: Any, state: dict, config: GradReduceConfig
                     ) -> Tuple[Any, dict]:
    """The one-step-stale pipelined reduce: reduces the CARRIED pending
    gradient (the previous step's) and stores ``grads`` as the new
    pending.  The returned ``reduced`` has no data dependence on this
    step's ``grads``, so its bucket collectives can overlap the step's
    forward/backward compute — the schedule MLFabric argues for.  Legal
    under error feedback: the residual absorbs the staleness exactly as
    it absorbs sparsification.  The first step reduces the
    zeros-initialized pending — a deterministic no-op apply (top-k of
    zeros sends zeros, int8 quantizes zeros to zeros) — so no validity
    flag is needed.  Callers flush with :func:`drain_pending` at fit
    end; mid-fit checkpoints carry ``pending`` like any other state leaf
    and resume the schedule exactly."""
    pending = state["pending"]
    core = {k: v for k, v in state.items() if k != "pending"}
    reduced, new_core = reduce_gradients(pending, core, config)
    new_core["pending"] = grads
    return reduced, new_core


def drain_pending(state: dict) -> Any:
    """Host-side exact drain of everything a finished overlapped fit has
    not yet applied: the participant-sum of the carried ``pending``
    gradient plus the EF residual (both per-participant, stacked over
    the leading dim — for the hierarchical layout the residual slices
    are disjoint per participant, so the plain sum is exact there too).
    One apply at fit end costs one exact all-reduce worth of bytes and
    leaves zero unsent mass behind."""
    pend = jax.tree_util.tree_map(
        lambda a: np.asarray(a, np.float32).sum(0), state["pending"])
    if "ef" in state:
        ef = jax.tree_util.tree_map(
            lambda a: np.asarray(a, np.float32).sum(0), state["ef"])
        pend = jax.tree_util.tree_map(lambda p, e: p + e, pend, ef)
    return pend


# ---------------------------------------------------------------------------
# bytes-on-wire accounting (host side)
# ---------------------------------------------------------------------------


def _spec_payload(n: int, spec, config: GradReduceConfig) -> int:
    """Bytes ONE participant contributes for one ``n``-element transport
    unit at rung ``spec`` (a density, ``"int8"``, or ``"exact"``) on the
    compressed hop."""
    if spec == "exact":
        return 4 * n
    if spec == "int8":
        nb = -(-n // config.block_size)
        return n + 4 * nb                  # int8 payload + f32 scales
    # int32 index + f32 value per sent entry
    return 8 * _topk_k(n, float(spec))


def _transport_units(grads_like: Any, config: GradReduceConfig, rungs=None):
    """The (element count, rung spec) pairs the reduce actually ships:
    per leaf on the legacy path, per bucket when bucketed/adaptive —
    with each bucket's rung resolved exactly as :func:`_reduce_bucketed`
    resolves it (max over the bucket's leaves; ``rungs=None`` uses the
    initial rung everywhere)."""
    if not _bucketed(config):
        sizes = [int(np.prod(np.shape(g), dtype=np.int64) or 1)
                 for g in jax.tree_util.tree_leaves(grads_like)]
        return [(n, _mode_spec(config)) for n in sizes]
    plan = plan_buckets(grads_like, config)
    if not config.adaptive:
        return [(hi - lo, _mode_spec(config)) for lo, hi in plan.ranges]
    lad = effective_ladder(config)
    if rungs is None:
        rungs = [_initial_rung(config)] * len(plan.leaf_sizes)
    rungs = [int(r) for r in np.asarray(rungs).reshape(-1)]
    return [(hi - lo, lad[max(rungs[l] for l in plan.bucket_leaves[bi])])
            for bi, (lo, hi) in enumerate(plan.ranges)]


def _rd_wire_unit(n: int, k: int, p: int) -> Tuple[float, float]:
    """Analytic (best, worst) per-participant bytes-on-wire for ONE
    ``n``-element hop unit shipping ``k`` (index, value) entries under
    recursive halving/doubling over ``p`` participants — total bytes
    across the hop divided by ``p``.

    Best case: every participant picks the same support, so the union
    never grows — halving routes ``k(1 - 1/core)`` entries per rank,
    doubling gathers the same back (the SparCML ~P/2 saving over the
    all-gather's ``(p-1)k`` per rank).  Worst case: supports are
    disjoint, capacity doubles every round until the range bound bites,
    and the doubling phase ships ``min(sparse, dense-switchover)``.
    Folding (non-power-of-two p) adds the extras' entry hand-off up
    front and a dense result broadcast at the end — both counted."""
    core, rounds, extras = rd_topology(p)
    n_pad = -(-n // core) * core
    best = 8.0 * k * extras                       # pre-fold hand-off
    best += core * 8.0 * k * (1.0 - 1.0 / core)   # halving, union stays k
    best += 8.0 * k * (core - 1)                  # sparse doubling
    best += 4.0 * n_pad * extras                  # post-fold dense result
    worst = 8.0 * k * extras
    cap = min((2 if extras else 1) * k, n_pad)
    for r in range(rounds):
        half = n_pad >> (r + 1)
        worst += core * 8.0 * min(cap, half)
        cap = min(2 * cap, half) if half else 0
    union = min(p * k, n_pad)
    worst += (core - 1) * min(8.0 * union, 4.0 * n_pad)
    worst += 4.0 * n_pad * extras
    return best / p, worst / p


def _measured_wire_bytes(fill_rows: np.ndarray, rounds: int) -> float:
    """Per-participant measured bytes from fill vectors (participant-
    averaged rows, one per transport unit): 8 B per sparse entry in the
    halving rounds, doubling billed at 8 B/entry sparse blending to
    4 B/element dense by the switchover rate, plus the fold traffic."""
    total = 0.0
    for row in fill_rows:
        sw = float(row[FILL_SWITCH_SLOT])
        total += 8.0 * float(row[:rounds].sum())
        total += float(row[FILL_DOUBLING_BASE:FILL_DOUBLING_BASE
                           + rounds].sum()) * (8.0 - 4.0 * sw)
        total += 8.0 * float(row[FILL_PREFOLD_SLOT])
        total += 4.0 * float(row[FILL_POSTFOLD_SLOT])
    return total


def payload_bytes(grads_like: Any, config: GradReduceConfig, *,
                  ici_size: int = 1, rungs=None, hop_size: int = None,
                  fill=None) -> dict:
    """Honest per-participant, per-step payload accounting: the bytes each
    participant injects into the reduction it is compressing (indices +
    values for topk, int8 payload + per-block f32 scales for int8), vs the
    4-bytes/element dense payload of the same hop.  Schedule multipliers
    (ring ``2(P-1)/P`` for dense all-reduce, ``P-1`` for the all-gather
    sparse form) are deliberately excluded — they depend on the transport,
    the payload does not.

    Bucketed/adaptive configs account per BUCKET (top-k granularity
    follows the transport); ``rungs`` — the realized per-leaf rung
    indices fetched from reducer state — resolves the adaptive ladder,
    defaulting to the initial rung.

    Hierarchical configs report the two fabrics SEPARATELY: the
    compressed DCN hop ships the ICI-scattered shard (unit sizes
    ``ceil(n / ici_size)``) and reports as ``dcn_dense_bytes`` /
    ``dcn_compressed_bytes`` / ``dcn_compression_ratio``; the exact ICI
    reduce-scatter + all-gather bytes ride in ``ici_bytes``;
    ``total_wire_bytes`` sums both fabrics — the single number that used
    to be reported (``compressed_bytes``, kept as the DCN-hop alias) hid
    which fabric the compression actually saved.

    ``hop_size`` (the compressed hop's participant count) unlocks the
    schedule-INCLUSIVE ``wire`` section comparing the two sparse
    transports per participant: the all-gather's ``(P-1) * 8k`` received
    bytes vs recursive halving/doubling's analytic best (overlapping
    supports — the ~P/2 saving) and worst (disjoint supports) bounds,
    per round, fabric split intact (top-k units only; exact/int8 units
    ship the same bytes under either protocol).  ``fill`` — the ``fill``
    reducer-state leaf (participant-stacked or squeezed) — adds the
    MEASURED bytes and per-round fill-in curve of the realized run.
    The legacy fields above stay payload-only and unchanged."""
    units = _transport_units(grads_like, config, rungs)
    hier = config.dcn_axis is not None
    if hier and ici_size > 1:
        hop_units = [(-(-n // ici_size), spec) for n, spec in units]
    else:
        hop_units = units
    dense = sum(4 * n for n, _ in hop_units)
    compressed = sum(_spec_payload(n, spec, config)
                     for n, spec in hop_units)
    report = {
        "mode": config.mode,
        "dense_bytes": int(dense),
        "compressed_bytes": int(compressed),
        "compression_ratio": (round(dense / compressed, 3)
                              if compressed else None),
        "total_wire_bytes": int(compressed),
    }
    if _bucketed(config):
        report["bucket_count"] = len(units)
    if hier:
        # reduce-scatter + all-gather of the full unit over ICI, ring
        # schedule: each participant moves ~2 * 4n * (I-1)/I bytes
        ici = int(sum(
            math.ceil(2 * 4 * n * (ici_size - 1) / max(ici_size, 1))
            for n, _ in units))
        report["ici_bytes"] = ici
        report["dcn_dense_bytes"] = int(dense)
        report["dcn_compressed_bytes"] = int(compressed)
        report["dcn_compression_ratio"] = report["compression_ratio"]
        report["total_wire_bytes"] = int(compressed) + ici
    report["wire_protocol"] = (
        "rd" if _rd_engaged(config) else "allgather")
    if hop_size is not None and hop_size > 1:
        tk = [(n, float(spec)) for n, spec in hop_units
              if not isinstance(spec, str)]
        if tk:
            p = int(hop_size)
            core, rounds, extras = rd_topology(p)
            allgather = sum(8.0 * _topk_k(n, d) * (p - 1) for n, d in tk)
            best = worst = 0.0
            for n, d in tk:
                b, w = _rd_wire_unit(n, _topk_k(n, d), p)
                best += b
                worst += w
            wire = {
                "hop_participants": p,
                "core": core,
                "rounds": rounds,
                "extras": extras,
                "topk_units": len(tk),
                "allgather_bytes": int(round(allgather)),
                "rd_bytes_best": int(round(best)),
                "rd_bytes_worst": int(round(worst)),
                "rd_bytes_measured": None,
                "fill_rounds_measured": None,
                "switch_rate_measured": None,
                "reduction_vs_allgather_best": (
                    round(allgather / best, 3) if best else None),
                "reduction_vs_allgather_measured": None,
            }
            if fill is not None:
                f = np.asarray(fill, np.float32)
                if f.ndim == 3:          # participant-stacked state leaf
                    f = f.mean(axis=0)
                if f.ndim == 1:
                    f = f[None]
                measured = _measured_wire_bytes(f, rounds)
                wire["rd_bytes_measured"] = round(float(measured), 1)
                wire["fill_rounds_measured"] = [
                    round(float(v), 2) for v in f[:, :rounds].sum(axis=0)]
                wire["switch_rate_measured"] = round(
                    float(f[:, FILL_SWITCH_SLOT].mean()), 3)
                if measured:
                    wire["reduction_vs_allgather_measured"] = round(
                        allgather / measured, 3)
            report["wire"] = wire
    return report


def bucket_report(grads_like: Any, config: GradReduceConfig,
                  rungs=None) -> dict:
    """The analytic bucket plan (pure shape math, device-independent,
    so it is there where nothing can be timed): bucket count,
    dense bytes per bucket, each bucket's resolved rung payload, and the
    per-leaf chosen density (``rungs`` = realized per-leaf rung indices
    from reducer state; ``None`` = the initial rung)."""
    plan = plan_buckets(grads_like, config)
    units = _transport_units(grads_like, config, rungs)
    lad = effective_ladder(config) if config.adaptive else ()
    if config.adaptive:
        if rungs is None:
            leaf_rungs = [_initial_rung(config)] * len(plan.leaf_sizes)
        else:
            leaf_rungs = [int(r) for r in np.asarray(rungs).reshape(-1)]
        leaf_specs = [lad[r] for r in leaf_rungs]
    else:
        leaf_specs = [_mode_spec(config)] * len(plan.leaf_sizes)

    def spec_entry(spec):
        if spec == "exact":
            return {"mode": "exact", "density": 1.0}
        if spec == "int8":
            return {"mode": "int8", "density": None}
        return {"mode": "topk", "density": float(spec)}

    # the transfer schedule _reduce_bucketed enforces: hierarchical
    # compressed reduces chain buckets in consumption order (earliest-
    # needed first); everything else launches unordered.
    chained = (config.dcn_axis is not None and config.mode != "exact"
               and config.dcn_schedule == "earliest" and len(units) > 1)
    return {
        "bucket_count": len(units),
        "bucket_bytes": [4 * n for n, _ in units],
        "bucket_payload_bytes": [_spec_payload(n, spec, config)
                                 for n, spec in units],
        "per_leaf": [{"leaf": i, "elems": plan.leaf_sizes[i],
                      **spec_entry(leaf_specs[i])}
                     for i in range(len(plan.leaf_sizes))],
        "schedule": {
            "policy": (config.dcn_schedule
                       if config.dcn_axis is not None else None),
            "order": list(range(len(units))) if chained else None,
        },
    }
