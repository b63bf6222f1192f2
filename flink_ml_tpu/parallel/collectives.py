"""Collective helpers for explicit-SPMD iteration bodies.

The reference's data plane is Flink's netty shuffle chosen by partitioners
(SURVEY §2.10); the TPU-native data plane is XLA collectives over ICI.  Most
bodies never call these directly — jit + NamedSharding lets XLA insert them —
but explicit ``shard_map`` bodies (ring attention, custom reductions, the
termination vote) use this thin, named vocabulary.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "FILL_VEC_LEN",
    "psum",
    "pmean",
    "pmax",
    "all_gather",
    "reduce_scatter",
    "ppermute_ring",
    "axis_index",
    "axis_size",
    "shard_map_fn",
    "sparse_all_reduce",
    "sparse_all_reduce_rd",
    "fixed_point_all_reduce",
    "quantized_all_reduce",
    "rd_topology",
]

# Fixed layout of the per-call fill-in vector returned by
# :func:`sparse_all_reduce_rd`.  The slot count is independent of the
# participant count (rounds <= FILL_ROUND_SLOTS, i.e. hops up to 2**16
# participants) so reducer state carrying these vectors keeps ONE static
# shape across elastic resizes — the same invariant every other
# grad_reduce state leaf obeys.
FILL_ROUND_SLOTS = 16                           # halving slots [0, 16)
FILL_DOUBLING_BASE = FILL_ROUND_SLOTS           # doubling slots [16, 32)
FILL_UNION_SLOT = 2 * FILL_ROUND_SLOTS          # 32: union |support| count
FILL_SWITCH_SLOT = FILL_UNION_SLOT + 1          # 33: 1.0 if densified
FILL_PREFOLD_SLOT = FILL_SWITCH_SLOT + 1        # 34: entries sent pre-fold
FILL_POSTFOLD_SLOT = FILL_PREFOLD_SLOT + 1      # 35: elements sent post-fold
FILL_VEC_LEN = FILL_POSTFOLD_SLOT + 1           # 36


def psum(x: Any, axis: str) -> Any:
    """All-reduce sum over a mesh axis (the gradient/centroid aggregation
    that replaces the reference's keyed reduce + network shuffle)."""
    return lax.psum(x, axis)


def pmean(x: Any, axis: str) -> Any:
    return lax.pmean(x, axis)


def pmax(x: Any, axis: str) -> Any:
    return lax.pmax(x, axis)


def all_gather(x: Any, axis: str, *, tiled: bool = True) -> Any:
    """Gather shards along the leading dim (the broadcast-variable fan-in)."""
    return lax.all_gather(x, axis, tiled=tiled)


def reduce_scatter(x: Any, axis: str, *, scatter_dimension: int = 0) -> Any:
    return lax.psum_scatter(x, axis, scatter_dimension=scatter_dimension,
                            tiled=True)


def axis_index(axis: str):
    return lax.axis_index(axis)


def axis_size(axis: str) -> int:
    """Static size of a bound mesh axis."""
    return lax.axis_size(axis)


def sparse_all_reduce(idx: jnp.ndarray, vals: jnp.ndarray, n: int,
                      axes) -> jnp.ndarray:
    """All-gather form of a sparse all-reduce over one flat length-``n``
    segment: each participant contributes ``k`` (index, value) pairs, and
    every participant scatter-adds the gathered pairs locally.  The
    LEGACY wire protocol of ``grad_reduce``'s top-k modes — every
    participant receives all P contributions (``(P-1) * 8k`` bytes), the
    P-fold redundancy SparCML's split-allreduce removes; kept as the
    oracle/fallback for multi-axis reductions, with
    :func:`sparse_all_reduce_rd` as the topology-aware replacement.
    Each call is one independent pair of ``all_gather``s with no data
    dependence on any other bucket or on the step's compute, which is
    exactly what lets XLA's latency-hiding scheduler overlap bucket
    ``k`` of step ``n`` with step ``n+1``'s forward/backward."""
    all_idx = lax.all_gather(idx, axes)        # (P, k)
    all_vals = lax.all_gather(vals, axes)
    return jnp.zeros((n,), vals.dtype).at[all_idx.reshape(-1)].add(
        all_vals.reshape(-1))


def rd_topology(p: int) -> Tuple[int, int, int]:
    """``(core, rounds, extras)`` of the recursive-halving/doubling
    schedule over ``p`` participants: a ``core = 2**floor(log2 p)`` rank
    group runs the log2 rounds; the ``extras = p - core`` leftover ranks
    fold their contribution in before round one and receive the result
    after the last round (the standard non-power-of-two embedding)."""
    if p < 1:
        raise ValueError(f"participant count must be >= 1, got {p}")
    core = 1 << (p.bit_length() - 1)
    rounds = core.bit_length() - 1
    if rounds > FILL_ROUND_SLOTS:
        raise ValueError(
            f"hop of {p} participants needs {rounds} rounds; the fill "
            f"accounting layout caps at {FILL_ROUND_SLOTS}")
    return core, rounds, p - core


def _merge_dedup(idx_a: jnp.ndarray, val_a: jnp.ndarray,
                 idx_b: jnp.ndarray, val_b: jnp.ndarray,
                 sentinel: int, cap: int
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Union two (idx, val) sets, summing values at duplicate indices.
    Invalid entries carry ``idx == sentinel`` (> every real index) and
    ``val == 0``; the output is sorted by index, compacted to the front,
    sentinel-padded, and sliced to ``cap`` (the caller guarantees the
    distinct count fits)."""
    idx = jnp.concatenate([idx_a, idx_b])
    val = jnp.concatenate([val_a, val_b])
    if idx.shape[0] == 0:
        return idx[:cap], val[:cap]
    order = jnp.argsort(idx)
    idx, val = idx[order], val[order]
    first = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), idx[1:] != idx[:-1]])
    seg = jnp.cumsum(first.astype(jnp.int32)) - 1
    m = idx.shape[0]
    out_val = jnp.zeros((m,), val.dtype).at[seg].add(val)
    out_idx = jnp.full((m,), sentinel, idx.dtype).at[seg].min(idx)
    return out_idx[:cap], out_val[:cap]


def sparse_all_reduce_rd(idx: jnp.ndarray, vals: jnp.ndarray, n: int,
                         axis: str,
                         uniform_axes: Optional[Tuple[str, ...]] = None
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Recursive-halving/doubling sparse all-reduce over ONE named axis
    (SparCML's split-allreduce, arXiv:1802.08021): log2(P) halving
    rounds of pairwise ``ppermute`` exchanges route each (index, value)
    set toward the rank that owns its index range — merging partner sets
    with duplicate-index summation at every hop — then log2(P) doubling
    rounds gather the reduced pieces back.  Fill-in (the union support
    growing round over round) is measured, not assumed: when the psum'd
    union count densifies past break-even (sparse doubling at 8 B/entry
    would ship more than dense doubling at 4 B/element, i.e.
    ``2*|union| > n_pad``), the doubling phase switches to dense block
    exchanges — a ``lax.cond`` whose predicate is psum-derived, so every
    participant switches together.  Non-power-of-two P runs a
    ``2**floor(log2 P)`` core with pre/post folding
    (:func:`rd_topology`).

    ``uniform_axes``: every mesh axis of the enclosing ``shard_map``
    whose shards run this reduce concurrently.  The switchover ``cond``
    holds collectives, so its predicate must be identical on EVERY
    device in the program, not just within this hop's subgroup —
    sibling groups along the other axes (e.g. the ICI columns of a
    hierarchical reduce, each compressing a different gradient shard)
    reaching different branches is an XLA collective-order deadlock.
    The union count is therefore ``pmax``'d over the non-hop axes
    before the comparison: one group past break-even switches them
    all.  The ``fill`` union slot still reports THIS group's union —
    accounting stays per-group truth; only the decision is global.

    Contract matches :func:`sparse_all_reduce`: ``0 <= idx < n``
    (duplicate indices within one contribution sum; out-of-range entries
    are dropped), result is the elementwise sum of every participant's
    scattered contribution.  f32 summation ORDER differs from the
    all-gather form (tree order vs gather order), so exact-mode A/B is
    asserted elementwise-close, not bitwise, by callers.

    Returns ``(dense_result (n,), fill (FILL_VEC_LEN,) f32)`` — the fill
    vector carries per-round sent-entry counts (halving slots [0, 16),
    doubling slots [16, 32)), the union count, the switchover flag, and
    the pre/post fold traffic, in the fixed layout the module constants
    name.  grad_reduce carries an EMA of it in reducer state and
    ``payload_bytes`` turns it into measured bytes-on-wire."""
    p = axis_size(axis)
    k = int(idx.shape[0])
    dtype = vals.dtype
    fill = jnp.zeros((FILL_VEC_LEN,), jnp.float32)
    if p == 1 or k == 0:
        dense = jnp.zeros((n,), dtype).at[idx].add(vals, mode="drop")
        return dense, fill
    core, rounds, extras = rd_topology(p)
    n_pad = -(-n // core) * core
    sentinel = n_pad
    rank = lax.axis_index(axis)
    is_core = rank < core

    ok = (idx >= 0) & (idx < n)
    cur_i = jnp.where(ok, idx.astype(jnp.int32), sentinel)
    cur_v = jnp.where(ok, vals, 0).astype(dtype)
    empty_i = jnp.zeros((0,), jnp.int32)
    empty_v = jnp.zeros((0,), dtype)
    # dedup within this participant's own contribution (also compacts)
    cur_i, cur_v = _merge_dedup(cur_i, cur_v, empty_i, empty_v,
                                sentinel, k)
    cnt = jnp.sum(cur_i < sentinel)
    cap = k

    # -- pre-fold: extras hand their set to rank (self - core) ------------
    if extras:
        perm = [(core + i, i) for i in range(extras)]
        r_i = lax.ppermute(cur_i, axis, perm)
        r_v = lax.ppermute(cur_v, axis, perm)
        r_c = lax.ppermute(cnt, axis, perm)
        valid = jnp.arange(k) < r_c          # non-receivers get zeros
        r_i = jnp.where(valid, r_i, sentinel)
        r_v = jnp.where(valid, r_v, 0)
        m_i, m_v = _merge_dedup(cur_i, cur_v, r_i, r_v, sentinel, 2 * k)
        pad_i = jnp.full((2 * k,), sentinel, jnp.int32)
        cur_i = jnp.where(is_core, m_i, pad_i)
        cur_v = jnp.where(is_core, m_v, 0)
        fill = fill.at[FILL_PREFOLD_SLOT].set(
            jnp.where(is_core, 0, cnt).astype(jnp.float32))
        cap = 2 * k

    # -- recursive halving: route entries to their range owner ------------
    lo = jnp.zeros((), jnp.int32)
    width = n_pad
    for r in range(rounds):
        dist = core >> (r + 1)
        half = width // 2
        mid = lo + half
        bit = (rank >> (rounds - 1 - r)) & 1
        send_mask = jnp.where(bit == 0, cur_i >= mid, cur_i < mid)
        send_i = jnp.where(send_mask, cur_i, sentinel)
        send_v = jnp.where(send_mask, cur_v, 0)
        sent = jnp.sum(send_mask & (cur_i < sentinel))
        perm = [(i, i ^ dist) for i in range(core)]
        r_i = lax.ppermute(send_i, axis, perm)
        r_v = lax.ppermute(send_v, axis, perm)
        keep_i = jnp.where(send_mask, sentinel, cur_i)
        keep_v = jnp.where(send_mask, 0, cur_v)
        cap_next = min(2 * cap, half)
        cur_i, cur_v = _merge_dedup(keep_i, keep_v, r_i, r_v,
                                    sentinel, cap_next)
        cap = cap_next
        lo = jnp.where(bit == 0, lo, mid)
        width = half
        fill = fill.at[r].set(sent.astype(jnp.float32))

    # -- measured fill-in decides the doubling wire format ----------------
    cnt = jnp.sum(cur_i < sentinel)
    union = lax.psum(jnp.where(is_core, cnt, 0), axis)
    switch_stat = union
    sibling_axes = tuple(a for a in (uniform_axes or ()) if a != axis)
    if sibling_axes:
        switch_stat = lax.pmax(switch_stat, sibling_axes)
    switched = (2 * switch_stat) > n_pad
    w = n_pad // core

    def _sparse_doubling(args):
        ci, cv, _ = args
        d = []
        for j in range(rounds):
            dist = 1 << j
            perm = [(i, i ^ dist) for i in range(core)]
            d.append(jnp.sum(ci < sentinel).astype(jnp.float32))
            r_i = lax.ppermute(ci, axis, perm)
            r_v = lax.ppermute(cv, axis, perm)
            # partner ranges are disjoint from mine: concat, no dedup
            ci = jnp.concatenate([ci, r_i])
            cv = jnp.concatenate([cv, r_v])
        dense = jnp.zeros((n_pad,), dtype).at[ci].add(cv, mode="drop")
        return dense, jnp.stack(d)

    def _dense_doubling(args):
        ci, cv, lo_ = args
        dense = jnp.zeros((n_pad,), dtype).at[ci].add(cv, mode="drop")
        d = []
        for j in range(rounds):
            dist = 1 << j
            size = w << j
            start = ((rank >> j) << j) * w
            piece = lax.dynamic_slice(dense, (start,), (size,))
            perm = [(i, i ^ dist) for i in range(core)]
            recv = lax.ppermute(piece, axis, perm)
            partner_start = (((rank ^ dist) >> j) << j) * w
            dense = lax.dynamic_update_slice(dense, recv,
                                             (partner_start,))
            d.append(jnp.float32(size))
        return dense, jnp.stack(d)

    dense, d_sent = lax.cond(switched, _dense_doubling, _sparse_doubling,
                             (cur_i, cur_v, lo))
    fill = lax.dynamic_update_slice(fill, d_sent, (FILL_DOUBLING_BASE,))
    fill = fill.at[FILL_UNION_SLOT].set(union.astype(jnp.float32))
    fill = fill.at[FILL_SWITCH_SLOT].set(switched.astype(jnp.float32))

    # -- post-fold: result back out to the extras -------------------------
    if extras:
        perm = [(i, core + i) for i in range(extras)]
        recv = lax.ppermute(dense, axis, perm)
        dense = jnp.where(is_core, dense, recv)
        fill = fill.at[FILL_POSTFOLD_SLOT].set(jnp.where(
            rank < extras, jnp.float32(n_pad), jnp.float32(0)))
        # extras' round slots carry garbage from the rounds they sat out
        round_mask = jnp.arange(FILL_VEC_LEN) < FILL_UNION_SLOT
        fill = jnp.where(jnp.logical_and(round_mask,
                                         jnp.logical_not(is_core)),
                         0.0, fill)
    return dense[:n], fill


def fixed_point_all_reduce(q: jnp.ndarray, axis: str) -> jnp.ndarray:
    """Exact int32 all-reduce by recursive doubling over ONE named axis:
    log2(P) pairwise ``ppermute`` exchanges, each hop ADDING integer
    payloads — SwitchML's in-fabric pool semantics (arXiv:1903.06701)
    emulated per hop, so quantization error stays one rounding per
    participant no matter how many hops the sum crosses, and the result
    is bit-identical on every participant (integer addition is exactly
    associative).  Non-power-of-two P folds the extras in before round
    one and broadcasts the total back after the last round."""
    p = axis_size(axis)
    if p == 1:
        return q
    core, rounds, extras = rd_topology(p)
    rank = lax.axis_index(axis)
    if extras:
        perm = [(core + i, i) for i in range(extras)]
        recv = lax.ppermute(q, axis, perm)   # non-receivers: int zeros
        q = q + recv
    for j in range(rounds):
        dist = 1 << j
        perm = [(i, i ^ dist) for i in range(core)]
        recv = lax.ppermute(q, axis, perm)
        q = q + recv
    if extras:
        perm = [(i, core + i) for i in range(extras)]
        recv = lax.ppermute(q, axis, perm)
        q = jnp.where(rank >= core, recv, q)
    return q


def quantized_all_reduce(q: jnp.ndarray, scale: jnp.ndarray,
                         axes) -> jnp.ndarray:
    """Dequantize-and-sum all-reduce of one block-quantized segment:
    ``q`` (nb, block) int8 payload + ``scale`` (nb, 1) f32 per-block
    scales are all-gathered and summed locally.  Like
    :func:`sparse_all_reduce`, one independent collective pair per call —
    the schedulable unit of the bucketed int8 reduce.

    This f32 dequantize-THEN-sum is the **legacy accumulation**
    (``GradReduceConfig.int8_accum="dequant"``, the default): each
    participant's payload is dequantized against its OWN scale before
    the f32 sum, so P stochastic roundings accumulate.  The int32-hop
    alternative (``int8_accum="fixed"``) shares one ``pmax`` scale per
    hop and sums integer codes through :func:`fixed_point_all_reduce`,
    dequantizing once — the two agree within the shared-scale quantum
    envelope (cross-checked in ``tests/test_grad_reduce.py``; an
    agreement envelope, not bit-equality — the orders round
    differently by design)."""
    all_q = lax.all_gather(q, axes)            # (P, nb, block)
    all_scale = lax.all_gather(scale, axes)    # (P, nb, 1)
    return jnp.sum(all_q.astype(jnp.float32) * all_scale, axis=0)


def ppermute_ring(x: Any, axis: str, *, shift: int = 1) -> Any:
    """Rotate shards around the ring formed by a mesh axis (the KV rotation
    of ring attention; rides neighbor ICI links only)."""
    n = axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis, perm)


def shard_map_fn(fn: Callable, mesh: Mesh, in_specs, out_specs,
                 check_vma: bool = False,
                 axis_names: frozenset = frozenset()) -> Callable:
    """``jax.shard_map`` with this framework's default flag: the
    varying-mesh-axes check is off, because ``pallas_call`` out_shapes
    carry no such annotation.  ``axis_names`` names the axes the body
    handles manually (empty: all of the mesh's)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=axis_names,
                         check_vma=check_vma)
