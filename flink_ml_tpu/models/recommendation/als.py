"""ALS — alternating least squares matrix factorization, TPU-native.

Part of the Flink ML 2.x library surface (the reference snapshot ships only
KMeans — SURVEY §2.8 — but the lib module is "the algorithm library"; ALS is
the canonical recommendation member of that line).  Supports explicit
feedback (ALS-WR: per-row regularization scaled by the row's rating count)
and implicit feedback (Hu/Koren confidence weighting,
``c = 1 + alpha * |r|``).

One half-epoch solves all users against fixed item factors (then the
items against the new users).  Its normal equations come in two forms
(``normalEquationsImpl``):

- ``grouped`` (``'sorted'``, and ``'auto'`` wherever one side's dense
  ``(n_groups, rank, rank)`` would outgrow a block): one host plan a side
  (:class:`GroupedPlan`) lays every group's ratings out at a padded
  length, groups of one length together (a rating reaches its slot, its
  group's first plus its rank in the group, by one native counting pass,
  ``native/als_plan.cpp``, and by a stable order of the ratings in NumPy
  where no library loads: the same slots), and the epoch body forms AND
  solves the equations a block of groups at a time under ``lax.scan``:
  gather the other side's rows, ``A_g = Y_g^T diag(w) Y_g`` and ``b_g`` as
  batched contractions over the group's own slots, Cholesky with the
  groups on the lanes, write the block's rows.  The state on the chip is
  one block's ``A``, never the side's.
- ``scatter``: the ratings in their own order, ``.at[group].add`` of one
  outer product a rating into dense ``(n_groups, rank, rank)`` operands,
  then ONE batched Cholesky over all groups.  Small ranks and few groups;
  the workset fit stays on it (it needs the per-rating ids).

Both half-epochs make one epoch, driven by the ``iterate`` runtime in fused
mode: the whole ``max_iter`` loop compiles to a single XLA program, factors
never leave HBM between epochs.

Ratings with weight 0 are padding and contribute nothing (all their
normal-equation contributions are multiplied by the weight).  Users/items
with no observed ratings keep their previous factors (their normal equations
would be singular).
"""

from __future__ import annotations

import ctypes
import functools
import os
from concurrent.futures import ThreadPoolExecutor
from typing import List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint

from ...api.stage import Estimator, Model
from ...data.table import Table
from ...iteration import (
    IterationBodyResult,
    IterationConfig,
    Workset,
    iterate,
    with_program_key,
)
from ...params.param import (
    BoolParam,
    FloatParam,
    IntParam,
    ParamValidators,
    StringParam,
)
from ...obs.trace import tracer
from ...params.shared import HasMaxIter, HasPredictionCol, HasSeed
from ...utils import persist

__all__ = ["ALS", "ALSModel", "ALSParams", "ALSModelParams"]

_CHUNK = 65536  # ratings per scan step: (chunk, rank^2) is the HBM high-water

#: a group's padded length is a multiple of one sublane tile: a block's
#: gathered rows ``(groups * length, rank)`` then reshape to ``(groups,
#: length, rank)`` without moving a word
_MIN_LENGTH = 8

#: the shares of the device's memory that one block's normal equations
#: ``A`` and one block's gathered rows may take, as the chip lays them
#: out (a float32 ``(rank, rank)`` tile padded to 8 sublanes x 128 lanes;
#: a gathered row padded to 128 lanes).  The factorisation works on two
#: more arrays of ``A``'s size, so a block peaks near half of the chip.
_BLOCK_A_SHARE = 1 / 8
_BLOCK_ROWS_SHARE = 1 / 12


#: host threads of a fit's index and plan: the native passes and the
#: sorts and searches over all the ratings release the GIL
_HOST_THREADS = min(8, os.cpu_count() or 1)

#: labels a column needs before it is indexed a part a thread
_THREADED_LABELS = 1 << 20


def _index_labels(labels: np.ndarray) -> tuple:
    """``(ids, index, native)``: ``np.unique(labels, return_inverse=True)``
    and whether the native pass gave it.  A long integer column takes
    ``als_index`` (``native/als_plan.cpp``: a hash of each thread's part,
    only the distinct labels sorted); any other long column a part a
    thread in NumPy (each part's distinct labels, the distinct of those,
    every label's place among them by binary search); a short one
    ``np.unique`` itself."""
    if (len(labels) >= _THREADED_LABELS and labels.dtype.kind in "iu"
            and np.can_cast(labels.dtype, np.int64)
            and (lib := _native_plan()) is not None):
        wide = np.ascontiguousarray(labels, np.int64)
        index = np.empty(len(labels), np.int64)
        # the front of a buffer of one a label: pages never written are
        # never mapped
        ids = np.empty(len(labels), np.int64)
        m = lib.als_index(wide.ctypes.data, len(labels), ids.ctypes.data,
                          index.ctypes.data, _HOST_THREADS)
        return ids[:m].astype(labels.dtype), index, True
    if len(labels) < _THREADED_LABELS or _HOST_THREADS == 1:
        return (*np.unique(labels, return_inverse=True), False)
    parts = np.array_split(labels, _HOST_THREADS)
    with ThreadPoolExecutor(_HOST_THREADS) as pool:
        ids = np.unique(np.concatenate(list(pool.map(np.unique, parts))))
        index = np.concatenate(list(pool.map(
            lambda part: np.searchsorted(ids, part), parts)))
    return ids, index, False


def _round_up(n: int, to: int) -> int:
    return -(-n // to) * to


def _block_sizes(rank: int) -> tuple:
    """``(groups, slots)`` a block may hold, from the rank and the first
    device's memory (16 GiB where the backend does not say)."""
    stats = jax.local_devices()[0].memory_stats() or {}
    memory = int(stats.get("bytes_limit", 16 << 30))
    row = 4 * _round_up(rank, 128)           # bytes of one padded row
    groups = int(memory * _BLOCK_A_SHARE) // (_round_up(rank, 8) * row)
    slots = int(memory * _BLOCK_ROWS_SHARE) // row
    # whole powers of two: the same blocks on every device of one size
    return (1 << max(groups.bit_length() - 1, 0),
            1 << max(slots.bit_length() - 1, 3))


@functools.lru_cache(maxsize=None)
def _native_plan() -> Optional[ctypes.CDLL]:
    """``native/als_plan.cpp`` built and loaded, or ``None`` on a machine
    with no ``make`` and no built library (the labels are then indexed
    and the plan places its ratings in NumPy): ``load_native_lib``'s
    policy."""
    from ...utils.native_lib import load_native_lib

    lib = load_native_lib("als_plan")
    if lib is not None:
        pointer, size = ctypes.c_void_p, ctypes.c_int64
        lib.als_place.argtypes = [pointer, size, pointer, size, size,
                                  pointer, pointer, pointer,
                                  pointer, pointer, pointer, size]
        lib.als_place.restype = ctypes.c_int
        lib.als_index.argtypes = [pointer, size, pointer, pointer, size]
        lib.als_index.restype = size
    return lib


def _stable_group_order(group_idx: np.ndarray, n_groups: int) -> np.ndarray:
    """``np.argsort(group_idx, kind="stable")`` in passes of 16 bits: a
    stable sort of 16-bit keys is NumPy's radix sort, linear in the
    ratings where the comparison sort of int32 keys is not.  Serves the
    plan's NumPy form only (:attr:`GroupedPlan.slot`): the native pass
    orders nothing."""
    order = np.argsort((group_idx & 0xFFFF).astype(np.uint16), kind="stable")
    if n_groups > 1 << 16:
        high = (group_idx >> 16).astype(np.uint16)[order]
        order = order[np.argsort(high, kind="stable")]
    return order


def _padded_lengths(counts: np.ndarray) -> np.ndarray:
    """The slots a group of ``counts`` ratings gets: the next of 8, 16,
    24, 32, 48, 64, 96, ... (two lengths an octave, every one a multiple
    of 8), so that about a sixth of the slots is padding where whole
    powers of two would make it a third."""
    power = 1 << np.ceil(np.log2(np.maximum(counts, _MIN_LENGTH))).astype(
        np.int64)
    three_quarters = 3 * power // 4
    return np.where((counts <= three_quarters) & (power >= 32),
                    three_quarters, power)


class GroupedClass(NamedTuple):
    """The groups of one padded ``length``: every block of the plan holds
    ``groups`` of them; ``rows`` ``(blocks, groups)`` names them
    (``n_groups`` fills the last block's tail: its slots have weight 0 and
    its row is dropped); ``offset`` is the class's first flat slot."""

    length: int
    groups: int
    rows: np.ndarray
    offset: int


class ClassShape(NamedTuple):
    """A :class:`GroupedClass` without its rows."""

    length: int
    groups: int


class PlanShape(NamedTuple):
    """What the epoch body's program follows from of a
    :class:`GroupedPlan` beside its arrays' shapes (:attr:`GroupedPlan.
    shape`): small, hashable, no array in it.  The body takes this view
    and not the plan, so that a program ``iterate`` keeps for it
    (``iteration/body.py: with_program_key``) cannot keep a fit's 24.8 M
    group indices alive."""

    n_groups: int
    rank: int
    parts: int
    classes: tuple

    @property
    def block_groups(self) -> int:
        """Groups a block holds, fill included."""
        return sum(c.groups for c in self.classes)


class GroupedPlan:
    """Static layout of one side's ratings for the grouped normal
    equations: one host pass a fit (the ratings are fixed for the whole
    fit, the same replay insight as the LR/WDL static routes).

    A group of ``n`` ratings gets :func:`_padded_lengths` slots; the
    groups of one length make a class, and every one of the plan's
    ``blocks`` holds the same number of groups of each class, so that all
    blocks have one shape: at most ``block_groups`` groups, and of no
    class more than ``block_slots`` slots.  A group with more ratings than
    ``block_slots`` is split instead into parts of that many slots, one
    part a scan step, whose partial sums the scan carries (``split_rows``
    ``(parts, 1)``, ``first`` / ``last`` ``(parts,)``).  A group with no
    rating is nowhere (it keeps its factors).

    The constructor counts and lays out; it places nothing.  Rating ``k``
    of group ``g`` belongs in the flat slots (class after class, then the
    parts) at ``g``'s first slot plus the number of ``g``'s ratings before
    ``k``; the slots no rating fills are padding, of weight 0.
    :meth:`arrays` puts the columns there by one native counting pass
    (``native/als_plan.cpp``: no order of the ratings, no slot index) or,
    where no library loads, through :attr:`slot`, the same places made in
    NumPy from a stable order of the ratings by group."""

    def __init__(self, group_idx: np.ndarray, n_groups: int, rank: int,
                 block_groups: Optional[int] = None,
                 block_slots: Optional[int] = None):
        self._group_idx = np.ascontiguousarray(group_idx, np.int64)
        self._slot0 = self._lay_out(
            np.bincount(self._group_idx, minlength=n_groups), rank,
            block_groups, block_slots)

    @classmethod
    def of_counts(cls, counts: np.ndarray, rank: int,
                  block_groups: Optional[int] = None,
                  block_slots: Optional[int] = None) -> "GroupedPlan":
        """The plan's classes for groups of ``counts`` ratings, without a
        place for any rating: the shapes of the epoch body's program."""
        plan = cls.__new__(cls)
        plan._group_idx = None
        plan._slot0 = plan._lay_out(np.asarray(counts), rank, block_groups,
                                    block_slots)
        return plan

    def _lay_out(self, counts, rank, block_groups, block_slots):
        """Sets the classes and the split parts; returns every group's
        first slot."""
        sized = _block_sizes(rank)
        block_groups = int(block_groups or sized[0])
        block_slots = int(block_slots or sized[1])
        if block_slots % _MIN_LENGTH:
            raise ValueError("block_slots must be a multiple of "
                             f"{_MIN_LENGTH}")
        n_groups = len(counts)
        self.n_groups, self.nnz, self.rank = n_groups, int(counts.sum()), rank
        self.counts = counts
        length = _padded_lengths(counts)
        whole = (counts > 0) & (length <= block_slots)
        sizes, members = np.unique(length[whole], return_counts=True)
        self.blocks = int(max(
            [1, -(-int(whole.sum()) // block_groups)]
            + [-(-int(n) // (block_slots // int(size)))
               for size, n in zip(sizes, members)]))
        slot0 = np.zeros(n_groups, np.int64)     # a group's first slot
        self.classes, offset = [], 0
        for size in sizes:
            ids = np.flatnonzero(whole & (length == size))
            groups = -(-len(ids) // self.blocks)
            rows = np.full(self.blocks * groups, n_groups, np.int32)
            rows[:len(ids)] = ids
            slot0[ids] = offset + np.arange(len(ids)) * int(size)
            self.classes.append(GroupedClass(
                int(size), groups, rows.reshape(self.blocks, groups),
                offset))
            offset += self.blocks * groups * int(size)
        self.split_offset, self.split_length = offset, block_slots
        long_ = np.flatnonzero(counts > block_slots)
        parts = -(-counts[long_] // block_slots)
        ends = np.cumsum(parts)
        self.parts = int(parts.sum())
        slot0[long_] = offset + (ends - parts) * block_slots
        self.split_rows = np.repeat(long_, parts).astype(np.int32)[:, None]
        self.first = np.zeros(self.parts, bool)
        self.last = np.zeros(self.parts, bool)
        self.first[ends - parts], self.last[ends - 1] = True, True
        self.slots = int(offset + self.parts * block_slots)
        return slot0

    @property
    def block_groups(self) -> int:
        """Groups a block holds, fill included."""
        return sum(c.groups for c in self.classes)

    @property
    def shape(self) -> PlanShape:
        """The plan as the epoch body's program sees it."""
        return PlanShape(self.n_groups, self.rank, self.parts, tuple(
            ClassShape(c.length, c.groups) for c in self.classes))

    @property
    def padded_share(self) -> float:
        """Pad slots over all slots."""
        return 1.0 - self.nnz / max(self.slots, 1)

    @property
    def slot(self) -> Optional[np.ndarray]:
        """Every rating's place in the flat slots, made on demand in
        NumPy (``None`` for a plan :meth:`of_counts`): in the stable order
        by group the k-th rating goes to its group's first slot plus its
        rank in the group, which is k, shifted group by group."""
        if self._group_idx is None:
            return None
        shift = self._slot0 - (np.cumsum(self.counts) - self.counts)
        slot = np.empty(self.nnz, np.int64)
        slot[_stable_group_order(self._group_idx, self.n_groups)] = (
            np.arange(self.nnz) + np.repeat(shift, self.counts))
        return slot

    def _by_block(self, flat: np.ndarray) -> tuple:
        """Flat slots as the epoch body scans them: ``(a (blocks, groups *
        length) array a class, the (parts, block_slots) array of the split
        groups)``."""
        return (tuple(flat[c.offset:c.offset + c.rows.size * c.length]
                      .reshape(self.blocks, -1) for c in self.classes),
                flat[self.split_offset:].reshape(self.parts, self.split_length))

    def arrange(self, values: np.ndarray,
                slot: Optional[np.ndarray] = None) -> tuple:
        """``values`` (one a rating) in the plan's slots, the padding 0,
        :meth:`_by_block`: the NumPy form, one scatter through ``slot``
        (:attr:`slot` where none is given)."""
        flat = np.zeros(self.slots, np.asarray(values).dtype)
        flat[self.slot if slot is None else slot] = values
        return self._by_block(flat)

    def _place_native(self, lib, other_idx, ratings, weights) -> list:
        """The columns in the plan's slots by ``als_place``: ratings in
        their own order, every column written as the pass goes."""
        columns = [np.ascontiguousarray(other_idx, np.int64),
                   np.ascontiguousarray(ratings, np.float32),
                   None if weights is None
                   else np.ascontiguousarray(weights, np.float32)]
        if any(c is not None and c.shape != (self.nnz,) for c in columns):
            raise ValueError(f"the plan places columns of {self.nnz} "
                             "ratings, one value a rating")
        flats = [None if c is None else np.zeros(self.slots, dtype)
                 for c, dtype in zip(columns,
                                     (np.int32, np.float32, np.float32))]
        # half of the host's threads: a fit plans its two sides at once
        failed = lib.als_place(
            self._group_idx.ctypes.data, self.nnz, self._slot0.ctypes.data,
            self.n_groups, self.slots,
            *(None if a is None else a.ctypes.data for a in columns + flats),
            max(1, _HOST_THREADS // 2))
        if failed:
            raise RuntimeError(f"als_place failed with {failed}: the "
                               "plan's lay-out does not hold its ratings")
        return [self._by_block(flat) for flat in flats if flat is not None]

    def unit_weights(self) -> tuple:
        """What :meth:`arrange` gives for a weight of 1 on every rating,
        made from the groups' counts without a pass over the ratings: a
        group's first ``count`` slots are its ratings."""
        counts = np.append(self.counts, 0)
        whole = tuple(
            (np.arange(c.length) < counts[c.rows][:, :, None]).astype(
                np.float32).reshape(self.blocks, -1) for c in self.classes)
        # a part holds what its group has left after the parts before it
        part = np.arange(self.parts)
        part -= np.maximum.accumulate(np.where(self.first, part, 0))
        left = counts[self.split_rows[:, 0]] - part * self.split_length
        split = (np.arange(self.split_length) < left[:, None]).astype(
            np.float32)
        return whole, split

    def arrays(self, other_idx, ratings, weights=None) -> tuple:
        """What the epoch body scans: ``(whole, split)``.  ``whole`` has
        ``(other side's index, rating, weight, rows)`` a class, leading
        axis the blocks; ``split`` is ``(index, rating, weight, rows,
        first, last)`` over the parts, or ``()`` where no group is split.
        The padding has weight 0 (every term of the normal equations is
        scaled by the weight, which is what makes it inert) and points at
        row 0.  ``weights=None`` is a weight of 1 on every rating."""
        lib = _native_plan()
        if lib is not None:
            cols = self._place_native(lib, other_idx, ratings, weights)
        else:
            columns = [(other_idx, np.int32), (ratings, np.float32)]
            if weights is not None:
                columns.append((weights, np.float32))
            slot = self.slot
            with ThreadPoolExecutor(len(columns)) as pool:
                cols = list(pool.map(
                    lambda c: self.arrange(np.asarray(c[0], c[1]), slot),
                    columns))
        if weights is None:
            cols.append(self.unit_weights())
        whole = tuple(
            (o, r, w, c.rows) for o, r, w, c in zip(
                *(col[0] for col in cols), self.classes))
        split = ()
        if self.parts:
            split = tuple(col[1] for col in cols) + (
                self.split_rows, self.first, self.last)
        return whole, split


def _block_normal_equations(factors, other_idx, ratings, weights,
                            groups: int, implicit: bool, alpha: float):
    """``A`` ``(groups, rank, rank)``, ``b`` ``(groups, rank)`` and the
    observed weight ``(groups,)`` of one block: its slots, group after
    group, each group ``slots / groups`` long."""
    rank = factors.shape[1]
    with jax.named_scope("als.gather"):
        y = factors[other_idx].reshape(groups, -1, rank)
    with jax.named_scope("als.normal_eq"):
        r, w = ratings.reshape(groups, -1), weights.reshape(groups, -1)
        if implicit:
            # Hu/Koren: A += (c-1) y y^T per observed pair, b += c p y
            # with p = 1 (the shared Y^T Y term is added at the solve);
            # b's weight is w + (c-1) w, NOT (1 + conf_m1) * w, which
            # would square fractional weights relative to A's
            conf_m1 = alpha * jnp.abs(r) * w
            aw, bw = conf_m1, w + conf_m1
        else:
            aw, bw = w, w * r
        A = jnp.einsum("gls,glt->gst", y * aw[:, :, None], y,
                       preferred_element_type=jnp.float32)
        b = jnp.einsum("gls,gl->gs", y, bw,
                       preferred_element_type=jnp.float32)
        return A, b, jnp.sum(w, axis=1)


def _block_solve(rank: int, groups: int):
    """The entry of registry op ``als_cholesky_solve``
    (``ops/als_solve_pallas.py``) for a block of ``groups`` systems of
    ``rank`` unknowns: on a TPU a block of a lane tile of groups or more
    is solved a tile at a time inside VMEM, every other block (the split
    groups' one system a step among them) and every block off the TPU by
    the XLA loop over the block's whole factor."""
    from ...kernels.registry import lookup

    return lookup("als_cholesky_solve", sig=(rank, groups))


def _solve_plan(plans, rank: int) -> str:
    """How a fit's blocks are solved, for the record: ``"vmem"`` or
    ``"xla"`` (the backend of :func:`_block_solve`, by its other name),
    ``"<users'>/<items'>"`` where the sides differ."""
    names = ["vmem" if _block_solve(rank, p.block_groups).backend == "pallas"
             else "xla" for p in plans if p.classes]
    return "/".join(dict.fromkeys(names)) or "xla"


def _regularized(A, cnt, gram, reg: float, implicit: bool):
    eye = jnp.eye(A.shape[-1], dtype=A.dtype)
    if implicit:
        return A + gram[None, :, :] + reg * eye[None, :, :]
    # ALS-WR: per-row lambda scaled by the row's rating count.
    return A + (reg * jnp.maximum(cnt, 1.0))[:, None, None] * eye[None, :, :]


def _solve_side_grouped(prev, factors, plan, arrays,
                        reg: float, implicit: bool, alpha: float):
    """Grouped half-epoch: ``prev``-side factors re-solved against fixed
    ``factors`` under ``plan`` (a :class:`GroupedPlan` or, all the epoch
    body has, its :class:`PlanShape`): a block of groups a scan step (class by class the
    gather and the contractions, then one solve for the block), then a
    split group's parts, one a step."""
    gram = factors.T @ factors if implicit else None      # shared Y^T Y
    whole, split = arrays

    def solved_rows(out, A, b, cnt, rows):
        with jax.named_scope("als.normal_eq"):
            A = _regularized(A, cnt, gram, reg, implicit)
        with jax.named_scope("als.solve"):
            # the groups on the lanes: (rank, rank, groups) pads nothing
            # on the chip where (groups, rank, rank) pads every matrix to
            # (8k, 128); At[k, i] = A[i, k], column k
            solve = _block_solve(A.shape[-1], A.shape[0])
            if solve.backend == "pallas":
                # The kernel's operand layout is lane-major.  Left to
                # itself XLA pushes it back through the transposition and
                # the concatenation to every class's contraction: a
                # hundred small transposing copies in place of one, a
                # program that compiles 12 s longer, and a memory-space
                # assignment that gathers 5 M of an iteration's rows from
                # HBM where it had the factors in VMEM (als_gather_ms 157
                # -> 270, my chip run, PR 34).  Pinned group-major, A is
                # transposed by one copy in front of the call and the
                # program around it is the one the XLA backend gets.
                A = with_layout_constraint(A, Layout(major_to_minor=(0, 1, 2)))
            solved = solve.fn(jnp.transpose(A, (2, 1, 0)), b.T).T
        # a group without a rating, and a singular system (regParam 0 and
        # fewer ratings than rank factor to NaN), keep their factors
        # rather than spreading NaN through the next half-epoch's gathers
        ok = ((cnt > 0)[:, None]
              & jnp.all(jnp.isfinite(solved), axis=1, keepdims=True))
        solved = jnp.where(ok, solved, out.at[rows].get(mode="clip"))
        return out.at[rows].set(solved, mode="drop")

    def block(out, xs):
        parts = [_block_normal_equations(factors, o, r, w, c.groups,
                                         implicit, alpha) + (rows,)
                 for (o, r, w, rows), c in zip(xs, plan.classes)]
        A, b, cnt, rows = (jnp.concatenate(p) for p in zip(*parts))
        return solved_rows(out, A, b, cnt, rows), None

    def part(carry, xs):
        out, held = carry
        o, r, w, rows, first, last = xs
        held = tuple(jnp.where(first, new, new + acc) for new, acc in zip(
            _block_normal_equations(factors, o, r, w, 1, implicit, alpha),
            held))
        rows = jnp.where(last, rows, plan.n_groups)
        return (solved_rows(out, *held, rows), held), None

    out = prev
    if plan.classes:
        out, _ = jax.lax.scan(block, out, whole)
    if plan.parts:
        rank = factors.shape[1]
        held = (jnp.zeros((1, rank, rank), prev.dtype),
                jnp.zeros((1, rank), prev.dtype), jnp.zeros((1,), prev.dtype))
        (out, _), _ = jax.lax.scan(part, (out, held), split)
    return out


def _planned_side(group_idx, other_idx, n_groups: int, ratings, rank: int):
    """One side's plan and what the epoch body scans of it."""
    plan = GroupedPlan(group_idx, n_groups, rank)
    return plan, plan.arrays(other_idx, ratings)


def grouped_normal_equations(factors, plan: "GroupedPlan", arrays,
                             implicit: bool = False, alpha: float = 1.0):
    """The grouped form's ``A``, ``b`` and observed weights for ALL groups
    as dense arrays, through the epoch body's own
    :func:`_block_normal_equations`: what the tests hold against the plain
    per-group sums.  The fit never forms these."""
    rank = factors.shape[1]
    dense = (jnp.zeros((plan.n_groups, rank, rank), factors.dtype),
             jnp.zeros((plan.n_groups, rank), factors.dtype),
             jnp.zeros((plan.n_groups,), factors.dtype))
    whole, split = jax.tree_util.tree_map(jnp.asarray, arrays)
    blocks = [(xs, c.groups) for xs, c in zip(whole, plan.classes)]
    for xs, groups in blocks + ([(split[:4], 1)] if plan.parts else []):
        for o, r, w, rows in zip(*xs):
            new = _block_normal_equations(factors, o, r, w, groups,
                                          implicit, alpha)
            dense = tuple(acc.at[rows].add(p, mode="drop")
                          for acc, p in zip(dense, new))
    return dense


class ALSModelParams(HasPredictionCol):
    USER_COL = StringParam("userCol", "User id column.", default="user")
    ITEM_COL = StringParam("itemCol", "Item id column.", default="item")

    def get_user_col(self) -> str:
        return self.get(ALSModelParams.USER_COL)

    def set_user_col(self, value: str):
        return self.set(ALSModelParams.USER_COL, value)

    def get_item_col(self) -> str:
        return self.get(ALSModelParams.ITEM_COL)

    def set_item_col(self, value: str):
        return self.set(ALSModelParams.ITEM_COL, value)


class ALSParams(ALSModelParams, HasMaxIter, HasSeed):
    RATING_COL = StringParam("ratingCol", "Rating column.", default="rating")
    RANK = IntParam("rank", "Factor dimension.", default=10,
                    validator=ParamValidators.gt_eq(1))
    REG_PARAM = FloatParam("regParam", "L2 regularization.", default=0.1,
                           validator=ParamValidators.gt_eq(0))
    IMPLICIT_PREFS = BoolParam(
        "implicitPrefs", "Implicit-feedback (confidence-weighted) mode.",
        default=False)
    ALPHA = FloatParam("alpha", "Implicit-feedback confidence scale.",
                       default=1.0, validator=ParamValidators.gt_eq(0))
    NEQ_IMPL = StringParam(
        "normalEquationsImpl",
        "Normal equations: 'sorted' — one static host plan per fit side "
        "lays each group's ratings out at a padded length, and the epoch "
        "forms and solves the equations a block of groups at a time as "
        "batched MXU contractions over each group's own slots (the "
        "grouped form: the chip holds one block's A); 'scatter' keeps the "
        "jnp .at[].add form over dense (n_groups, rank, rank) operands; "
        "'auto' (default) takes the grouped form wherever a side's dense "
        "operand would be larger than one block.  All are exact up to "
        "f32 summation order.",
        default="auto",
        validator=ParamValidators.in_array(("auto", "sorted", "scatter")))

    def get_rating_col(self) -> str:
        return self.get(ALSParams.RATING_COL)

    def set_rating_col(self, value: str):
        return self.set(ALSParams.RATING_COL, value)

    def get_rank(self) -> int:
        return self.get(ALSParams.RANK)

    def set_rank(self, value: int):
        return self.set(ALSParams.RANK, value)

    def get_reg_param(self) -> float:
        return self.get(ALSParams.REG_PARAM)

    def set_reg_param(self, value: float):
        return self.set(ALSParams.REG_PARAM, value)

    def get_implicit_prefs(self) -> bool:
        return self.get(ALSParams.IMPLICIT_PREFS)

    def set_implicit_prefs(self, value: bool):
        return self.set(ALSParams.IMPLICIT_PREFS, value)

    def get_alpha(self) -> float:
        return self.get(ALSParams.ALPHA)

    def set_alpha(self, value: float):
        return self.set(ALSParams.ALPHA, value)

    WORKSET_TOL = FloatParam(
        "worksetTol",
        "Delta/workset iteration threshold (0 disables): a user/item "
        "whose neighborhood factors all moved less than this (L2 row "
        "movement) last round keeps its previous factors — its solve "
        "result is masked out (the fused program still evaluates the "
        "dense normal equations; the wall-clock win today is that the "
        "while_loop exits as soon as every movement settles below the "
        "threshold, instead of always running maxIter epochs).  "
        "Approximate by construction (masked updates would have moved "
        "< tol); the fit records a per-round report in "
        "estimator.last_workset_report.",
        default=0.0, validator=ParamValidators.gt_eq(0))

    def get_workset_tol(self) -> float:
        return self.get(ALSParams.WORKSET_TOL)

    def set_workset_tol(self, value: float):
        return self.set(ALSParams.WORKSET_TOL, value)


def _normal_equations(factors, group_idx, other_idx, ratings, weights,
                      n_groups: int, implicit: bool, alpha: float):
    """Accumulate per-group A (n_groups, r, r), b (n_groups, r) and observed
    counts, scanning the ratings in fixed-size chunks."""
    rank = factors.shape[1]
    nnz = group_idx.shape[0]
    chunk = min(_CHUNK, nnz)
    n_chunks = -(-nnz // chunk)
    pad = n_chunks * chunk - nnz
    if pad:
        group_idx = jnp.concatenate([group_idx, jnp.zeros(pad, group_idx.dtype)])
        other_idx = jnp.concatenate([other_idx, jnp.zeros(pad, other_idx.dtype)])
        ratings = jnp.concatenate([ratings, jnp.zeros(pad, ratings.dtype)])
        weights = jnp.concatenate([weights, jnp.zeros(pad, weights.dtype)])

    def scan_step(carry, xs):
        A, b, cnt = carry
        g, o, r, w = xs
        y = factors[o]                                    # (chunk, rank)
        if implicit:
            # Hu/Koren: A += (c-1) y y^T per observed pair, b += c p y with
            # p = 1; the shared Y^T Y term is added by the caller.
            conf_m1 = alpha * jnp.abs(r) * w              # c - 1, weighted
            A = A.at[g].add(conf_m1[:, None, None]
                            * y[:, :, None] * y[:, None, :])
            # weighted Hu/Koren b-term: w * (1 + alpha|r|) * y = (w + conf_m1)
            # * y — NOT (1 + conf_m1) * w, which would square fractional
            # weights relative to the A term above.
            b = b.at[g].add((w + conf_m1)[:, None] * y)
        else:
            A = A.at[g].add(w[:, None, None] * y[:, :, None] * y[:, None, :])
            b = b.at[g].add((w * r)[:, None] * y)
        cnt = cnt.at[g].add(w)
        return (A, b, cnt), None

    init = (jnp.zeros((n_groups, rank, rank), factors.dtype),
            jnp.zeros((n_groups, rank), factors.dtype),
            jnp.zeros((n_groups,), factors.dtype))
    xs = tuple(x.reshape(n_chunks, chunk, *x.shape[1:])
               for x in (group_idx, other_idx, ratings, weights))
    (A, b, cnt), _ = jax.lax.scan(scan_step, init, xs)
    return A, b, cnt


def _solve_from_neq(prev, factors, A, b, cnt, reg: float, implicit: bool):
    """The solve tail shared by both normal-equation forms: regularize,
    batched Cholesky, keep previous factors for unobserved/singular
    groups."""
    gram = factors.T @ factors if implicit else None      # shared Y^T Y
    A = _regularized(A, cnt, gram, reg, implicit)
    chol = jax.scipy.linalg.cho_factor(A)
    solved = jax.scipy.linalg.cho_solve(chol, b[..., None])[..., 0]
    # A singular system (regParam=0 + fewer ratings than rank) factors to
    # NaN; keep the previous factors rather than letting NaN spread through
    # the next half-epoch's gathers.
    ok = ((cnt > 0)[:, None]
          & jnp.all(jnp.isfinite(solved), axis=1, keepdims=True))
    return jnp.where(ok, solved, prev)


def _solve_side(prev, factors, group_idx, other_idx, ratings, weights,
                n_groups: int, reg: float, implicit: bool, alpha: float):
    """One half-epoch: re-solve ``prev``-side factors against fixed
    ``factors``.  Groups with zero observed weight keep their previous
    factors."""
    A, b, cnt = _normal_equations(factors, group_idx, other_idx, ratings,
                                  weights, n_groups, implicit, alpha)
    return _solve_from_neq(prev, factors, A, b, cnt, reg, implicit)


def _block_solves(shape: "PlanShape") -> tuple:
    """``(backend, fn)`` of every :func:`_block_solve` the trace of one
    side's half-epoch asks the registry for: its whole blocks', its split
    parts'.  The epoch body's program key names them, since the trace
    reads them from the registry and not from its arguments."""
    sizes = (([shape.block_groups] if shape.classes else [])
             + ([1] if shape.parts else []))
    return tuple((solve.backend, solve.fn) for solve in
                 (_block_solve(shape.rank, groups) for groups in sizes))


def als_epoch_step(n_users: int, n_items: int, reg: float, implicit: bool,
                   alpha: float, plans=None):
    """One ALS epoch (users then items) as an ``iterate`` body.

    ``plans=(shape_u, shape_v)`` (each side's :attr:`GroupedPlan.shape`,
    a :class:`PlanShape`: the body holds no plan) switches to the grouped
    normal equations — the data is then each side's
    :meth:`GroupedPlan.arrays` instead of the raw ``(u_idx, i_idx, r,
    w)``.

    The body states its program key (``iteration/body.py:
    with_program_key``): every argument here, the scatter form's chunk,
    for the grouped form what the registry answers each side's solves
    with, and the module's functions the trace calls."""

    def body(state, epoch, data):
        U, V = state
        # TPU f32 matmuls default to bf16 inputs; the normal equations and
        # triangular solves need true f32 or convergence stalls well short
        # of the CPU result.
        with jax.default_matmul_precision("highest"):
            if plans is None:
                u_idx, i_idx, r, w = data
                U = _solve_side(U, V, u_idx, i_idx, r, w, n_users, reg,
                                implicit, alpha)
                V = _solve_side(V, U, i_idx, u_idx, r, w, n_items, reg,
                                implicit, alpha)
            else:
                plan_u, plan_v = plans
                if not U.shape[1] == plan_u.rank == plan_v.rank:
                    raise ValueError(
                        f"factors of rank {U.shape[1]} under plans of rank "
                        f"{plan_u.rank} and {plan_v.rank}")
                by_user, by_item = data
                U = _solve_side_grouped(U, V, plan_u, by_user, reg,
                                        implicit, alpha)
                V = _solve_side_grouped(V, U, plan_v, by_item, reg,
                                        implicit, alpha)
        return IterationBodyResult(feedback=(U, V))

    # the module's own functions the trace calls, by what their names hold
    # NOW: a test that patches one (the benchmark's fault ``plain_lambda``
    # patches ``_regularized``) must get a program of its own
    how = ((_CHUNK, _solve_side, _normal_equations, _solve_from_neq,
            _regularized) if plans is None else
           (tuple(plans), tuple(_block_solves(p) for p in plans),
            _solve_side_grouped, _block_normal_equations, _regularized))
    return with_program_key(body, als_epoch_step, n_users, n_items, reg,
                            implicit, alpha, how)


def als_workset_epoch_step(n_users: int, n_items: int, reg: float,
                           implicit: bool, alpha: float, tol: float):
    """One workset ALS epoch: the delta-iteration port of
    :func:`als_epoch_step`.

    The workset masks the two factor sides independently
    (``mask={"users": (n_users,), "items": (n_items,)}``): a group stays
    active only while something in its NEIGHBORHOOD still moves — user
    ``u`` re-solves while any item it rated moved ≥ ``tol`` (L2 row
    movement) last round, and symmetrically for items.  A masked group
    keeps its previous factors; since its normal equations are built from
    neighbor rows that all moved < ``tol``, the discarded update would
    have been sub-threshold too — that is the approximation accepted in
    exchange for settling.  Fixed shapes mean the dense solve is still
    evaluated each round (what a compacting backend would skip); the
    wall-clock saving today is the exit: when every movement settles
    below ``tol`` both masks drain and the driver's active-fraction
    criterion ends the fused while_loop strictly before ``maxIter``.

    Uses the raw-index (scatter) data tuple — the movement aggregation
    needs the per-rating (user, item) ids that the grouped layout
    deliberately discards."""

    def body(state, ws, epoch, data):
        U, V = state
        u_idx, i_idx, r, w = data
        m_u, m_i = ws.mask["users"], ws.mask["items"]
        # same precision pin as the BSP body (als_epoch_step)
        with jax.default_matmul_precision("highest"):
            U_solved = _solve_side(U, V, u_idx, i_idx, r, w, n_users, reg,
                                   implicit, alpha)
            U_new = jnp.where(m_u[:, None] > 0, U_solved, U)
            V_solved = _solve_side(V, U_new, i_idx, u_idx, r, w, n_items,
                                   reg, implicit, alpha)
            V_new = jnp.where(m_i[:, None] > 0, V_solved, V)
        du = jnp.sqrt(jnp.sum(jnp.square(U_new - U), axis=1))  # (n_users,)
        dv = jnp.sqrt(jnp.sum(jnp.square(V_new - V), axis=1))  # (n_items,)
        # neighborhood max-movement via scatter-max over the ratings
        moved_u = jnp.zeros((n_users,), du.dtype).at[u_idx].max(dv[i_idx])
        moved_i = jnp.zeros((n_items,), dv.dtype).at[i_idx].max(du[u_idx])
        new_ws = Workset({"users": (moved_u >= tol).astype(jnp.float32),
                          "items": (moved_i >= tol).astype(jnp.float32)})
        return IterationBodyResult(feedback=((U_new, V_new), new_ws))

    return body


@jax.jit
def _predict_pairs(U, V, u_idx, i_idx, known):
    preds = jnp.sum(U[u_idx] * V[i_idx], axis=1)
    return jnp.where(known, preds, jnp.nan)


class ALSModel(ALSModelParams, Model):
    """Prediction: ``U[u] . V[i]`` per (user, item) row; ids unseen at fit
    time predict NaN (the "cold start = nan" convention)."""

    def __init__(self):
        super().__init__()
        self._user_ids: Optional[np.ndarray] = None
        self._item_ids: Optional[np.ndarray] = None
        self._user_factors: Optional[np.ndarray] = None
        self._item_factors: Optional[np.ndarray] = None
        #: the normal-equation form the fit that made this model planned
        #: ("grouped" or "scatter"); None for a model that was loaded
        self.neq_plan: Optional[str] = None
        #: how that fit solved a block of them: "vmem" (a tile of groups
        #: at a time inside VMEM, op ``als_cholesky_solve``'s kernel) or
        #: "xla"; like ``neq_plan`` a record of the fit, not saved with
        #: the model: None for a model that was loaded
        self.solve_plan: Optional[str] = None

    def set_model_data(self, *inputs) -> "ALSModel":
        (t,) = inputs
        self._user_ids = np.asarray(t["userIds"][0])
        self._item_ids = np.asarray(t["itemIds"][0])
        self._user_factors = np.asarray(t["userFactors"][0], np.float32)
        self._item_factors = np.asarray(t["itemFactors"][0], np.float32)
        return self

    def get_model_data(self) -> List[Table]:
        self._require_model()
        return [Table({"userIds": self._user_ids[None],
                       "itemIds": self._item_ids[None],
                       "userFactors": self._user_factors[None],
                       "itemFactors": self._item_factors[None]})]

    def _require_model(self) -> None:
        if self._user_factors is None:
            raise RuntimeError("ALSModel has no model data; call "
                               "set_model_data() or fit an ALS first")

    def _lookup(self, values, ids):
        """Map raw ids to dense indices; (indices, known_mask)."""
        idx = np.searchsorted(ids, values)
        idx = np.clip(idx, 0, len(ids) - 1)
        known = ids[idx] == values
        return idx.astype(np.int32), known

    def transform(self, *inputs) -> List[Table]:
        (table,) = inputs
        self._require_model()
        users = np.asarray(table[self.get_user_col()])
        items = np.asarray(table[self.get_item_col()])
        u_idx, u_known = self._lookup(users, self._user_ids)
        i_idx, i_known = self._lookup(items, self._item_ids)
        preds = np.asarray(_predict_pairs(
            jnp.asarray(self._user_factors), jnp.asarray(self._item_factors),
            jnp.asarray(u_idx), jnp.asarray(i_idx),
            jnp.asarray(u_known & i_known)))
        return [table.with_column(self.get_prediction_col(),
                                  preds.astype(np.float64))]

    def recommend_for_users(self, users, k: int,
                            exclude: Optional[Table] = None) -> Table:
        """Top-k items per user: ONE ``U_sel @ V.T`` MXU matmul scores
        everything, then a host ``argpartition`` (O(items), not a full
        sort) ranks the k winners — the producer shape
        ``RankingEvaluator`` consumes (each output cell is that user's
        ranked item-id list).

        ``exclude`` optionally REMOVES already-seen (user, item) pairs
        (the usual train-interaction filter) given as a Table carrying
        this model's user/item columns; a user with fewer than k
        non-excluded items gets a shorter list.  Unknown user ids
        raise."""
        self._require_model()
        if k <= 0:
            raise ValueError(f"k must be positive, got {k}")
        k = min(k, len(self._item_ids))
        users = np.asarray(users)
        u_idx, known = self._lookup(users, self._user_ids)
        if not known.all():
            raise ValueError(
                f"unknown user id {users[~known][0]!r}; recommendations "
                "need users seen at fit time")

        # np.array (copy): the device result is read-only and the exclude
        # mask writes -inf in place
        scores = np.array(
            jnp.asarray(self._user_factors)[jnp.asarray(u_idx)]
            @ jnp.asarray(self._item_factors).T)
        if exclude is not None:
            eu_idx, eu_known = self._lookup(
                np.asarray(exclude[self.get_user_col()]), self._user_ids)
            ei_idx, ei_known = self._lookup(
                np.asarray(exclude[self.get_item_col()]), self._item_ids)
            valid = eu_known & ei_known
            eu, ei = eu_idx[valid], ei_idx[valid]
            # vectorized (pair -> request rows) expansion: request rows
            # sorted by user, each exclude pair covers its searchsorted
            # range (the ragged-range trick — no per-pair Python loop)
            order = np.argsort(u_idx, kind="stable")
            su = u_idx[order]
            left = np.searchsorted(su, eu, side="left")
            right = np.searchsorted(su, eu, side="right")
            counts = right - left
            total = int(counts.sum())
            if total:
                starts = np.repeat(left, counts)
                offsets = np.arange(total) - np.repeat(
                    np.cumsum(counts) - counts, counts)
                rows = order[starts + offsets]
                scores[rows, np.repeat(ei, counts)] = -np.inf

        part = np.argpartition(-scores, kth=k - 1, axis=1)[:, :k]
        part_scores = np.take_along_axis(scores, part, axis=1)
        rank = np.argsort(-part_scores, axis=1, kind="stable")
        top = np.take_along_axis(part, rank, axis=1)
        top_scores = np.take_along_axis(part_scores, rank, axis=1)

        recs = np.empty(len(users), object)
        rec_scores = np.empty(len(users), object)
        for r in range(len(users)):
            keep = ~np.isneginf(top_scores[r])   # drop excluded items
            recs[r] = list(self._item_ids[top[r][keep]])
            rec_scores[r] = [float(s) for s in top_scores[r][keep]]
        return Table({self.get_user_col(): users,
                      "recommendations": recs, "scores": rec_scores})

    def save(self, path: str) -> None:
        self._require_model()
        persist.save_metadata(self, path)
        persist.save_model_arrays(path, "model", {
            "userIds": self._user_ids, "itemIds": self._item_ids,
            "userFactors": self._user_factors,
            "itemFactors": self._item_factors})

    @classmethod
    def load(cls, path: str) -> "ALSModel":
        model = persist.load_stage_param(path)
        data = persist.load_model_arrays(path, "model")
        model._user_ids = data["userIds"]
        model._item_ids = data["itemIds"]
        model._user_factors = data["userFactors"].astype(np.float32)
        model._item_factors = data["itemFactors"].astype(np.float32)
        return model


class ALS(ALSParams, Estimator[ALSModel]):
    def fit(self, *inputs) -> ALSModel:
        (table,) = inputs
        with tracer.fit_span(type(self).__name__):
            return self._fit(table)

    def _fit(self, table: Table) -> ALSModel:
        """``fit`` under its root span.  The phase spans (``fit.gather``
        with its part ``.index``, ``fit.arrange`` with ``.plan``,
        ``fit.upload``, then ``iterate.dispatch`` inside ``iterate``,
        ``fit.fetch``) follow each other without a gap and add no fence.

        The start is ``default_rng(seed)``: ``U0 = normal(size=(n_users,
        rank)) / sqrt(rank)`` as float32, then ``V0`` the same, rows in
        the order of the sorted distinct ids."""
        # report describes THIS fit only — a reused estimator must not
        # serve a stale report from an earlier workset fit
        self.last_workset_report = None
        with tracer.span("fit.gather", "fit"):
            users = np.asarray(table[self.get_user_col()])
            items = np.asarray(table[self.get_item_col()])
            ratings = np.asarray(table[self.get_rating_col()], np.float32)
            if len(ratings) == 0:
                raise ValueError("ALS.fit requires at least one rating")
            if self.get_implicit_prefs() and np.any(ratings < 0):
                raise ValueError("implicitPrefs expects non-negative "
                                 "ratings (interaction strengths)")
            with tracer.span("fit.gather.index", "fit") as span:
                user_ids, u_idx, u_native = _index_labels(users)
                item_ids, i_idx, i_native = _index_labels(items)
                span.note(native=int(u_native and i_native))
        n_users, n_items = len(user_ids), len(item_ids)
        rank = self.get_rank()

        with tracer.span("fit.arrange", "fit"):
            rng = np.random.default_rng(self.get_seed())
            scale = 1.0 / np.sqrt(rank)
            U0 = (rng.normal(size=(n_users, rank)) * scale).astype(
                np.float32)
            V0 = (rng.normal(size=(n_items, rank)) * scale).astype(
                np.float32)
            ws_tol = self.get_workset_tol()
            neq_mode = self.get(ALSParams.NEQ_IMPL)
            # 'auto': the grouped form wherever one side's dense (n_groups,
            # rank, rank) would be larger than the block it works on
            grouped = ws_tol == 0 and (neq_mode == "sorted" or (
                neq_mode == "auto"
                and max(n_users, n_items) > _block_sizes(rank)[0]))
            with tracer.span("fit.arrange.plan", "fit") as span:
                plans = None
                # loaded (and, on a clean tree, built) before the two
                # sides' threads ask for it
                placed_native = grouped and _native_plan() is not None
                if grouped:
                    # one static host plan per side (the ratings are fixed
                    # for the whole fit), the two sides side by side; the
                    # data ships in the plan's slots, so no per-epoch
                    # permute exists on device
                    with ThreadPoolExecutor(2) as pool:
                        plans, data = zip(*pool.map(
                            lambda side: _planned_side(*side, ratings, rank),
                            ((u_idx, i_idx, n_users), (i_idx, u_idx, n_items))))
                else:
                    data = (u_idx.astype(np.int32), i_idx.astype(np.int32),
                            ratings, np.ones(len(ratings), np.float32))
                solve_plan = _solve_plan(plans, rank) if grouped else "xla"
                span.note(neq_plan="grouped" if grouped else "scatter",
                          solve=solve_plan,
                          placed_native=int(placed_native),
                          route_bytes=sum(int(a.nbytes) for a in
                                          jax.tree_util.tree_leaves(data)))
                if grouped:
                    span.note(padded_share=1.0 - 2.0 * len(ratings)
                              / sum(p.slots for p in plans))

        if ws_tol > 0:
            return self._fit_workset(user_ids, item_ids, data, U0, V0, ws_tol)
        with tracer.span("fit.upload", "fit"):
            # the start goes up flat (a 2-D put of narrow rows costs the
            # host a transposition) and takes its shape on the device
            state = tuple(jnp.asarray(x.ravel()).reshape(x.shape)
                          for x in (U0, V0))
            data = jax.tree_util.tree_map(jnp.asarray, data)
        result = iterate(
            als_epoch_step(n_users, n_items, self.get_reg_param(),
                           self.get_implicit_prefs(), self.get_alpha(),
                           plans=plans and tuple(p.shape for p in plans)),
            state, data,
            max_epochs=self.get_max_iter(),
            config=IterationConfig(mode="fused"),
        )
        with tracer.span("fit.fetch", "fit"):
            U, V = (np.asarray(jax.device_get(x)) for x in result.state)

        model = ALSModel()
        model.copy_params_from(self)
        model.set_model_data(Table({
            "userIds": user_ids[None], "itemIds": item_ids[None],
            "userFactors": U[None], "itemFactors": V[None]}))
        model.neq_plan = "grouped" if grouped else "scatter"
        model.solve_plan = solve_plan
        return model

    def _fit_workset(self, user_ids, item_ids, data, U0, V0,
                     ws_tol: float) -> ALSModel:
        """Workset (delta-iteration) fit: raw-index ``data``, both sides
        masked, convergence-driven while_loop exit (see
        :func:`als_workset_epoch_step`)."""
        with tracer.span("fit.upload", "fit"):
            data = tuple(jnp.asarray(a) for a in data)
        ws0 = Workset({"users": jnp.ones((len(user_ids),), jnp.float32),
                       "items": jnp.ones((len(item_ids),), jnp.float32)})
        result = iterate(
            als_workset_epoch_step(len(user_ids), len(item_ids),
                                   self.get_reg_param(),
                                   self.get_implicit_prefs(),
                                   self.get_alpha(), ws_tol),
            (jnp.asarray(U0), jnp.asarray(V0)),
            data,
            max_epochs=self.get_max_iter(),
            workset=ws0,
            config=IterationConfig(mode="fused"),
        )
        trace = result.side.get("epoch_trace", {})
        self.last_workset_report = {
            "rounds": result.num_epochs,
            "max_epochs": self.get_max_iter(),
            "active_fraction": np.asarray(
                trace.get("active_fraction", ()), np.float64),
            "n_groups": len(user_ids) + len(item_ids),
        }
        with tracer.span("fit.fetch", "fit"):
            U, V = (np.asarray(jax.device_get(x)) for x in result.state)
        model = ALSModel()
        model.copy_params_from(self)
        model.set_model_data(Table({
            "userIds": user_ids[None], "itemIds": item_ids[None],
            "userFactors": U[None], "itemFactors": V[None]}))
        model.neq_plan, model.solve_plan = "scatter", "xla"
        return model

    def save(self, path: str) -> None:
        persist.save_metadata(self, path)

    @classmethod
    def load(cls, path: str) -> "ALS":
        return persist.load_stage_param(path)
