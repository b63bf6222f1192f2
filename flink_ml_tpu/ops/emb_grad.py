"""Statically-routed embedding-gradient scatter — the Wide&Deep backward
hot path.

Problem: the Wide&Deep backward must form the dense gradient of the
stacked ``(total_vocab, emb_dim)`` embedding table from per-slot gradient
rows: ``g_table[cat[b, f]] += g_rows[b, f]``, 26 slots a row of the
batch.  Autodiff lowers this to XLA's general scatter-add — one random
HBM read-modify-write per slot with conflict handling.

But bounded fits replay the SAME epoch tensor every epoch
(``models/common/sgd.py`` builds it once), so — exactly as with the LR
family's ELL kernels (``ops/ell_scatter.py``) — the slot routing is
**static**: we pay one host sort per fit and turn the per-step scatter
into four conflict-free streaming stages:

1. ``g_sorted = g_flat[order]`` — a static PERMUTATION gather
   (``unique_indices=True``: every source row read exactly once),
2. a segmented suffix-fold (Hillis–Steele) over runs of equal ids:
   after ``ceil(log2(max_run))`` masked shift-adds, the slot at each
   run's START holds the full run sum — ``fold_passes`` is static per
   fit (0 passes when every id in a step is unique),
3. placement of the run sums into the dense table, in one of two forms
   chosen at route-build time:

   - ``placement="gather"`` (default): ``dense = g_folded_ext[pos_map]``
     — a per-step static INVERSE map (``pos_map[v]`` = sorted position
     of vocab row ``v``'s run start, or ``S`` for untouched rows, which
     reads the appended zero row).  NO scatter exists anywhere in the
     step: the dense gradient is one streaming row-gather, which XLA
     lowers far better than any scatter and fuses into the Adam
     consumer.  Costs ``steps x num_rows`` i32 of route storage.
   - ``placement="scatter"``: compaction pick of run-start rows at
     static positions, then ``zeros.at[out_ids].set(run_sums,
     indices_are_sorted=True, unique_indices=True, mode="drop")`` —
     with unique ascending indices XLA needs no conflict handling and
     no read-modify-write; padded entries carry ascending OUT-OF-RANGE
     sentinels (``num_rows + rank``) so they stay unique and are
     dropped, never silently aliased.  Route storage stays
     ``O(slots)``, for vocabularies so large the inverse map would not
     fit.  The pick alone is :func:`routed_run_sums`: where the tables
     lie on one device, Wide&Deep's step hands those ``(U, E)`` run sums
     and ``out_ids`` to op ``routed_adam_update``
     (``ops/adam_table_pallas.py``), whose fused pass on a TPU places
     them inside the optimizer's own sweep of the table, so that no
     table-shaped gradient is zero-filled, scattered into and read back
     (PR 32: 39.3 -> 21.2 ms a step at 33.76 M x 16, 126 k touched rows).

The result equals the XLA scatter-add up to f32 summation order (runs
fold pairwise instead of sequentially).  The same route applies to any
per-slot payload width: the wide tower's ``(total_vocab,)`` scalar
table reuses it with ``E == 1``.

The reference has no analog — its one DNN-shaped config never existed
(`/root/reference/flink-ml-lib` ships KMeans only); this is the
TPU-native replacement for what its keyed-shuffle reduction
(``flink-ml-lib/.../clustering/kmeans/KMeans.java:172-196``) would have
had to become at embedding-gradient scale.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["EmbGradRoute", "emb_grad_route", "routed_run_sums",
           "routed_table_grad", "routed_table_grad_gather",
           "scatter_run_sums"]

#: placement="auto" picks gather until the inverse map would cost more
#: than this (steps x num_rows x 4 bytes of route storage), then falls
#: back to the O(slots) scatter placement.
_POS_MAP_BUDGET_BYTES = 512 << 20


@dataclass
class EmbGradRoute:
    """Static per-step routing for :func:`routed_table_grad` /
    :func:`routed_table_grad_gather`.

    All arrays are per-step stacks (leading dim = steps) so a
    ``lax.scan`` over steps slices them with one dynamic index.
    Exactly one of the placement array groups is populated — ``pos_map``
    for ``placement="gather"``, ``out_pos``/``out_ids`` for
    ``placement="scatter"``.
    """
    order: jnp.ndarray       # (steps, S) i32: sort permutation of the
                             #   flattened (batch*fields) slot ids
    sorted_ids: jnp.ndarray  # (steps, S) i32: ids in sorted order
    fold_passes: int         # static: ceil(log2(max run length)) over
                             #   every step (0 when all ids unique)
    num_rows: int            # destination table rows (total vocab)
    placement: str = "gather"
    # gather placement:
    pos_map: Optional[jnp.ndarray] = None  # (steps, num_rows) i32:
                             #   run-start position of each vocab row's
                             #   run, S for untouched rows (zero row)
    # scatter placement:
    out_pos: Optional[jnp.ndarray] = None  # (steps, U) i32: run-start
                             #   positions into the sorted axis; pad = S
                             #   (reads the appended zero row)
    out_ids: Optional[jnp.ndarray] = None  # (steps, U) i32: unique ids
                             #   per run, ascending; pad = num_rows +
                             #   rank (unique, out of range -> dropped)
    unique_per_step: Optional[np.ndarray] = None  # (steps,) host i64:
                             #   table rows each step touches (what the
                             #   fit's route span reports)

    @property
    def steps(self) -> int:
        return self.order.shape[0]

    def stacked_arrays(self):
        """The per-step array stack a scan body threads through (order
        matches :meth:`step_slice`)."""
        if self.placement == "gather":
            return (self.order, self.sorted_ids, self.pos_map)
        return (self.order, self.sorted_ids, self.out_pos, self.out_ids)

    def step_slice(self, i):
        """The per-step arrays for scan bodies at step ``i`` (dynamic
        index OK)."""
        return tuple(a[i] for a in self.stacked_arrays())

    def apply(self, g_flat, *step_arrays):
        """Dense table gradient from one step's slice (either
        placement) — the XLA lowering of registry op
        ``routed_table_grad``."""
        if self.placement == "gather":
            order, sid, pos_map = step_arrays
            return routed_table_grad_gather(
                g_flat, order, sid, pos_map,
                fold_passes=self.fold_passes)
        order, sid, out_pos, out_ids = step_arrays
        return routed_table_grad(
            g_flat, order, sid, out_pos, out_ids,
            num_rows=self.num_rows, fold_passes=self.fold_passes)

    def kernel_sig(self) -> tuple:
        """The ``(placement, fold_passes, slots_per_step)`` schema
        signature registry op ``routed_table_grad`` selects backends
        on."""
        return (self.placement, self.fold_passes, int(self.order.shape[1]))

    def resolve_apply(self, backend: Optional[str] = None):
        """Registry-resolved per-step apply: ``fn(g_flat, *step_arrays)``.

        The training step builders (``widedeep._make_train_ops``) call
        this ONCE at step-build time instead of hardcoding the XLA
        lowering — on TPU the fused Mosaic fold
        (``ops/emb_grad_pallas.py``) is picked up automatically, off TPU
        (or with ``backend="xla"`` forced) this is exactly
        :meth:`apply`.  ``fn.entry`` is the registry's answer, for a
        caller that has to name it (a step's program key)."""
        from ..kernels.registry import lookup

        entry = lookup("routed_table_grad", sig=self.kernel_sig(),
                       backend=backend)

        def apply_fn(g_flat, *step_arrays):
            return entry.fn(self, g_flat, *step_arrays)

        apply_fn.entry = entry
        return apply_fn


def emb_grad_route(cat_steps: np.ndarray, num_rows: int,
                   u_cap: Optional[int] = None,
                   device: bool = True,
                   placement: str = "gather") -> EmbGradRoute:
    """Build the static routing from a ``(steps, batch, fields)`` int
    epoch tensor of (already offset) categorical ids — host numpy, one
    time per fit.

    ``placement`` picks how run sums land in the dense table (see module
    doc): ``"gather"`` (default — scatter-free, ``steps x num_rows``
    route storage) or ``"scatter"`` (``O(slots)`` storage).  ``u_cap``
    (scatter placement) forces the unique-run capacity for streaming
    callers whose batches must share one compiled shape; a step with
    more unique ids raises rather than dropping gradient rows.
    ``device=False`` keeps the arrays host numpy for callers that manage
    their own placement.
    """
    if placement not in ("auto", "gather", "scatter"):
        raise ValueError(f"unknown placement {placement!r}")
    cat_steps = np.asarray(cat_steps)
    steps = cat_steps.shape[0]
    S = int(np.prod(cat_steps.shape[1:]))
    if placement == "auto":
        # gather's inverse map costs steps x num_rows i32 — the right
        # trade until it rivals the epoch data itself; past the budget
        # (large vocab x many steps) fall back to O(slots) scatter
        placement = ("gather"
                     if steps * num_rows * 4 <= _POS_MAP_BUDGET_BYTES
                     else "scatter")
    orders = np.empty((steps, S), np.int32)
    sids = np.empty((steps, S), np.int32)
    starts_list = []
    max_run = 1
    for s in range(steps):
        flat = cat_steps[s].reshape(-1)
        order = np.argsort(flat, kind="stable").astype(np.int32)
        sid = flat[order].astype(np.int32)
        orders[s] = order
        sids[s] = sid
        start = np.empty(S, bool)
        start[0] = True
        np.not_equal(sid[1:], sid[:-1], out=start[1:])
        pos = np.flatnonzero(start).astype(np.int32)
        starts_list.append((pos, sid[pos]))
        runs = np.diff(np.append(pos, S))
        max_run = max(max_run, int(runs.max(initial=1)))
    fold_passes = (max(0, int(np.ceil(np.log2(max_run))))
                   if max_run > 1 else 0)
    wrap = jnp.asarray if device else np.asarray
    # the u_cap contract holds for BOTH placements (a caller-forced cap
    # must never be silently ignored); gather just has no U-shaped
    # arrays to size with it
    unique_per_step = np.asarray([p.size for p, _ in starts_list], np.int64)
    need_u = int(unique_per_step.max())
    if u_cap is not None and need_u > u_cap:
        raise ValueError(
            f"route needs {need_u} unique ids in some step > forced "
            f"u_cap {u_cap}; gradient rows would silently drop — raise "
            "the cap")
    if placement == "gather":
        pos_map = np.full((steps, num_rows), S, np.int32)
        for s, (pos, uids) in enumerate(starts_list):
            pos_map[s][uids] = pos
        return EmbGradRoute(
            order=wrap(orders), sorted_ids=wrap(sids),
            pos_map=wrap(pos_map), fold_passes=fold_passes,
            num_rows=num_rows, placement="gather",
            unique_per_step=unique_per_step)
    U = u_cap if u_cap is not None else need_u
    out_pos = np.full((steps, U), S, np.int32)
    # pad ids: ascending out-of-range sentinels — unique (the scatter's
    # unique_indices claim stays true) and dropped by mode="drop"
    out_ids = (num_rows
               + np.arange(U, dtype=np.int32)[None, :].repeat(steps, 0))
    for s, (pos, uids) in enumerate(starts_list):
        out_pos[s, :pos.size] = pos
        out_ids[s, :uids.size] = uids
    return EmbGradRoute(
        order=wrap(orders), sorted_ids=wrap(sids),
        out_pos=wrap(out_pos), out_ids=wrap(out_ids),
        fold_passes=fold_passes, num_rows=num_rows, placement="scatter",
        unique_per_step=unique_per_step)


def _folded_ext(g_flat, order, sorted_ids, fold_passes):
    """Stages 1-2 shared by both placements: static permutation gather,
    then the segmented suffix-fold — after pass k (offset 2^k), g[i]
    holds the sum of the sorted rows i .. min(run_end, i + 2^(k+1) - 1).
    Returns ``(g_ext, squeeze)`` where ``g_ext (S+1, E)`` carries an
    appended zero row (position ``S`` — what padded picks read)."""
    squeeze = g_flat.ndim == 1
    if squeeze:
        g_flat = g_flat[:, None]
    S, E = g_flat.shape
    g = jnp.take(g_flat, order, axis=0, unique_indices=True)
    offs = 1
    for _ in range(fold_passes):
        same = jnp.concatenate(
            [sorted_ids[offs:] == sorted_ids[:-offs],
             jnp.zeros((offs,), bool)])
        shifted = jnp.concatenate(
            [g[offs:], jnp.zeros((offs, E), g.dtype)], axis=0)
        g = g + jnp.where(same[:, None], shifted, 0.0)
        offs *= 2
    return jnp.concatenate([g, jnp.zeros((1, E), g.dtype)], axis=0), \
        squeeze


def routed_run_sums(g_flat: jnp.ndarray, order: jnp.ndarray,
                    sorted_ids: jnp.ndarray, out_pos: jnp.ndarray, *,
                    fold_passes: int) -> jnp.ndarray:
    """Stages 1-2 and the pick of the SCATTER placement: the folded
    gradient of the rows a step touches, ``(U, E)`` (``(U,)`` for a
    scalar payload), row ``u`` belonging to table row ``out_ids[u]``;
    padded picks read the appended zero row.  What :func:`routed_table_grad`
    scatters into a table-shaped array, and what
    ``ops/adam_table_pallas.py`` places inside the optimizer's pass."""
    g_ext, squeeze = _folded_ext(g_flat, order, sorted_ids, fold_passes)
    run_sums = jnp.take(g_ext, out_pos, axis=0, unique_indices=True)
    return run_sums[:, 0] if squeeze else run_sums


def scatter_run_sums(run_sums: jnp.ndarray, out_ids: jnp.ndarray,
                     num_rows: int) -> jnp.ndarray:
    """Stage 3 of the SCATTER placement: ``(U, E)`` or ``(U,)`` run sums
    set into a fresh ``(num_rows, E)`` or ``(num_rows,)`` array of zeros
    at the ascending unique ``out_ids``; padded ids (``>= num_rows``) are
    dropped."""
    return jnp.zeros((num_rows,) + run_sums.shape[1:], run_sums.dtype).at[
        out_ids].set(run_sums, indices_are_sorted=True,
                     unique_indices=True, mode="drop")


def routed_table_grad(g_flat: jnp.ndarray, order: jnp.ndarray,
                      sorted_ids: jnp.ndarray, out_pos: jnp.ndarray,
                      out_ids: jnp.ndarray, *, num_rows: int,
                      fold_passes: int) -> jnp.ndarray:
    """The dense ``(num_rows, E)`` table gradient from per-slot rows
    ``g_flat (S, E)`` via one step's route slice, SCATTER placement (see
    module doc).  Equals ``zeros.at[ids].add(g_flat)`` up to f32
    summation order.  ``num_rows``/``fold_passes`` are static."""
    return scatter_run_sums(
        routed_run_sums(g_flat, order, sorted_ids, out_pos,
                        fold_passes=fold_passes), out_ids, num_rows)


def routed_table_grad_gather(g_flat: jnp.ndarray, order: jnp.ndarray,
                             sorted_ids: jnp.ndarray,
                             pos_map: jnp.ndarray, *,
                             fold_passes: int) -> jnp.ndarray:
    """GATHER placement: the dense gradient is one streaming row-gather
    of the folded array at the static inverse map — no scatter exists
    anywhere (see module doc).  ``pos_map (num_rows,)`` holds each vocab
    row's run-start position in sorted order (``S`` = untouched -> the
    appended zero row).  Same result as :func:`routed_table_grad`."""
    g_ext, squeeze = _folded_ext(g_flat, order, sorted_ids, fold_passes)
    out = jnp.take(g_ext, pos_map, axis=0)
    return out[:, 0] if squeeze else out


# ---------------------------------------------------------------------------
# kernel-registry entry (XLA backend of op ``routed_table_grad``; the
# fused Mosaic fold registers the "pallas" backend from
# ``ops/emb_grad_pallas.py``).  The registry signature is
# ``fn(route, g_flat, *step_arrays)`` so one entry serves every payload
# width — the (S, E) embedding rows and the (S,) wide-scalar table alike.
# ---------------------------------------------------------------------------

def routed_apply_xla(route: EmbGradRoute, g_flat, *step_arrays):
    """XLA backend of op ``routed_table_grad``."""
    return EmbGradRoute.apply(route, g_flat, *step_arrays)


def _register_emb_grad_kernels() -> None:
    from ..kernels.registry import register_kernel

    register_kernel("routed_table_grad", "xla", routed_apply_xla)


_register_emb_grad_kernels()
