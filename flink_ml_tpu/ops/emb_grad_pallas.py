"""Fused Mosaic fold for the routed embedding gradient — the Wide&Deep
backward hot path, stage 2 of ``ops/emb_grad.py`` in ONE VMEM pass.

The r5 round put the routed embedding-gradient step at the top of the
Wide&Deep profile: the dense towers ride the MXU while the table
gradient is bounded by HBM streaming.  The XLA routed path is already
scatter-free, but its segmented suffix-fold materialises the full
``(S, E)`` sorted-gradient array in HBM once per fold pass —
``fold_passes`` is ``ceil(log2(max_run))``, and one heavy-hitter id
appearing in most of an 8192-row batch drives it to ~13, i.e. ~13
read+write round trips of the ``(S, E)`` f32 slot array for what is
arithmetically a handful of masked adds per element.

This kernel runs ALL fold passes on a VMEM tile: HBM traffic drops to
one read + one write of ``(S, E)`` regardless of ``fold_passes``.
Correctness
across tile boundaries uses a halo: the fold only propagates values
from HIGHER to LOWER sorted positions over distances < ``2^fold_passes``,
so with ``block_n >= 2^fold_passes`` a tile's fully-folded rows depend
on at most the next tile — each grid step loads its own block plus the
following one (the input is padded by one zero block with sentinel id
-1, which can never extend a run: real ids are >= 0).

The fold expression is element-identical to ``emb_grad._folded_ext``
(same masked shift-add tree), so the fused path is BIT-exact with the
XLA routed gradient — asserted in interpret mode by the
``tests/test_kernels.py`` parity matrix.  The surrounding stages stay
XLA: the permutation gather and the ``pos_map`` placement gather are
single streaming passes XLA already lowers well.

Registered as the ``pallas`` backend of registry op
``routed_table_grad`` (gather placement, ``fold_passes >= 1``) but
PARKED — forced lookup only: the kernel lowers for TPU and Mosaic then
refuses it (see :func:`_register`), so on a chip
``EmbGradRoute.resolve_apply`` plans the XLA fold.
"""

from __future__ import annotations

import functools

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.padding import require_block_rows

__all__ = ["fold_block_n", "fold_runs_fused",
           "routed_table_grad_gather_fused", "routed_apply_fused"]

#: fold tiles: smallest block worth a grid step; the VMEM footprint is
#: 2 blocks of (block_n, E) f32 + 2 id blocks — tiny for any E <= 128.
_MIN_BLOCK = 256
_MAX_BLOCK = 8192


def fold_block_n(S: int, fold_passes: int) -> Optional[int]:
    """Smallest viable power-of-two block for a sorted axis of ``S``
    slots: ``>= 2^fold_passes`` (the halo argument above), ``>= 256``,
    dividing ``S``.  None when no block ``<= 8192`` works — the caller
    falls back to the XLA fold."""
    bn = max(_MIN_BLOCK, 1 << max(fold_passes, 0))
    while bn <= _MAX_BLOCK:
        if S % bn == 0:
            return bn
        bn <<= 1
    return None


def _fold_kernel(fold_passes: int, block_n: int):
    def kern(g_ref, g_next_ref, id_ref, id_next_ref, out_ref):
        g = jnp.concatenate([g_ref[:], g_next_ref[:]], axis=0)  # (2bn, E)
        ids = jnp.concatenate([id_ref[:], id_next_ref[:]])      # (2bn,)
        offs = 1
        for _ in range(fold_passes):
            # element-identical to emb_grad._folded_ext's pass: add the
            # row offs below iff it continues this row's run
            same = jnp.concatenate(
                [ids[offs:] == ids[:-offs],
                 jnp.zeros((offs,), bool)])
            shifted = jnp.concatenate(
                [g[offs:], jnp.zeros((offs, g.shape[1]), g.dtype)], axis=0)
            g = g + jnp.where(same[:, None], shifted, 0.0)
            offs *= 2
        # rows [0, bn) saw every in-run contribution within 2^fold_passes
        # - 1 <= 2bn - bn rows of lookahead — exact; the halo rows are
        # the next grid step's problem
        out_ref[:] = g[:block_n]

    return kern


@functools.partial(jax.jit,
                   static_argnames=("fold_passes", "block_n", "interpret"))
def fold_runs_fused(g_sorted: jnp.ndarray, sorted_ids: jnp.ndarray, *,
                    fold_passes: int, block_n: int,
                    interpret: bool = False) -> jnp.ndarray:
    """All ``fold_passes`` segmented-fold passes of ``(S, E)`` sorted
    gradient rows in one Mosaic pass (run starts end up holding full run
    sums, exactly as ``emb_grad._folded_ext`` computes them — minus its
    appended zero row, which the caller re-appends)."""
    squeeze = g_sorted.ndim == 1
    if squeeze:
        g_sorted = g_sorted[:, None]
    S, E = g_sorted.shape
    require_block_rows(S, block_n, op="fold_runs_fused")
    if (1 << fold_passes) > block_n:
        raise ValueError(
            f"fold_runs_fused: 2^fold_passes={1 << fold_passes} exceeds "
            f"block_n={block_n} — a run could span more than the one-block "
            "halo; use fold_block_n to size the block")
    # one zero pad block with sentinel id -1: real ids are >= 0, so no
    # run extends into the pad and the last tile's halo reads are inert
    g_pad = jnp.concatenate(
        [g_sorted, jnp.zeros((block_n, E), g_sorted.dtype)], axis=0)
    id_pad = jnp.concatenate(
        [sorted_ids.astype(jnp.int32),
         jnp.full((block_n,), -1, jnp.int32)])

    out = pl.pallas_call(
        _fold_kernel(fold_passes, block_n),
        grid=(S // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, E), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_n, E), lambda i: (i + 1, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_n,), lambda i: (i,),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_n,), lambda i: (i + 1,),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((block_n, E), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((S, E), g_sorted.dtype),
        interpret=interpret,
    )(g_pad, g_pad, id_pad, id_pad)
    return out[:, 0] if squeeze else out


def routed_table_grad_gather_fused(g_flat: jnp.ndarray, order: jnp.ndarray,
                                   sorted_ids: jnp.ndarray,
                                   pos_map: jnp.ndarray, *,
                                   fold_passes: int, block_n: int,
                                   interpret: bool = False) -> jnp.ndarray:
    """Gather-placement routed table gradient with the fused fold:
    XLA permutation gather -> one Mosaic fold pass -> XLA placement
    gather.  Bit-exact with ``emb_grad.routed_table_grad_gather``."""
    squeeze = g_flat.ndim == 1
    g2 = g_flat[:, None] if squeeze else g_flat
    g = jnp.take(g2, order, axis=0, unique_indices=True)
    if fold_passes:
        g = fold_runs_fused(g, sorted_ids, fold_passes=fold_passes,
                            block_n=block_n, interpret=interpret)
    g_ext = jnp.concatenate(
        [g, jnp.zeros((1, g.shape[1]), g.dtype)], axis=0)
    out = jnp.take(g_ext, pos_map, axis=0)
    return out[:, 0] if squeeze else out


def routed_apply_fused(route, g_flat, *step_arrays, interpret: bool = False):
    """``pallas`` backend of registry op ``routed_table_grad`` (gather
    placement only — the supports predicate gates)."""
    order, sid, pos_map = step_arrays
    bn = fold_block_n(int(order.shape[0]), route.fold_passes)
    return routed_table_grad_gather_fused(
        g_flat, order, sid, pos_map, fold_passes=route.fold_passes,
        block_n=bn, interpret=interpret)


def _fused_route_supported(sig: tuple) -> bool:
    """sig = (placement, fold_passes, slots_per_step) from
    ``EmbGradRoute.kernel_sig``.  fold_passes == 0 has nothing to fuse
    (the XLA path is already gather -> gather)."""
    if len(sig) != 3:
        return False
    placement, fold_passes, slots = sig
    return (placement == "gather" and fold_passes >= 1
            and fold_block_n(int(slots), int(fold_passes)) is not None)


def _register() -> None:
    from ..kernels.registry import register_kernel

    # Parked (PR 21, TPU v5 lite, libtpu 0.0.34).  Every fold_passes 1-4 x
    # E in (8, 64) case fails the same way on the chip:
    #   Mosaic failed to compile TPU kernel: Not implemented: Input
    #   offsets outside of the first tile
    #   %17 = "tpu.concatenate"(%15, %16) <{dimension = 0 : i32}> :
    #   (vector<256xi32>, vector<256xi32>) -> vector<512xi32>
    # — the 1-D id concatenation at the top of the kernel body; the 1-D
    # bool concatenations and the 1/2/4-sublane shifts after it have not
    # been reached.  Carrying the run flags some other way is a rewrite,
    # not a layout change (ROADMAP S2/S5b decide: rewrite or delete).
    register_kernel(
        "routed_table_grad", "pallas", routed_apply_fused, priority=20,
        supports=_fused_route_supported,
        forced_only='Mosaic: "Not implemented: Input offsets outside of '
                    'the first tile" (tpu.concatenate of two 1-D i32 '
                    'vectors)')


_register()
