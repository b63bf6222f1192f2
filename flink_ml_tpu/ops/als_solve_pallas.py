"""Batched Cholesky solve with the groups on the lanes: registry op
``als_cholesky_solve``.

ALS's grouped half-epoch (``models/recommendation/als.py``) ends a block
in ``groups`` symmetric positive definite systems ``A_g x_g = b_g`` of
``rank`` unknowns.  Both backends take them lane-major, ``At`` ``(rank,
rank, groups)`` with ``At[k, i] = A[i, k]`` (column ``k``) and ``bt``
``(rank, groups)``, and return ``x`` ``(rank, groups)``: ``(rank, rank,
groups)`` pads nothing on the chip where ``(groups, rank, rank)`` pads
every matrix to ``(8k, 128)``, and a column step is elementwise work
over ``(rank, groups)`` slabs that vectorises over the groups.  An exact
Cholesky factorisation and both triangular solves in float32; a matrix
that is not positive definite gives NaN in its own lane, which the
caller catches.

``backend="xla"`` (:func:`cholesky_solve_lanes`) is a left-looking
factorisation as a ``lax.fori_loop`` of ``rank`` column steps over the
whole block's factor ``L`` ``(rank, rank, groups)``, which lives in HBM:
every column step reads all of it.  It is the path off the TPU, for a
block of fewer groups than a lane tile (the split groups' one system a
step) and the parity oracle.

``backend="pallas"`` (:func:`cholesky_solve_vmem`) is the same
recurrence a tile of 128 groups at a time, the tile's matrices in VMEM
from the first column to the last substitution: a grid step takes
``At[:, :, tile]`` (5.3 MB at rank 100, double-buffered by the pipeline),
factors it into a VMEM scratch of the same shape, substitutes forwards
as the columns are made and backwards against the finished factor, and
writes the tile's ``x``.  ``L`` never exists in HBM.

- Columns come in panels of 8 (one sublane tile).  Column ``j`` of panel
  ``p`` needs the rows ``i >= 8p`` only, so its accumulator, the slabs of
  the made columns it reads and the rows it writes are the STATIC slice
  ``[8p:]``: the slabs above the diagonal cost nothing (28.7 k vreg
  multiply-subtracts a tile at rank 100 where whole slabs are 130 k).
  The panels are unrolled (13 at rank 100); the columns of a panel and
  the made columns they read are ``fori_loop`` s.
- A panel's own columns are zeroed before its first is made, so a column
  reads all 8 of them whether made or not: no loop of a dynamic length,
  and nothing stale from the tile before (a NaN there would survive a
  multiplication by 0).
- The made columns are read 8 a loop iteration as ONE ``(8, rows, tile)``
  array times their ``(8, 1, tile)`` entries in row ``j`` (a dynamic
  single-sublane read), summed over the 8 before they leave the
  accumulator: the chain through the accumulator is one subtraction an
  iteration, the sum stays as close to a float64 solve as the XLA loop's
  ``jnp.sum`` does (4.4e-6 against 4.9e-6; one subtraction a column reads
  9e-6), and the kernel's body is a third of what eight written-out
  products make it: it is traced and lowered in the first fit of a
  process, twice (a side's blocks each), and on the chip's host that
  fit's program took 7.35 s to lower with the products written out and
  takes 2.45 s so (0.98 s without the kernel; my chip runs, PR 34).
- A column's pivot lies in the panel's first 8 rows: only those are
  searched for it.
- The lanes of a last, partial tile hold whatever the pipeline left
  there; no lane reads another, and their ``x`` is never written back.

On the chip (my chip runs, PR 34, kernel alone, rank 100): a block of
30,020 groups in 7.4 ms where the XLA loop takes 191, one of 5,891 in 2.0
against 39 (rank 32, 32,768 groups: 1.7 against 9.4); the DMA of ``At``
alone is 2.4 of the 7.4, the backward substitution 0.7.  The
multiply-subtracts run at 0.6 cycles a vreg, so what is left is their
count.  Tried there and not kept: tiles of 256 and 512 groups (7.1 and
7.8 ms where 128 took 8.1), panels of 16 and 32 columns (8.7, 10.4), 4
made columns an iteration (8.9), a reciprocal of the pivot in place of
the division (7.9), and the backward substitution by strided reads of
``L``'s rows in place of the sublane reduction (no difference).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["cholesky_solve_lanes", "cholesky_solve_vmem"]

_SUBLANES = 8
#: groups a grid step holds on its lanes: one lane tile.  At rank 100 a
#: block of 30,020 groups took 8.07 / 7.15 / 7.79 ms at 128 / 256 / 512 (my
#: chip run, PR 34): 256 buys a tenth of the kernel, 0.4% of an ALS
#: iteration, for twice the VMEM
_TILE = 128
#: what one grid step's buffers may take of the v5e's 128 MiB of VMEM:
#: the tile's ``At`` twice (the pipeline's two buffers) and the factor
_VMEM_BUDGET = 100 << 20


def cholesky_solve_lanes(At, bt):
    """``xla`` backend of op ``als_cholesky_solve`` (module doc)."""
    rank = At.shape[0]
    index = jnp.arange(rank)[:, None]

    def factor(j, L):
        # left-looking: column j of A less the columns already made, each
        # scaled by its entry in row j (columns not made yet are 0).  One
        # pass over the whole factor a column: static bounds on the
        # columns and rows a panel needs make XLA copy the slices (1.12 s
        # an epoch against 0.90 at rank 100, PERF.md section 6)
        row = jax.lax.dynamic_index_in_dim(L, j, 1, keepdims=False)
        col = (jax.lax.dynamic_index_in_dim(At, j, 0, keepdims=False)
               - jnp.sum(L * row[:, None, :], axis=0))
        pivot = jnp.sqrt(jax.lax.dynamic_index_in_dim(col, j, 0))
        col = jnp.where(index >= j, col / pivot, 0.0)
        return jax.lax.dynamic_update_index_in_dim(L, col, j, 0)

    L = jax.lax.fori_loop(0, rank, factor, jnp.zeros_like(At))

    def forward(j, y):                       # L y = b, column by column
        col = jax.lax.dynamic_index_in_dim(L, j, 0, keepdims=False)
        yj = (jax.lax.dynamic_index_in_dim(y, j, 0)
              / jax.lax.dynamic_index_in_dim(col, j, 0))
        return jnp.where(index > j, y - col * yj,
                         jnp.where(index == j, yj, y))

    def backward(t, x):                      # L^T x = y, from the last row
        j = rank - 1 - t
        col = jax.lax.dynamic_index_in_dim(L, j, 0, keepdims=False)
        below = jnp.sum(jnp.where(index > j, col * x, 0.0), axis=0,
                        keepdims=True)
        xj = ((jax.lax.dynamic_index_in_dim(x, j, 0) - below)
              / jax.lax.dynamic_index_in_dim(col, j, 0))
        return jax.lax.dynamic_update_index_in_dim(x, xj, j, 0)

    y = jax.lax.fori_loop(0, rank, forward, bt)
    return jax.lax.fori_loop(0, rank, backward, y)


def _tile_bytes(rank: int) -> int:
    """VMEM of one grid step: ``At``'s tile twice and the factor, each
    ``rank`` slabs of ``rank`` rows on whole sublane tiles, and the two
    buffers each of ``bt`` and ``x``: 16 MB at rank 100."""
    slab = -(-rank // _SUBLANES) * _SUBLANES * _TILE * 4
    return 3 * rank * slab + 4 * slab


def _kernel(rank: int):
    panels, tile = -(-rank // _SUBLANES), _TILE

    def kern(a_ref, b_ref, x_ref, l_ref):
        x_ref[...] = b_ref[...]              # y, then x, in place
        for p in range(panels):
            lo = p * _SUBLANES
            rows, cols = rank - lo, min(_SUBLANES, rank - lo)
            index = lo + jax.lax.broadcasted_iota(jnp.int32, (rows, tile), 0)
            head = index[:cols]              # the rows of the panel's pivots
            l_ref[lo:lo + cols, lo:, :] = jnp.zeros((cols, rows, tile),
                                                    jnp.float32)

            def made(acc, j, first, count):
                """``acc`` less ``count`` made columns from ``first`` on,
                each scaled by its entry in row ``j``."""
                made_cols = pl.ds(first, count)
                return acc - jnp.sum(
                    l_ref[made_cols, lo:, :]
                    * l_ref[made_cols, pl.ds(j, 1), :], axis=0)

            def column(jj, carry):
                j = lo + jj
                acc = jax.lax.fori_loop(
                    0, p, lambda q, acc: made(
                        acc, j, pl.multiple_of(q * _SUBLANES, _SUBLANES),
                        _SUBLANES), a_ref[j, lo:, :])
                acc = made(acc, j, lo, cols)

                def entry(slab):             # row j of ``slab``
                    return jnp.sum(jnp.where(head == j, slab[:cols], 0.0),
                                   axis=0, keepdims=True)

                col = jnp.where(index >= j, acc / jnp.sqrt(entry(acc)), 0.0)
                l_ref[j, lo:, :] = col
                # L y = b, the step of this column
                y = x_ref[lo:, :]
                yj = x_ref[pl.ds(j, 1), :] / entry(col)
                x_ref[lo:, :] = jnp.where(index > j, y - col * yj,
                                          jnp.where(index == j, yj, y))
                return carry

            jax.lax.fori_loop(0, cols, column, 0)

        for p in reversed(range(panels)):    # L^T x = y, from the last row
            lo = p * _SUBLANES
            rows, cols = rank - lo, min(_SUBLANES, rank - lo)
            index = lo + jax.lax.broadcasted_iota(jnp.int32, (rows, tile), 0)

            def row(t, carry):
                j = lo + cols - 1 - t
                col = l_ref[j, lo:, :]
                below = jnp.sum(
                    jnp.where(index > j, col * x_ref[lo:, :], 0.0), axis=0,
                    keepdims=True)
                x_ref[pl.ds(j, 1), :] = (
                    (x_ref[pl.ds(j, 1), :] - below)
                    / l_ref[j, pl.ds(j, 1), :])
                return carry

            jax.lax.fori_loop(0, cols, row, 0)

    return kern


@functools.partial(jax.jit, static_argnames=("interpret",))
def cholesky_solve_vmem(At, bt, *, interpret=False):
    """``pallas`` backend of op ``als_cholesky_solve`` (module doc)."""
    rank, _, groups = At.shape
    vector = pl.BlockSpec((rank, _TILE), lambda i: (0, i),
                          memory_space=pltpu.VMEM)
    return pl.pallas_call(
        _kernel(rank),
        grid=(pl.cdiv(groups, _TILE),),
        in_specs=[pl.BlockSpec((rank, rank, _TILE), lambda i: (0, 0, i),
                               memory_space=pltpu.VMEM), vector],
        out_specs=vector,
        scratch_shapes=[pltpu.VMEM((rank, rank, _TILE), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((rank, groups), jnp.float32),
        # over the 16 MiB of scoped VMEM a kernel gets by default: the
        # v5e has 128 MiB, and a smaller tile would be no whole lane tile
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_tile_bytes(rank) + (8 << 20)),
        interpret=interpret,
    )(At, bt)


def _vmem_supported(sig: tuple) -> bool:
    """``sig = (rank, groups)``: a lane tile of groups at least (the
    split groups' one system a step stays with XLA), and a rank whose
    tile fits :data:`_VMEM_BUDGET`: up to 256."""
    if len(sig) != 2:
        return False
    rank, groups = sig
    return (groups >= _TILE and rank >= 1
            and _tile_bytes(rank) <= _VMEM_BUDGET)


def _register() -> None:
    from ..kernels.registry import register_kernel, tpu_only

    register_kernel("als_cholesky_solve", "xla", cholesky_solve_lanes)
    register_kernel("als_cholesky_solve", "pallas", cholesky_solve_vmem,
                    priority=20, supports=_vmem_supported,
                    available=tpu_only)


_register()
