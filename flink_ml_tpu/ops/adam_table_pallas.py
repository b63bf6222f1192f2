"""Dense Adam over an embedding table that places the step's gradient
itself: registry op ``routed_adam_update``.

Wide&Deep's routed step (``ops/emb_grad.py``, ``scatter`` placement)
ends its fold with the *run sums*: the gradient of the rows a step
touches, one row a unique id, ascending (at Criteo's cardinalities about
126 k rows of 33.76 M).  Dense Adam still updates EVERY row (a row with
history keeps its momentum tail), so the whole of ``p``, ``m`` and ``v``
streams through the chip either way.  What need not exist is a
table-shaped gradient: a fresh ``(num_rows, E)`` array that is
zero-filled, scattered into and read back as a seventh stream, every
step.

``backend="xla"`` is that composition, and stays the path off the TPU and
the parity oracle: ``emb_grad.scatter_run_sums`` (zeros, the sorted unique
scatter-set), then the expressions of ``optax.scale_by_adam`` and
``scale(-lr)``.

``backend="pallas"`` (:func:`routed_adam_update_fused`) makes one pass:

- The tables are read the way the chip holds them.  A narrow ``(N, E)``
  float32 array lies column-major there (``f32[N,16]{0,1:T(8,128)}``), so
  ``p.T`` is that array under another name (a bitcast, as PR 30 found
  for KMeans' points): blocks ``(E, block_n)``, the features on
  sublanes, the table's rows on lanes, the last block ragged.  ``p``,
  ``m`` and ``v`` are aliased in -> out: no second copy of a table.
- ``out_ids`` is sorted, so the rows a block of the table is touched in
  are one contiguous segment of it: ``segments`` (one ``searchsorted`` on
  the device, scalar-prefetched) gives every block its bounds.  The ids
  and the run sums (feature-major too, ``(E, U)``) reach the kernel in
  windows of ``block_n`` entries or more, the segment's own and the one
  after it (a segment is no longer than a block and so ends in the
  second at the latest); the window's block index comes from
  ``segments``, so the pipeline fetches a window only when the segment
  moves on to it.
- Per block the kernel zeroes a ``(E, block_n)`` gradient scratch in
  VMEM, puts each segment row's run sum into its lane (its aligned
  128-lane tile of run sums rotated so that the row's column lies on the
  destination lane, then merged into the 128-lane tile of the gradient
  that the loop carries and stores), and applies Adam to the whole
  block.  A block at the head of a field can be touched in every row, one
  in a field's tail in none.
- The padded ids (``num_rows + rank``, ``ops/emb_grad.py``) sort behind
  the last block's segment and are never visited.

The arithmetic is float32 in ``optax.adam``'s own expressions on every
row; the bias corrections are computed outside from the shared step
count and handed in.  A row with ``m = v = g = 0`` comes out as it went
in, bit for bit: ``p + (-lr) * ((0 / c1) / (sqrt(0 / c2) + eps))``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .emb_grad import scatter_run_sums

__all__ = ["routed_adam_update_fused", "routed_adam_update_xla",
           "pick_block_n"]

_LANES = 128
#: rows of a segment placed per iteration of the kernel's loop
_UNROLL = 8
#: what the kernel's buffers may take of the 16 MiB of scoped VMEM
_VMEM_BUDGET = 12 << 20


def _window(block_n: int) -> int:
    """Entries of a window of ids and run sums: a whole segment (at most
    ``block_n`` ids), in multiples of the 1024 entries XLA tiles a
    one-dimensional array by (Mosaic refuses a block of another size)."""
    return -(-block_n // 1024) * 1024


def pick_block_n(num_rows: int, e: int) -> int:
    """Rows of the table a block holds on its lanes: the largest power of
    two up to 8192 whose buffers fit :data:`_VMEM_BUDGET` (three tables in
    and out, two buffers each, the gradient scratch, and four windows of
    run sums, on ``ceil(e / 8) * 8`` sublanes: 8.5 MiB at ``e`` 16, block
    8192, whose four id windows are 128 KiB of SMEM), or for a small table
    the whole of it rounded up to a lane tile.  The block hardly matters
    on the chip: at 33.76 M x 16 a step took 28.9 / 28.3 / 28.1 ms at
    blocks 4096 / 8192 / 16384 (PR 32, the first placement loop; 16384
    needs a raised VMEM limit)."""
    sublanes = -(-max(e, 1) // 8) * 8
    block_n = 8192
    while (block_n > _LANES and 4 * sublanes * (13 * block_n
                                                + 4 * _window(block_n))
           > _VMEM_BUDGET):
        block_n //= 2
    return min(block_n, -(-num_rows // _LANES) * _LANES)


def _adam(p, m, v, g, c1, c2, lr, b1, b2, eps):
    """``optax.scale_by_adam`` + ``scale(-lr)`` + ``apply_updates`` on one
    array, in optax's own order of operations (``c1``, ``c2``: the bias
    corrections ``1 - b ** count``)."""
    m = (1 - b1) * g + b1 * m
    v = (1 - b2) * (g * g) + b2 * v
    step = (m / c1) / (jnp.sqrt(v / c2) + eps)
    return p + (-lr) * step, m, v


def _bias_corrections(count, b1, b2):
    """optax's ``1 - decay ** count`` for both moments, as ``(2,)``."""
    return jnp.stack([1 - b1 ** count, 1 - b2 ** count]).astype(jnp.float32)


def routed_adam_update_xla(p, m, v, run_sums, out_ids, count, *, lr, b1, b2,
                           eps):
    """``xla`` backend of op ``routed_adam_update``: the table-shaped
    gradient, then Adam on it."""
    c = _bias_corrections(count, b1, b2)
    return _adam(p, m, v, scatter_run_sums(run_sums, out_ids, p.shape[0]),
                 c[0], c[1], lr, b1, b2, eps)


def _kernel(block_n: int, window: int, lr: float, b1: float, b2: float,
            eps: float):
    def kern(seg_ref, c_ref, ids0_ref, ids1_ref, rs0_ref, rs1_ref,
             p_ref, m_ref, v_ref, p_out, m_out, v_out, g_ref):
        i = pl.program_id(0)
        lo, hi = seg_ref[i], seg_ref[i + 1]
        first = (lo // window) * window       # where the first window starts
        split = jnp.minimum(hi, first + window)
        base = i * block_n
        g_ref[...] = jnp.zeros_like(g_ref)
        tile = (g_ref.shape[0], _LANES)
        lane = jax.lax.broadcasted_iota(jnp.int32, tile, 1)

        def place(ids_ref, rs_ref, start, lo, hi, carry):
            """Rows ``lo .. hi`` of the segment, which lie in the window
            that starts at ``start``.  The ids ascend, so the rows of one
            128-lane tile of the gradient follow each other: the tile is
            gathered in ``acc`` and stored after every row (the last
            store of a tile is the whole of it).  Nothing is read back
            inside the loop, so its iterations depend on each other
            through ``acc`` alone and ``_UNROLL`` of them overlap; a row
            past ``hi`` changes nothing and stores the tile again."""
            def body(t, carry):
                acc, cur = carry
                for k in range(_UNROLL):
                    j = lo + t * _UNROLL + k
                    live = j < hi
                    at = jnp.minimum(j - start, window - 1)
                    q = ids_ref[at] - base    # the row's lane in the block
                    src = pl.multiple_of((at >> 7) << 7, _LANES)
                    dst = jnp.where(live, (q >> 7) << 7, cur)
                    # the row's column of its tile of run sums, rotated
                    # onto the lane it goes to
                    sums = pltpu.roll(rs_ref[:, pl.ds(src, _LANES)],
                                      (q - at) & (_LANES - 1), 1)
                    acc = jnp.where(dst == cur, acc,
                                    jnp.zeros(tile, acc.dtype))
                    acc = jnp.where(
                        lane == jnp.where(live, q & (_LANES - 1), -1),
                        sums, acc)
                    g_ref[:, pl.ds(pl.multiple_of(dst, _LANES), _LANES)] = acc
                    cur = dst
                return acc, cur
            return jax.lax.fori_loop(0, pl.cdiv(hi - lo, _UNROLL), body, carry)

        carry = (jnp.zeros(tile, jnp.float32), jnp.int32(0))
        carry = place(ids0_ref, rs0_ref, first, lo, split, carry)
        place(ids1_ref, rs1_ref, first + window, split, hi, carry)
        p_out[...], m_out[...], v_out[...] = _adam(
            p_ref[...], m_ref[...], v_ref[...], g_ref[...], c_ref[0],
            c_ref[1], lr, b1, b2, eps)

    return kern


@functools.partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps",
                                             "block_n", "interpret"))
def routed_adam_update_fused(p, m, v, run_sums, out_ids, count, *, lr, b1,
                             b2, eps, block_n=None, interpret=False):
    """``pallas`` backend of op ``routed_adam_update`` (module doc).
    ``block_n``: a multiple of 128 (default :func:`pick_block_n`)."""
    squeeze = p.ndim == 1
    if squeeze:
        p, m, v = p[:, None], m[:, None], v[:, None]
        run_sums = run_sums[:, None]
    n, e = p.shape
    u = out_ids.shape[0]
    block_n = block_n or pick_block_n(n, e)
    blocks = pl.cdiv(n, block_n)
    window = _window(block_n)
    # the window after the last segment's own has to exist
    u_pad = (pl.cdiv(u, window) + 1) * window
    ids = jnp.concatenate([out_ids.astype(jnp.int32),
                           jnp.full((u_pad - u,), n, jnp.int32)])
    sums_t = jnp.pad(run_sums.T, ((0, 0), (0, u_pad - u)))
    # a block's segment of the sorted ids; the padded ids (>= n) lie
    # behind the last one
    segments = jnp.searchsorted(
        ids, jnp.minimum(jnp.arange(blocks + 1, dtype=jnp.int32) * block_n,
                         n)).astype(jnp.int32)

    def ids_window(k):
        return lambda i, seg: (seg[i] // window + k,)

    def sums_window(k):
        return lambda i, seg: (0, seg[i] // window + k)

    table = pl.BlockSpec((e, block_n), lambda i, seg: (0, i),
                         memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        _kernel(block_n, window, lr, b1, b2, eps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(blocks,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((window,), ids_window(0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((window,), ids_window(1),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((e, window), sums_window(0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((e, window), sums_window(1),
                             memory_space=pltpu.VMEM),
                table, table, table,
            ],
            out_specs=[table, table, table],
            scratch_shapes=[pltpu.VMEM((e, block_n), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((e, n), p.dtype)] * 3,
        # operands count from the scalar prefetch: p, m, v are 6, 7, 8
        input_output_aliases={6: 0, 7: 1, 8: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(segments, _bias_corrections(count, b1, b2), ids, ids, sums_t, sums_t,
      p.T, m.T, v.T)
    p, m, v = (x.T for x in out)
    return (p[:, 0], m[:, 0], v[:, 0]) if squeeze else (p, m, v)


def _fused_supported(sig: tuple) -> bool:
    """``sig = (rows, width)``, width 0 for a vector of scalars.  The
    widths whose transposed view is the array the chip holds: whole
    sublane tiles, narrower than a lane tile (compiled for a described
    v5e at 16 and 64: bitcasts, no temporary).  At width 128 the chip
    keeps the table row-major and XLA transposes it around the call; the
    scalar table's ``(1, N)`` view makes it copy ``f32[N]{0:T(1024)}``
    into ``{1,0:T(1,128)}`` and back, five table-sized temporaries (and
    the kernel alone takes 17.3 ms there against the XLA composition's
    2.5; my chip run, PR 32).  Those stay with the XLA composition, as the
    widths under 8 do, unmeasured.  A forced lookup without a signature
    still reaches the kernel (the interpret-mode parity matrix runs
    scalars through it)."""
    return len(sig) == 2 and 8 <= sig[1] <= 64


def _register() -> None:
    from ..kernels.registry import register_kernel, tpu_only

    register_kernel("routed_adam_update", "xla", routed_adam_update_xla)
    register_kernel("routed_adam_update", "pallas", routed_adam_update_fused,
                    priority=20, supports=_fused_supported,
                    available=tpu_only)


_register()
