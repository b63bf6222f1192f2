"""A boosted tree's level histograms as exact one-hot contractions on the MXU.

For one tree level of ``n_nodes`` nodes, ``d`` features and ``bins`` bins:

    g_hist[node, f, b] = sum of grad over the rows with node id ``node``
                         and bin ``b`` of feature ``f``   (hess the same)

Written as a contraction over rows it is ``onehot @ W^T``: ``onehot``
holds, for every (feature, bin), a row of 0/1 over the rows; ``W`` holds,
for every node, the rows' gradient and hessian where the row belongs to
the node and 0 elsewhere.  The MXU multiplies bfloat16 operands, so ``W``
carries each float32 value as three bfloat16 parts whose sum is the value
EXACTLY (``x = a + b + c``, ``a = bf16(x)``, ``b = bf16(x - a)``, ``c = x -
a - b``: 8 significant bits a part, 24 together).  Every product is then
0 or 1 times a bfloat16 and exact in float32, the MXU accumulates in
float32, and the three partial sums are added last: the histogram is the
float32 sum of the rows' values in another order, with no addend
rounded.  (A one-hot contraction at the MXU's default precision rounds
each gradient to bfloat16 before it is summed: relative errors of 2^-9
that do not cancel where many rows share one value, as every row of a
first tree does.)

The rows stream through VMEM a block at a time; the accumulators,
``d * bins`` rows of ``6 * n_nodes`` float32, stay there for the whole
pass.  Nothing of size (rows x features) exists outside the kernel.
Operands are the level-major arrays the trainer keeps on the device:
``d`` feature columns of bin ids (int32, ``(n,)`` each), the rows' node
ids (int32, -1 for a row already in a leaf), gradient and hessian
(float32) — one-dimensional, so that no array of the table is padded to
a tile of the chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows a grid step takes from HBM; the step walks them ``SUB_ROWS`` at a
#: time, so that one step's one-hot stays a few MB of VMEM
BLOCK_ROWS = 32768
SUB_ROWS = 2048
#: rows of one matmul's one-hot: a group of features of ``bins`` rows each
_ONEHOT_ROWS = 512
_VMEM_LIMIT = 48 << 20


def _up(x: int, multiple: int) -> int:
    return -(-x // multiple) * multiple


def block_rows(n: int) -> int:
    """Rows of a grid step for a table of ``n`` rows: ``BLOCK_ROWS``, or
    the rows rounded up to ``SUB_ROWS`` where there are fewer.  A caller
    that keeps ``n`` a multiple of it (``padded_rows``) passes the arrays
    as they are; any other ``n`` is padded inside the call."""
    return min(BLOCK_ROWS, _up(max(n, 1), SUB_ROWS))


def padded_rows(n: int) -> int:
    return _up(max(n, 1), block_rows(n))


def vmem_bytes(d: int, bins: int, n_nodes: int) -> int:
    """VMEM a call of ``d`` features, ``bins`` bins and ``n_nodes`` nodes
    takes as this module lays it out, counted high: two buffers of every
    operand's block of ``BLOCK_ROWS`` rows and of the accumulators, one
    sub-block's node weights (float32 and bfloat16) and one-hot, one
    matmul's product.  Compiled for a v5e, every shape it admits under
    ``_VMEM_LIMIT`` also compiles (``tests/test_tpu_lowering.py``)."""
    bins_p = _up(bins, 16)
    onehot_rows = max(1, _ONEHOT_ROWS // bins_p) * bins_p
    w_rows = _up(6 * n_nodes, 16)
    return (4 * (2 * (d + 3) * BLOCK_ROWS + 2 * d * bins_p * w_rows
                 + onehot_rows * w_rows)
            + 6 * SUB_ROWS * (w_rows + onehot_rows))


def supported(sig: tuple) -> bool:
    """``sig = (d, bins, n_nodes)`` of one level: whether its call fits
    ``_VMEM_LIMIT`` by :func:`vmem_bytes`.  The weights and accumulators
    grow with the nodes, the operands' blocks with the features: at 13
    features a level of up to 256 nodes (32 bins) or 128 (256 bins)."""
    return (len(sig) == 3 and min(sig) >= 1
            and vmem_bytes(*sig) <= _VMEM_LIMIT)


def _exact_bf16_parts(x):
    """Three float32 arrays, each exactly a bfloat16, that sum to ``x``."""
    a = x.astype(jnp.bfloat16).astype(jnp.float32)
    r = x - a
    b = r.astype(jnp.bfloat16).astype(jnp.float32)
    return a, b, r - b


def _kernel(d: int, bins_p: int, n_nodes: int, w_rows: int, sub: int,
            subs: int, group: int):
    def kern(*refs):
        cols = refs[:d]
        node_ref, g_ref, h_ref, out_ref = refs[d:]

        @pl.when(pl.program_id(0) == 0)
        def _init():
            out_ref[...] = jnp.zeros(out_ref.shape, jnp.float32)

        def step(j, carry):
            rows = pl.ds(pl.multiple_of(j * sub, sub), sub)
            node = node_ref[rows].reshape(1, sub)
            hit = node == jax.lax.broadcasted_iota(jnp.int32, (n_nodes, sub),
                                                   0)
            parts = [jnp.where(hit, part, 0.0)
                     for x_ref in (g_ref, h_ref)
                     for part in _exact_bf16_parts(x_ref[rows].reshape(1, sub))]
            if w_rows > 6 * n_nodes:
                parts.append(jnp.zeros((w_rows - 6 * n_nodes, sub),
                                       jnp.float32))
            w = jnp.concatenate(parts, axis=0).astype(jnp.bfloat16)
            ids = jax.lax.broadcasted_iota(jnp.int32, (bins_p, sub), 0)
            for f0 in range(0, d, group):
                fs = range(f0, min(d, f0 + group))
                onehot = jnp.concatenate(
                    [jnp.where(cols[f][rows].reshape(1, sub) == ids, 1.0, 0.0)
                     for f in fs], axis=0).astype(jnp.bfloat16)
                at = pl.ds(f0 * bins_p, len(fs) * bins_p)
                out_ref[at, :] += jax.lax.dot_general(
                    onehot, w, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, subs, step, 0)

    return kern


@functools.partial(jax.jit, static_argnames=("n_nodes", "d", "bins",
                                             "interpret"))
def level_histograms(cols, node_ids, grad, hess, n_nodes: int, d: int,
                     bins: int, *, interpret: bool = False):
    """``(g_hist, h_hist)``, each ``(n_nodes, d, bins)`` float32, of one
    level: ``cols`` the ``d`` bin-id columns ``(n,)`` int32 (ids in
    ``[0, bins)``), ``node_ids`` ``(n,)`` int32 in ``[0, n_nodes)`` or -1
    for a row that no node of the level holds."""
    n = node_ids.shape[0]
    block = block_rows(n)
    pad = padded_rows(n) - n
    if pad:
        cols = [jnp.pad(c, (0, pad)) for c in cols]
        node_ids = jnp.pad(node_ids, (0, pad), constant_values=-1)
        grad, hess = jnp.pad(grad, (0, pad)), jnp.pad(hess, (0, pad))
    sub = min(SUB_ROWS, block)
    bins_p = _up(bins, 16)
    group = max(1, _ONEHOT_ROWS // bins_p)
    w_rows = _up(6 * n_nodes, 16)
    row_block = pl.BlockSpec((block,), lambda i: (i,))
    acc = pl.pallas_call(
        _kernel(d, bins_p, n_nodes, w_rows, sub, block // sub, group),
        grid=((n + pad) // block,),
        in_specs=[row_block] * (d + 3),
        out_specs=pl.BlockSpec((d * bins_p, w_rows), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((d * bins_p, w_rows), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(*cols, node_ids, grad, hess)
    acc = acc[:, :6 * n_nodes].reshape(d, bins_p, 6, n_nodes)[:, :bins]
    g = (acc[:, :, 0] + acc[:, :, 1]) + acc[:, :, 2]
    h = (acc[:, :, 3] + acc[:, :, 4]) + acc[:, :, 5]
    return jnp.transpose(g, (2, 0, 1)), jnp.transpose(h, (2, 0, 1))
