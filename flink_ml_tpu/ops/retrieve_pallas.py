"""Fused Pallas ``retrieve`` backend: coarse probe -> DMA posting lists
-> masked scan -> top-k merge, VMEM-resident, eight queries per grid step.

The XLA lowering (``retrieval/ivf.py``) gathers the probed posting-list
blocks into a ``(b, nprobe, block)`` candidate tensor; XLA:TPU keeps the
distance tiles fused but the gathered vector blocks themselves still
round-trip HBM once per operand of the scan.  This kernel streams each
probed block HBM->VMEM with an explicit async copy instead: the posting
arrays stay in ``pl.ANY`` (HBM) and only the ``nprobe`` blocks a
query actually probes ever move, directly into a reused VMEM scratch
buffer — candidate distances and the running top-k never exist outside
VMEM.

A grid step owns a block of ``_QUERY_BLOCK`` query rows (the TPU block
rule: second-to-last block dim a multiple of 8, or the whole array) and
walks them one at a time, so the per-query program is the same whatever
the batch.

On the chip (PR 21, TPU v5 lite): the FLAT scan (backend ``pallas``)
compiles under Mosaic and matches (``tests_tpu``), and is planned where
``dim`` and ``block`` are multiples of 128 (:func:`fused_supported`); the
PQ scan (backend ``pallas-pq``) does not lower and is forced-lookup only
— see :func:`_register`.

Parity contract: per-row outputs are BITWISE-equal to the XLA backend in
interpret mode (asserted by the ``tests/test_kernels.py`` matrix).  The
kernel guarantees this by construction —

- distance expressions are THE shared helpers of ``retrieval/ivf.py``
  (``coarse_distances`` / ``flat_distances`` / ``pq_lut`` /
  ``adc_distances``), never re-derived forms;
- probes are consumed in ascending (distance, list-index) order — the
  exact order ``lax.top_k`` emits them, reproduced with the
  where/min/iota first-index selection of the KMeans Pallas kernels (a
  true argmin would lower to a slow Mosaic index loop);
- the running top-k merge breaks distance ties by candidate POSITION
  (k kept slots first, then the block in row order), which provably
  equals ``lax.top_k``'s lowest-flat-index tie rule because kept slots
  always originate from earlier flat positions than the block being
  merged.  Consumed slots are neutralised in both coordinates (distance
  -> +inf AND position -> out-of-range) so an all-+inf tail can never
  re-select them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..kernels.registry import register_kernel, tpu_only
from ..retrieval.ivf import (adc_distances, coarse_distances,
                             decode_codebooks, flat_distances, pq_lut,
                             runtime_one)

__all__ = ["retrieve_stage_pallas", "fused_supported"]

_VMEM_BUDGET = 12 * 1024 * 1024  # headroom below the ~16 MB/core VMEM


def _tile_bytes(dim: int, nlist: int, block: int, k: int) -> int:
    """Per-step VMEM model of the flat scan: resident centroids + the
    DMA'd posting block + the merge tiles.  The merge chain is modelled
    as ~4 live (1, k + block) tiles (candidates, positions, the compare
    masks) — unrolled steps reuse the same buffers."""
    resident = nlist * dim * 4 + nlist * 4          # centroids + coarse row
    resident += block * dim * 4 + block * 4         # vector buf + ids buf
    merge = 4 * (k + block) * 4
    return resident + merge


def fused_supported(sig: tuple) -> bool:
    """supports() predicate of the flat scan: a well-formed FLAT
    (``m == 0``) ``retrieve`` signature whose DMA slices Mosaic accepts
    and whose working set fits the VMEM budget."""
    if len(sig) != 7:
        return False
    nprobe, k, dim, m, _ksub, nlist, block = sig
    if m or not 1 <= nprobe <= nlist or k < 1:
        return False
    # the per-probe DMAs copy (block, dim) vector rows and a (1, block) id
    # row out of HBM: Mosaic wants both slices' lane extents tile-aligned
    # ("Slice shape along dimension 1 must be aligned to tiling (128), but
    # is 32" at dim=32 on the chip)
    if dim % 128 or block % 128:
        return False
    return _tile_bytes(dim, nlist, block, k) <= _VMEM_BUDGET


def _pq_sig(sig: tuple) -> bool:
    """supports() predicate of the parked PQ scan: any PQ signature."""
    return len(sig) == 7 and sig[3] > 0


def _select_first_min(scores, iota, out_of_range):
    """Smallest index attaining the row minimum — the KMeans Pallas
    where/min/iota idiom (first-index argmin without an argmin loop)."""
    mins = jnp.min(scores, axis=1, keepdims=True)
    return jnp.min(jnp.where(scores <= mins, iota, out_of_range))


def _merge_topk(best_d, best_i, dist, ids_row, k: int):
    """Merge one probed block into the running top-k.  Tie rule: smallest
    candidate position (kept slots 0..k-1, block slots k..), which equals
    ``lax.top_k``'s lowest-flat-index rule — see the module docstring."""
    total = k + dist.shape[1]
    cand_d = jnp.concatenate([best_d, dist], axis=1)
    cand_i = jnp.concatenate([best_i, ids_row], axis=1)
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, total), 1)
    out_d, out_i = [], []
    for _ in range(k):
        dmin = jnp.min(cand_d, axis=1, keepdims=True)
        tied = cand_d <= dmin
        pmin = jnp.min(jnp.where(tied, pos, total), axis=1, keepdims=True)
        sel = pos == pmin                      # exactly one slot
        out_d.append(jnp.sum(jnp.where(sel, cand_d, 0.0), axis=1,
                             keepdims=True))
        out_i.append(jnp.sum(jnp.where(sel, cand_i, 0), axis=1,
                             keepdims=True))
        cand_d = jnp.where(sel, jnp.inf, cand_d)
        pos = jnp.where(sel, total, pos)       # never re-selectable
    return (jnp.concatenate(out_d, axis=1),
            jnp.concatenate(out_i, axis=1).astype(jnp.int32))


#: query rows per grid step: the sublane tile, so the (rows, d) query block
#: and the (rows, k) output blocks satisfy the TPU block-shape rule
_QUERY_BLOCK = 8


def _for_each_query(q_ref, nn_ref, nd_ref, scan_one) -> None:
    """Run ``scan_one(q (1, d)) -> (ids (1, k), dists (1, k))`` over the
    rows of this grid step's query block, one row at a time."""
    def body(j, carry):
        best_i, best_d = scan_one(q_ref[pl.ds(j, 1), :])
        nn_ref[pl.ds(j, 1), :] = best_i
        nd_ref[pl.ds(j, 1), :] = best_d
        return carry

    jax.lax.fori_loop(0, q_ref.shape[0], body, 0)


def _flat_kernel(nprobe: int, k: int, nlist: int):
    def kern(q_ref, cent_ref, ids_hbm, vecs_hbm, nn_ref, nd_ref,
             vec_buf, ids_buf, sem_v, sem_i):
        iota_l = jax.lax.broadcasted_iota(jnp.int32, (1, nlist), 1)

        def scan_one(q):                                     # (1, d)
            coarse = coarse_distances(q, cent_ref[:])        # (1, nlist)
            best_d = jnp.full((1, k), jnp.inf, jnp.float32)
            best_i = jnp.full((1, k), -1, jnp.int32)
            for _ in range(nprobe):
                probe = _select_first_min(coarse, iota_l, nlist)
                coarse = jnp.where(iota_l == probe, jnp.inf, coarse)
                cp_v = pltpu.make_async_copy(vecs_hbm.at[probe], vec_buf,
                                             sem_v)
                cp_i = pltpu.make_async_copy(ids_hbm.at[probe], ids_buf,
                                             sem_i)
                cp_v.start()
                cp_i.start()
                cp_v.wait()
                cp_i.wait()
                # (1, d) x (block, d): a plain 2-D contraction — Mosaic
                # has no form for the batched one a leading 1 would make
                dist = flat_distances(q, vec_buf[:])         # (1, block)
                ids_row = ids_buf[:]                         # (1, block)
                dist = jnp.where(ids_row >= 0, dist, jnp.inf)
                best_d, best_i = _merge_topk(best_d, best_i, dist, ids_row,
                                             k)
            return best_i, best_d

        _for_each_query(q_ref, nn_ref, nd_ref, scan_one)

    return kern


def _pq_kernel(nprobe: int, k: int, nlist: int, m: int):
    def kern(q_ref, cent_ref, cbq_ref, cbs_ref, ids_hbm, codes_hbm,
             nn_ref, nd_ref, code_buf, ids_buf, sem_c, sem_i):
        one = runtime_one(cbs_ref[0, 0])
        # mirror of the XLA stage: runtime-1.0 pins the decode rounding
        books = decode_codebooks(cbq_ref[:], cbs_ref[:]) * one
        iota_l = jax.lax.broadcasted_iota(jnp.int32, (1, nlist), 1)

        def scan_one(q):                                     # (1, d)
            coarse = coarse_distances(q, cent_ref[:])
            best_d = jnp.full((1, k), jnp.inf, jnp.float32)
            best_i = jnp.full((1, k), -1, jnp.int32)
            for _ in range(nprobe):
                probe = _select_first_min(coarse, iota_l, nlist)
                coarse = jnp.where(iota_l == probe, jnp.inf, coarse)
                cp_c = pltpu.make_async_copy(codes_hbm.at[probe], code_buf,
                                             sem_c)
                cp_i = pltpu.make_async_copy(ids_hbm.at[probe], ids_buf,
                                             sem_i)
                cp_c.start()
                cp_i.start()
                cp_c.wait()
                cp_i.wait()
                resid = q - cent_ref[pl.ds(probe, 1), :]     # (1, d)
                lut = pq_lut(resid.reshape(1, m, -1), books, one)
                dist = adc_distances(lut, code_buf[:][None])  # (1, block)
                ids_row = ids_buf[:]
                dist = jnp.where(ids_row >= 0, dist, jnp.inf)
                best_d, best_i = _merge_topk(best_d, best_i, dist, ids_row,
                                             k)
            return best_i, best_d

        _for_each_query(q_ref, nn_ref, nd_ref, scan_one)

    return kern


def _fused_call(kernel, q, resident, hbm, k: int, scratch, interpret: bool):
    """The ``pallas_call`` both scans share: queries blocked
    ``_QUERY_BLOCK`` rows per grid step (zero pad rows are scanned and
    sliced off — outputs are row-independent), ``resident`` operands
    whole in VMEM, ``hbm`` operands left in place for the kernel's DMAs.
    The posting arrays go in as (nlist, rows, lanes) so a probe's DMA
    source is ``ref.at[probe]`` — an index on an untiled leading dim;
    Mosaic refuses a one-row slice of a tiled dim ("Slice shape along
    dimension 0 must be aligned to tiling (8), but is 1")."""
    b, d = q.shape
    pad = (-b) % _QUERY_BLOCK
    if pad:
        q = jnp.concatenate([q, jnp.zeros((pad, d), q.dtype)])
    rows = q.shape[0]
    out_block = pl.BlockSpec((_QUERY_BLOCK, k), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)
    nbrs, dists = pl.pallas_call(
        kernel,
        grid=(rows // _QUERY_BLOCK,),
        in_specs=[pl.BlockSpec((_QUERY_BLOCK, d), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)]
        + [pl.BlockSpec(a.shape, lambda i, nd=a.ndim: (0,) * nd,
                        memory_space=pltpu.VMEM) for a in resident]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(hbm),
        out_specs=[out_block, out_block],
        out_shape=[
            jax.ShapeDtypeStruct((rows, k), jnp.int32),
            jax.ShapeDtypeStruct((rows, k), jnp.float32),
        ],
        scratch_shapes=scratch + [pltpu.SemaphoreType.DMA,
                                  pltpu.SemaphoreType.DMA],
        interpret=interpret,
    )(q, *resident, *hbm)
    return nbrs[:b], dists[:b]


@functools.partial(
    jax.jit, static_argnames=("nprobe", "k", "nlist", "block", "interpret"))
def retrieve_flat_fused(q, centroids, ids, vecs, *, nprobe: int, k: int,
                        nlist: int, block: int, interpret: bool = False):
    """Fused flat-f32 search: ``(q (b, d), centroids (nlist, d), ids
    (nlist, block) i32, vecs (nlist*block, d)) -> (neighbors (b, k) i32,
    distances (b, k) f32)``."""
    d = q.shape[1]
    return _fused_call(
        _flat_kernel(nprobe, k, nlist), q, [centroids],
        [ids[:, None, :], vecs.reshape(nlist, block, d)],
        k, [pltpu.VMEM((block, d), jnp.float32),
            pltpu.VMEM((1, block), jnp.int32)], interpret)


@functools.partial(
    jax.jit,
    static_argnames=("nprobe", "k", "nlist", "block", "m", "interpret"))
def retrieve_pq_fused(q, centroids, ids, codes, cb_q, cb_s, *, nprobe: int,
                      k: int, nlist: int, block: int, m: int,
                      interpret: bool = False):
    """Fused IVF-PQ search: int8 code blocks DMA'd per probe, LUT built
    in VMEM from the decoded per-subspace codebooks."""
    return _fused_call(
        _pq_kernel(nprobe, k, nlist, m), q, [centroids, cb_q, cb_s],
        [ids[:, None, :], codes.reshape(nlist, block, m)],
        k, [pltpu.VMEM((block, m), jnp.int8),
            pltpu.VMEM((1, block), jnp.int32)], interpret)


def retrieve_stage_pallas(static, params, cols, *, interpret: bool = False):
    """Stage-convention entry: same (static, params, cols) contract and
    staging outputs as the XLA stage in ``retrieval/ivf.py``."""
    (qcol, ncol, dcol, nprobe, k, nlist, block, m, _ksub) = static
    q = cols[qcol]
    if m:
        nbrs, dists = retrieve_pq_fused(
            q, params["centroids"], params["ids"], params["codes"],
            params["cb_q"], params["cb_s"], nprobe=nprobe, k=k,
            nlist=nlist, block=block, m=m, interpret=interpret)
    else:
        nbrs, dists = retrieve_flat_fused(
            q, params["centroids"], params["ids"], params["vecs"],
            nprobe=nprobe, k=k, nlist=nlist, block=block,
            interpret=interpret)
    return {ncol: nbrs, dcol: dists}


def _register() -> None:
    register_kernel("retrieve", "pallas", retrieve_stage_pallas,
                    priority=10, supports=fused_supported,
                    available=tpu_only, convention="stage")
    # The PQ scan is its own backend so it can be parked by itself: Pallas'
    # TPU lowering rejects ``adc_distances``' LUT gather — take_along_axis
    # over the (1, m, ksub) table — with "NotImplementedError: Only 2D
    # gather is supported".  Scanning the codes some other way (a one-hot
    # contraction per subspace) is a rewrite, not a layout change
    # (ROADMAP S5b); PQ indexes plan the XLA stage meanwhile.
    register_kernel(
        "retrieve", "pallas-pq", retrieve_stage_pallas, supports=_pq_sig,
        convention="stage",
        forced_only='Pallas TPU lowering: "NotImplementedError: Only 2D '
                    'gather is supported" (the ADC lookup-table gather)')


_register()
