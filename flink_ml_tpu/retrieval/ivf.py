"""IVF / IVF-PQ vector index: build, search planning, incremental updates.

The index is the repo's first NON-model servable (ISSUE 19): approximate
nearest-neighbor retrieval packaged behind the exact seams models serve
through — a registry-dispatched ``retrieve`` kernel plan, the bucketed
``run_kernel`` dispatch surface, rebind-safe generation swaps, and the
PR 7 delta codec for incremental posting-list updates.

**Index layout.**  ``IVFIndex.build(vectors, nlist, pq=None)`` trains the
coarse quantizer with the EXISTING workset KMeans fit (the delta-iteration
Lloyd's from ``models/clustering/kmeans.py`` — no second clustering
implementation), then assigns every vector to its nearest centroid's
posting list.  Lists are device-resident padded row blocks: each list
occupies ``block`` contiguous rows of one packed ``(nlist*block, d)``
array (the CSR row-block layout; ``offsets`` below are the CSR offsets of
the REAL rows), padded with exact zeros through the maskless
``pad_rows_to_block`` contract of ``utils/padding.py`` — pad rows carry
id ``-1`` and are masked inert inside the kernel, never corrected after.

**PQ variant.**  ``pq=PQConfig(m, ksub)`` stores residuals (vector minus
its coarse centroid) as ``m`` int8 codes per vector against per-subspace
codebooks.  Sub-codebooks are trained with the same workset KMeans on
each residual subspace and STORED through the ``kernels/quantize.py``
recipe (per-row symmetric max-abs int8 codes + f32 scales,
``quantize_rows``); encoding argmins against the DECODED book, so the
codes are exact argmins of the values the kernel actually scans with.

**Search.**  ONE registry-dispatched kernel per ``(nprobe, k, dim, pq)``
schema: coarse-probe selection, masked posting-list scan (flat f32 or PQ
lookup-table distances) and top-k merge are a single fused program —
candidate distances never round-trip HBM.  The XLA backend below runs
everywhere; ``ops/retrieve_pallas.py`` registers a VMEM-blocked Pallas
backend gated TPU-only, bitwise-equal per row in interpret mode (the
parity matrix in ``tests/test_kernels.py`` enforces both an exact
brute-force oracle and a recall envelope per backend).

**Updates.**  ``updated(inserts, delete_ids)`` edits posting-list blocks
in place (swap-remove deletes, free-slot inserts) and reports ``"delta"``
— the changed rows ride the PR 7 sparse delta codec under digest
verification.  When a list overflows its block or the centroid drift
(max per-list ||member mean - centroid|| over the centroid RMS norm)
crosses ``drift_threshold``, it reports ``"reanchor"`` with a freshly
built index instead: same-shape re-anchors publish as one FullUpdate,
shape-changing ones go through ``registry.deploy``.  Generation swaps
are atomic either way — in-flight queries finish on the old lists.
"""

from __future__ import annotations

import dataclasses

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..data.table import Table
from ..kernels.quantize import quantize_rows
from ..kernels.registry import lookup, register_kernel
from ..utils.padding import pad_rows_to_block, require_block_rows

__all__ = [
    "IVFIndex",
    "PQConfig",
    "SearchPlan",
    "adc_distances",
    "coarse_distances",
    "decode_codebooks",
    "flat_distances",
    "pq_lut",
    "retrieve_sig",
]


@dataclasses.dataclass(frozen=True)
class PQConfig:
    """Product-quantization config: ``m`` subspaces of ``dim // m``
    components each, ``ksub`` codebook entries per subspace (int8 codes,
    so at most 127), trained for ``max_iter`` workset-KMeans rounds."""

    m: int
    ksub: int = 16
    max_iter: int = 8


@dataclasses.dataclass(frozen=True)
class SearchPlan:
    """The planned search schema: the registry signature, the plan-static
    tuple, and the backend the registry resolved for this host."""

    sig: tuple
    static: tuple
    backend: str


def retrieve_sig(nprobe: int, k: int, dim: int, m: int, ksub: int,
                 nlist: int, block: int) -> tuple:
    """The ``retrieve`` op's registry signature — one kernel schema per
    (nprobe, k, dim, pq) point; ``m == 0`` is the flat-f32 scan."""
    return (nprobe, k, dim, m, ksub, nlist, block)


# ---------------------------------------------------------------------------
# shared distance expressions.  Both backends (the XLA stage fn below and
# the Pallas kernel body in ops/retrieve_pallas.py) call THESE helpers, so
# per-row outputs are expression-identical by construction — the parity
# matrix asserts bitwise equality in interpret mode.  Broadcasting over
# leading batch dims keeps one definition serving the vectorized XLA form
# (b, nprobe, ...) and the per-query Pallas form (1, ...).
# ---------------------------------------------------------------------------

def coarse_distances(q, centroids):
    """Selection-only coarse scores ``||c||^2 - 2 q.c`` for ``q`` of shape
    (..., d) against (nlist, d) — the ``q^2`` term is rank-invariant and
    omitted, exactly like the KMeans assign kernel's pairwise."""
    c2 = jnp.sum(centroids * centroids, axis=-1)
    qc = jnp.dot(q, centroids.T, preferred_element_type=jnp.float32)
    return c2 - 2.0 * qc


def flat_distances(q, vecs):
    """Full squared L2 ``||q - x||^2`` (as ``q^2 + x^2 - 2 q.x``) for
    ``q`` (..., d) against row blocks ``vecs`` (..., L, d) -> (..., L)."""
    q2 = jnp.sum(q * q, axis=-1)[..., None]
    x2 = jnp.sum(vecs * vecs, axis=-1)
    qx = jnp.einsum("...d,...ld->...l", q, vecs)
    return q2 + x2 - 2.0 * qx


def decode_codebooks(cb_q, cb_s):
    """Dequantize the stored per-subspace codebooks: int8 codes
    (m, ksub, dsub) times per-row scales (m, ksub) — the exact inverse of
    the ``quantize_rows`` recipe they were stored with."""
    return cb_q.astype(jnp.float32) * cb_s[..., None]


def pq_lut(resid, codebooks, one):
    """Per-(query, probe) ADC lookup table: squared L2 from the query's
    residual subvectors (..., m, dsub) to every codebook entry
    (m, ksub, dsub) -> (..., m, ksub).

    ``one`` must be a RUNTIME f32 1.0 (see :func:`runtime_one`): it pins
    the rounding of each squared term before the reduction adds.  LLVM
    may contract a mul feeding an add into one fma, skipping the mul's
    intermediate rounding — and it decides differently for the two
    backends' fusion shapes, a 1-ulp parity break.  With the runtime
    mul in between, the square is always rounded (mul-mul never
    contracts) and any fma THROUGH the barrier is value-identical
    (``fma(t, 1, c)`` rounds to exactly ``t + c``) — the registry's
    ``_run_plan`` rounding-barrier argument, applied inside the
    expression."""
    return jnp.sum(((resid[..., None, :] - codebooks) ** 2) * one,
                   axis=-1)


def runtime_one(x):
    """An exactly-1.0 f32 the compiler must treat as runtime data: float
    ``x * 0`` is never algebraically simplified (NaN/Inf semantics), so
    the chain can't constant-fold.  ``x`` must be a finite runtime
    value — both backends derive it from the codebook scales."""
    return x * 0.0 + 1.0


def adc_distances(lut, codes):
    """Asymmetric-distance scan: gather each candidate's per-subspace LUT
    entries and sum.  ``lut`` (..., m, ksub), ``codes`` (..., L, m) ->
    (..., L)."""
    idx = jnp.swapaxes(codes.astype(jnp.int32), -1, -2)
    return jnp.sum(jnp.take_along_axis(lut, idx, axis=-1), axis=-2)


# ---------------------------------------------------------------------------
# the XLA backend: ONE fused stage — coarse-probe selection, masked
# posting-list scan, top-k merge.  Candidate distances live only as
# fusion-internal values of this one dispatched program.
# ---------------------------------------------------------------------------

def _retrieve_stage_xla(static, params, cols):
    """Stage-convention ``retrieve`` kernel (XLA lowering, every host).

    Pad rows of the query batch are inert (row-independent outputs,
    sliced off by the dispatch fetch); pad slots of the posting lists
    carry id ``-1`` and are masked to ``+inf`` distance, so they can win
    a top-k slot only when fewer than k real candidates were scanned —
    reported as neighbor ``-1`` at distance ``+inf``, never a fake id.

    The flat scan runs as a ``lax.map`` over the query batch with
    ``dynamic_slice`` slab reads rather than one batched gather:
    XLA:CPU scalarizes a (b, nprobe) gather of (block, d) row slabs to
    per-element loads and then re-streams the materialized candidate
    tensor through each fused consumer.  A dynamic-slice of a contiguous row block is a plain
    copy, and the whole per-query scan (norms, dot, mask, top-k) stays
    resident in cache.  The distance math is identical expression for
    expression, so the per-row bits — and Pallas parity — are
    unchanged.  PQ codes are ~d/m times smaller per row, the batched
    gather is not the bottleneck there, and the LUT build wants the
    query batch whole, so the PQ path keeps the batched form."""
    (qcol, ncol, dcol, nprobe, k, nlist, block, m, _ksub) = static
    q = cols[qcol]                                       # (b, d)
    cents = params["centroids"]
    coarse = coarse_distances(q, cents)                  # (b, nlist)
    _, probes = jax.lax.top_k(-coarse, nprobe)           # (b, nprobe)
    if m:
        pids = params["ids"][probes]                     # (b, nprobe, L)
        codes = params["codes"].reshape(nlist, block, m)[probes]
        resid = q[:, None, :] - cents[probes]            # (b, nprobe, d)
        one = runtime_one(params["cb_s"][0, 0])
        # the same runtime-1.0 pins the decoded books' rounding: the
        # decode mul feeding the LUT subtraction is itself a contraction
        # candidate (fused multiply-subtract)
        books = decode_codebooks(params["cb_q"], params["cb_s"]) * one
        lut = pq_lut(resid.reshape(resid.shape[:-1] + (m, -1)),
                     books, one)
        dist = adc_distances(lut, codes)                 # (b, nprobe, L)
        dist = jnp.where(pids >= 0, dist, jnp.inf)
        flat_d = dist.reshape(dist.shape[0], -1)
        flat_i = pids.reshape(pids.shape[0], -1)
        neg, pos = jax.lax.top_k(-flat_d, k)
        nbrs = jnp.take_along_axis(flat_i, pos, axis=1)
        return {ncol: nbrs.astype(jnp.int32), dcol: -neg}

    vecs = params["vecs"]                                # (nlist*block, d)
    ids = params["ids"]                                  # (nlist, block)

    def scan_one(args):
        qi, pr = args                                    # (d,), (nprobe,)
        dists, pids = [], []
        for j in range(nprobe):
            slab = jax.lax.dynamic_slice(
                vecs, (pr[j] * block, 0), (block, vecs.shape[1]))
            dists.append(
                flat_distances(qi[None, None, :], slab[None, None])[0, 0])
            pids.append(
                jax.lax.dynamic_slice(ids, (pr[j], 0), (1, block))[0])
        dist = jnp.stack(dists)                          # (nprobe, L)
        pid = jnp.stack(pids)                            # (nprobe, L)
        dist = jnp.where(pid >= 0, dist, jnp.inf)
        neg, pos = jax.lax.top_k(-dist.reshape(-1), k)
        return jnp.take(pid.reshape(-1), pos), -neg

    nbrs, dist = jax.lax.map(scan_one, (q, probes))
    return {ncol: nbrs.astype(jnp.int32), dcol: dist}


# ---------------------------------------------------------------------------
# the index
# ---------------------------------------------------------------------------

class IVFIndex:
    """A built IVF / IVF-PQ index: device params + host bookkeeping.

    ``params`` is the canonical publish pytree (a flat dict — the delta
    publisher's ``params_of_model`` adapter returns it verbatim):
    ``centroids`` (nlist, d) f32, ``ids`` (nlist, block) int32 (-1 =
    empty slot), ``counts`` (nlist,) int32, and either ``vecs``
    (nlist*block, d) f32 (flat) or ``codes`` (nlist*block, m) int8 +
    ``cb_q``/``cb_s`` codebooks (PQ).  Everything else (the id->vector
    store for drift/re-anchor/exact-scan probes) is host-side only and
    never ships to serving."""

    query_col = "query"
    neighbors_col = "neighbors"
    distances_col = "distances"

    def __init__(self, *, params: Dict[str, np.ndarray], nlist: int,
                 block: int, dim: int, k: int, nprobe: int,
                 pq: Optional[PQConfig], seed: int, list_slack: int,
                 drift_threshold: Optional[float], max_iter: int,
                 store: Dict[int, np.ndarray]):
        self.params = params
        self.nlist = int(nlist)
        self.block = int(block)
        self.dim = int(dim)
        self.k = int(k)
        self.nprobe = int(nprobe)
        self.pq = pq
        self.seed = int(seed)
        self.list_slack = int(list_slack)
        self.drift_threshold = drift_threshold
        self.max_iter = int(max_iter)
        self._store = store

    # -- build --------------------------------------------------------------
    @classmethod
    def build(cls, vectors, nlist: int, pq: Optional[PQConfig] = None, *,
              k: int = 10, nprobe: Optional[int] = None,
              ids=None, seed: int = 0, list_slack: int = 8,
              drift_threshold: Optional[float] = 0.25, max_iter: int = 10,
              block: Optional[int] = None) -> "IVFIndex":
        """Train the coarse quantizer (workset KMeans fit), assign vectors
        to padded posting-list row blocks, and (PQ) encode residuals.

        ``block`` (rows per list, a multiple of 8) is normally sized to
        the fullest list plus ``list_slack`` insert headroom; passing it
        explicitly pins the device shapes — the same-shape re-anchor
        path uses this so a rebuilt index can publish as one FullUpdate
        instead of a full redeploy."""
        from ..models.clustering.kmeans import KMeans

        vectors = np.ascontiguousarray(np.asarray(vectors, np.float32))
        if vectors.ndim != 2 or vectors.shape[0] == 0:
            raise ValueError("vectors must be a non-empty (n, d) array")
        n, dim = vectors.shape
        if not 1 <= nlist <= n:
            raise ValueError(f"nlist={nlist} must be in [1, n={n}]")
        ids = (np.arange(n, dtype=np.int32) if ids is None
               else np.asarray(ids, np.int32))
        if ids.shape != (n,) or len(set(ids.tolist())) != n:
            raise ValueError("ids must be n unique int32 values")
        if np.any(ids < 0):
            raise ValueError("ids must be non-negative (-1 marks pad "
                             "slots in the posting lists)")
        if pq is not None:
            if dim % pq.m:
                raise ValueError(f"PQ m={pq.m} must divide dim={dim}")
            if not 2 <= pq.ksub <= 127:
                raise ValueError("PQ ksub must be in [2, 127] (int8 "
                                 "codes)")
            if n < pq.ksub:
                raise ValueError(f"PQ needs n >= ksub={pq.ksub}")

        coarse_fit = (KMeans().set_k(nlist).set_workset(True)
                      .set_seed(seed).set_max_iter(max_iter)
                      .fit(Table({"features": vectors})))
        centroids = np.asarray(
            coarse_fit.get_model_data()[0]["centroids"][0], np.float32)
        centroids = _refine_balance(centroids, vectors)
        assign = _nearest_list(centroids, vectors)
        counts = np.bincount(assign, minlength=nlist).astype(np.int32)
        need = int(counts.max()) if n else 1
        if block is None:
            block = _round_up8(max(need + list_slack, 8))
        elif need > block:
            raise ValueError(f"block={block} cannot hold the fullest "
                             f"list ({need} rows)")
        require_block_rows(block, 8, op="retrieve")

        ids2 = np.full((nlist, block), -1, np.int32)
        rows_of: List[np.ndarray] = []
        for lst in range(nlist):
            rows = np.flatnonzero(assign == lst)
            rows_of.append(rows)
            ids2[lst, :rows.size] = ids[rows]
        params: Dict[str, np.ndarray] = {
            "centroids": centroids,
            "ids": ids2,
            "counts": counts,
        }
        if pq is None:
            params["vecs"] = _pack_blocks(vectors, rows_of, block, dim,
                                          np.float32)
        else:
            cb_q, cb_s = _fit_codebooks(
                vectors - centroids[assign], pq, seed, max_iter)
            codes = _encode_pq(vectors - centroids[assign], cb_q, cb_s)
            params["codes"] = _pack_blocks(codes, rows_of, block, pq.m,
                                           np.int8)
            params["cb_q"], params["cb_s"] = cb_q, cb_s
        store = {int(i): vectors[j].copy()
                 for j, i in enumerate(ids.tolist())}
        return cls(params=params, nlist=nlist, block=block, dim=dim, k=k,
                   nprobe=(max(1, nlist // 8) if nprobe is None
                           else int(nprobe)),
                   pq=pq, seed=seed, list_slack=list_slack,
                   drift_threshold=drift_threshold, max_iter=max_iter,
                   store=store)

    # -- search planning ----------------------------------------------------
    def sig(self) -> tuple:
        pq = self.pq
        return retrieve_sig(self.nprobe, self.k, self.dim,
                            pq.m if pq else 0, pq.ksub if pq else 0,
                            self.nlist, self.block)

    def _static(self) -> tuple:
        pq = self.pq
        return (self.query_col, _NN_STAGE, _DIST_STAGE, self.nprobe,
                self.k, self.nlist, self.block, pq.m if pq else 0,
                pq.ksub if pq else 0)

    def search_plan(self) -> SearchPlan:
        """Resolve this index's (nprobe, k, dim, pq) schema against the
        kernel registry: Pallas on TPU hosts, the XLA lowering
        everywhere else — the availability/supports predicates decide,
        never a call-site branch."""
        entry = lookup("retrieve", self.sig())
        return SearchPlan(sig=self.sig(), static=self._static(),
                          backend=entry.backend)

    def with_options(self, *, nprobe: Optional[int] = None,
                     k: Optional[int] = None) -> "IVFIndex":
        """A view of the same index at a different operating point (new
        plan schema, same posting lists): what an nprobe sweep walks."""
        clone = dataclasses.replace if False else None  # noqa: F841
        out = IVFIndex.__new__(IVFIndex)
        out.__dict__.update(self.__dict__)
        if nprobe is not None:
            if not 1 <= nprobe <= self.nlist:
                raise ValueError(f"nprobe={nprobe} not in [1, "
                                 f"nlist={self.nlist}]")
            out.nprobe = int(nprobe)
        if k is not None:
            out.k = int(k)
        return out

    def transform_kernel(self, schema):
        """Chain TERMINAL: the registry-resolved fused scan as a
        StageKernel — the same (fn, static) plan the serving executor,
        the fused pipelines, and offline ``transform`` dispatch."""
        from ..api.chain import StageKernel, numeric_entry

        if numeric_entry(schema, self.query_col) is None:
            return None
        entry = lookup("retrieve", self.sig())
        ncol, dcol = self.neighbors_col, self.distances_col

        def post(host):
            return {ncol: host[_NN_STAGE].astype(np.int64),
                    dcol: host[_DIST_STAGE]}

        return StageKernel(
            fn=entry.fn, static=self._static(),
            params={k: np.asarray(v) for k, v in self.params.items()},
            consumes=(self.query_col,),
            produces=(_NN_STAGE, _DIST_STAGE), post=post)

    # -- search -------------------------------------------------------------
    def transform(self, *inputs) -> List[Table]:
        """Batch search: appends ``neighbors`` (n, k) int64 ids (-1 for
        unfilled slots) and ``distances`` (n, k) f32 — squared L2 for
        flat, the ADC lookup-table approximation for PQ."""
        (table,) = inputs
        from ..api.chain import run_kernel

        kernel = self.transform_kernel(table.schema())
        if kernel is None:
            raise TypeError(
                f"IVFIndex.transform needs a numeric {self.query_col!r} "
                "column of query vectors")
        cols = run_kernel(kernel, table, op="retrieve")
        out = table.with_column(self.neighbors_col,
                                cols[self.neighbors_col])
        return [out.with_column(self.distances_col,
                                cols[self.distances_col])]

    def search(self, queries, *, nprobe: Optional[int] = None,
               k: Optional[int] = None) -> Tuple[np.ndarray, np.ndarray]:
        """Convenience entry: (neighbor ids (n, k) int64, distances
        (n, k) f32) for a raw (n, d) query array."""
        index = self.with_options(nprobe=nprobe, k=k)
        out = index.transform(Table({self.query_col: np.asarray(
            queries, np.float32)}))[0]
        return (np.asarray(out[self.neighbors_col]),
                np.asarray(out[self.distances_col]))

    def scan_fraction(self, queries, nprobe: Optional[int] = None) -> float:
        """Analytic scan accounting: the mean over queries of (real rows
        in the probed lists) / (live rows) — derived from the coarse
        selection and the CSR counts, not from timing."""
        nprobe = self.nprobe if nprobe is None else int(nprobe)
        queries = np.asarray(queries, np.float32)
        cents = self.params["centroids"]
        coarse = (np.sum(cents * cents, axis=1)[None, :]
                  - 2.0 * queries @ cents.T)
        probes = np.argsort(coarse, axis=1, kind="stable")[:, :nprobe]
        live = max(1, self.num_vectors)
        scanned = self.params["counts"][probes].sum(axis=1)
        return float(np.mean(scanned) / live)

    # -- bookkeeping ---------------------------------------------------------
    @property
    def num_vectors(self) -> int:
        return int(self.params["counts"].sum())

    @property
    def offsets(self) -> np.ndarray:
        """CSR list offsets of the REAL rows (exclusive cumsum of
        ``counts``; ``offsets[-1]`` is the live row total) — the logical
        addressing the padded row blocks materialize at stride
        ``block``."""
        return np.concatenate(
            ([0], np.cumsum(self.params["counts"], dtype=np.int64)))

    def stored_vectors(self) -> Tuple[np.ndarray, np.ndarray]:
        """(ids (n,) int32, vectors (n, d) f32) of every live vector in
        ascending id order — the exact-scan reference for recall
        probes and the re-anchor rebuild corpus."""
        order = sorted(self._store)
        ids = np.asarray(order, np.int32)
        if not order:
            return ids, np.zeros((0, self.dim), np.float32)
        return ids, np.stack([self._store[i] for i in order])

    def centroid_drift(self) -> float:
        """Max over non-empty lists of ||member mean - centroid||, over
        the RMS centroid norm — the configurable re-anchor signal."""
        cents = self.params["centroids"].astype(np.float64)
        scale = float(np.sqrt(np.mean(np.sum(cents * cents, axis=1))))
        ids2, counts = self.params["ids"], self.params["counts"]
        worst = 0.0
        for lst in range(self.nlist):
            cnt = int(counts[lst])
            if not cnt:
                continue
            members = np.stack([self._store[int(i)]
                                for i in ids2[lst, :cnt]])
            gap = float(np.linalg.norm(
                members.astype(np.float64).mean(axis=0) - cents[lst]))
            worst = max(worst, gap)
        return worst / (scale + 1e-12)

    # -- incremental updates -------------------------------------------------
    def updated(self, inserts=None, insert_ids=None,
                delete_ids=()) -> Tuple[str, "IVFIndex"]:
        """Apply inserts/deletes; returns ``(mode, new_index)`` with this
        index untouched (in-flight queries finish on the old lists).

        ``mode == "delta"``: only the touched posting-list rows changed —
        publish ``new_index.params`` through the delta codec.  ``mode ==
        "reanchor"``: a list overflowed its block or centroid drift
        crossed the threshold, and ``new_index`` is a fresh build over
        the surviving + inserted vectors (same ``block`` kept when the
        new occupancy still fits, so the re-anchor can publish as one
        same-shape FullUpdate)."""
        inserts = (np.zeros((0, self.dim), np.float32) if inserts is None
                   else np.asarray(inserts, np.float32).reshape(-1, self.dim))
        if insert_ids is None:
            nxt = (max(self._store) + 1) if self._store else 0
            insert_ids = np.arange(nxt, nxt + inserts.shape[0],
                                   dtype=np.int32)
        insert_ids = np.asarray(insert_ids, np.int32).reshape(-1)
        if insert_ids.shape[0] != inserts.shape[0]:
            raise ValueError("insert_ids must match inserts rows")
        for vid in insert_ids.tolist():
            if vid in self._store or vid < 0:
                raise ValueError(f"insert id {vid} already live (or "
                                 "negative)")

        params = {name: arr.copy() for name, arr in self.params.items()}
        store = dict(self._store)
        ids2, counts = params["ids"], params["counts"]
        slot = {int(ids2[lst, j]): (lst, j)
                for lst in range(self.nlist)
                for j in range(int(counts[lst]))}
        for did in delete_ids:
            did = int(did)
            if did not in slot:
                raise KeyError(f"delete id {did} is not in the index")
            lst, j = slot.pop(did)
            last = int(counts[lst]) - 1
            if j != last:
                moved = int(ids2[lst, last])
                ids2[lst, j] = moved
                slot[moved] = (lst, j)
                self._move_row(params, lst, last, j)
            ids2[lst, last] = -1
            self._clear_row(params, lst, last)
            counts[lst] = last
            del store[did]

        cents = params["centroids"]
        overflow = False
        for vec, vid in zip(inserts, insert_ids.tolist()):
            lst = int(_nearest_list(cents, vec[None])[0])
            j = int(counts[lst])
            if j >= self.block:
                overflow = True
                break
            ids2[lst, j] = vid
            self._write_row(params, lst, j, vec)
            counts[lst] = j + 1
            slot[vid] = (lst, j)
            store[vid] = vec.copy()

        if overflow:
            merged = dict(self._store)
            for did in delete_ids:
                merged.pop(int(did), None)
            merged.update({int(i): v.copy()
                           for i, v in zip(insert_ids.tolist(), inserts)})
            return "reanchor", self._rebuilt(merged)

        out = IVFIndex.__new__(IVFIndex)
        out.__dict__.update(self.__dict__)
        out.params = params
        out._store = store
        if (self.drift_threshold is not None
                and out.centroid_drift() > self.drift_threshold):
            return "reanchor", self._rebuilt(store)
        return "delta", out

    def rebound(self, params: Dict[str, Any]) -> "IVFIndex":
        """The publish-side clone: same plan schema, new param buffers —
        what ``model_with_params`` hands the rebind fast path.  Host
        bookkeeping stays with the producer's authoritative copy."""
        out = IVFIndex.__new__(IVFIndex)
        out.__dict__.update(self.__dict__)
        out.params = {name: np.asarray(arr) for name, arr in params.items()}
        return out

    def _rebuilt(self, store: Dict[int, np.ndarray]) -> "IVFIndex":
        order = sorted(store)
        vectors = np.stack([store[i] for i in order])
        counts = np.bincount(
            _nearest_list(self.params["centroids"], vectors),
            minlength=self.nlist)
        keep = (int(counts.max()) + self.list_slack <= self.block)

        def build(block):
            return IVFIndex.build(
                vectors, self.nlist, self.pq, k=self.k,
                nprobe=self.nprobe, ids=np.asarray(order, np.int32),
                seed=self.seed, list_slack=self.list_slack,
                drift_threshold=self.drift_threshold,
                max_iter=self.max_iter, block=block)

        if keep:
            # the occupancy estimate above used the OLD centroids; the
            # re-anchor refits them, so the same-shape attempt (one
            # FullUpdate publish instead of a redeploy) can still
            # overflow — fall through to a fresh block size then
            try:
                return build(self.block)
            except ValueError:
                pass
        return build(None)

    # row edits shared by insert/delete (vecs for flat, codes for PQ)
    def _move_row(self, params, lst, src, dst):
        base = lst * self.block
        for name in ("vecs", "codes"):
            if name in params:
                params[name][base + dst] = params[name][base + src]

    def _clear_row(self, params, lst, j):
        base = lst * self.block
        for name in ("vecs", "codes"):
            if name in params:
                params[name][base + j] = 0

    def _write_row(self, params, lst, j, vec):
        base = lst * self.block
        if "vecs" in params:
            params["vecs"][base + j] = vec
        else:
            resid = vec - params["centroids"][lst]
            params["codes"][base + j] = _encode_pq(
                resid[None], params["cb_q"], params["cb_s"])[0]


#: staging column names — the device outputs are chain-terminal staging
#: values; the host ``post`` maps them to the public columns (the
#: ``__chain_assign__`` idiom of the KMeans terminal)
_NN_STAGE = "__retrieve_nn__"
_DIST_STAGE = "__retrieve_dist__"


# ---------------------------------------------------------------------------
# host-side build helpers (deterministic numpy — never on the serve path)
# ---------------------------------------------------------------------------

def _round_up8(n: int) -> int:
    return -(-int(n) // 8) * 8


def _nearest_list(centroids: np.ndarray, vectors: np.ndarray) -> np.ndarray:
    """Nearest-centroid assignment (f32 expression, first-index ties) —
    the same pairwise form the kernels rank with."""
    c = np.asarray(centroids, np.float32)
    v = np.asarray(vectors, np.float32)
    scores = np.sum(c * c, axis=1)[None, :] - 2.0 * (v @ c.T)
    return np.argmin(scores, axis=1).astype(np.int32)


def _refine_balance(centroids: np.ndarray, vectors: np.ndarray,
                    rounds: Optional[int] = None) -> np.ndarray:
    """Split-heaviest / merge-lightest refinement of the coarse fit.

    The workset KMeans fit can leave a heavy tail — a few centroids
    covering many natural clusters — and the padded row-block layout
    charges every probe for the FULLEST list, so one fat list inflates
    the whole index's scan cost (``block`` is sized to ``max(counts)``,
    not the mean).  Each round takes the heaviest list, splits its
    members at the median of their projection onto the farthest
    member's direction (both halves always non-empty), and re-uses the
    lightest list's centroid slot for the second half; only the two
    touched lists' members are locally re-assigned between rounds — the
    caller's final global ``_nearest_list`` pass restores the
    nearest-centroid invariant.  Deterministic, pure numpy, stops when
    the heaviest list is within 2x of the mean occupancy."""
    c = np.array(centroids, np.float32, copy=True)
    n, nlist = vectors.shape[0], c.shape[0]
    if nlist < 2 or n == 0:
        return c
    assign = _nearest_list(c, vectors)
    counts = np.bincount(assign, minlength=nlist)
    cap = max(2.0 * n / nlist, 8.0)
    for _ in range(nlist if rounds is None else rounds):
        h = int(counts.argmax())
        lo = int(counts.argmin())
        if h == lo or counts[h] <= cap or counts[h] < 2:
            break
        rows = np.flatnonzero(assign == h)
        pts = vectors[rows]
        dvec = pts - c[h]
        far = dvec[int(np.argmax(np.einsum("nd,nd->n", dvec, dvec)))]
        proj = dvec @ far
        side = proj > np.median(proj)
        if not side.any() or side.all():
            break
        c[h] = pts[side].mean(axis=0)
        c[lo] = pts[~side].mean(axis=0)
        moved = np.concatenate([rows, np.flatnonzero(assign == lo)])
        assign[moved] = _nearest_list(c, vectors[moved])
        counts = np.bincount(assign, minlength=nlist)
    return c


def _pack_blocks(rows: np.ndarray, rows_of: List[np.ndarray], block: int,
                 width: int, dtype) -> np.ndarray:
    """Pack per-list member rows into the (nlist*block, width) row-block
    array; non-empty lists pad through the maskless exact-zero
    ``pad_rows_to_block`` contract (pad rows are masked inert by their
    ``-1`` ids, so zero filler is never corrected downstream)."""
    out = np.zeros((len(rows_of) * block, width), dtype)
    for lst, members in enumerate(rows_of):
        if not members.size:
            continue
        (padded,), _ = pad_rows_to_block((rows[members],), block)
        out[lst * block:(lst + 1) * block] = padded.astype(dtype)
    return out


def _fit_codebooks(resid: np.ndarray, pq: PQConfig, seed: int,
                   max_iter: int) -> Tuple[np.ndarray, np.ndarray]:
    """Per-subspace codebooks: workset-KMeans on each residual subspace,
    stored through the ``quantize_rows`` recipe (int8 codes + per-row
    f32 scales)."""
    from ..models.clustering.kmeans import KMeans

    dsub = resid.shape[1] // pq.m
    cb_q = np.empty((pq.m, pq.ksub, dsub), np.int8)
    cb_s = np.empty((pq.m, pq.ksub), np.float32)
    for s in range(pq.m):
        sub = np.ascontiguousarray(resid[:, s * dsub:(s + 1) * dsub])
        fit = (KMeans().set_k(pq.ksub).set_workset(True)
               .set_seed(seed + 1 + s).set_max_iter(pq.max_iter)
               .fit(Table({"features": sub})))
        book = np.asarray(fit.get_model_data()[0]["centroids"][0],
                          np.float32)
        cb_q[s], cb_s[s] = quantize_rows(book)
    return cb_q, cb_s


def _encode_pq(resid: np.ndarray, cb_q: np.ndarray,
               cb_s: np.ndarray) -> np.ndarray:
    """int8 PQ codes: per-subspace argmin against the DECODED codebook —
    the exact values the kernel's LUT scans with."""
    m, _ksub, dsub = cb_q.shape
    decoded = cb_q.astype(np.float32) * cb_s[..., None]
    codes = np.empty((resid.shape[0], m), np.int8)
    for s in range(m):
        sub = resid[:, s * dsub:(s + 1) * dsub]
        d2 = np.sum(
            (sub[:, None, :] - decoded[s][None, :, :]) ** 2, axis=-1)
        codes[:, s] = np.argmin(d2, axis=1).astype(np.int8)
    return codes


# ---------------------------------------------------------------------------
# registry entry.  The Pallas backend registers from
# ops/retrieve_pallas.py (kernels live in ops/, models and indexes look
# them up); the catalog imports both so any consumer's first lookup sees
# the full backend set.
# ---------------------------------------------------------------------------

def _register_retrieve_kernels() -> None:
    register_kernel("retrieve", "xla", _retrieve_stage_xla,
                    convention="stage")


_register_retrieve_kernels()
