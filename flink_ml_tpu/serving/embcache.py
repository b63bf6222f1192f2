"""Hot embedding-row cache — device-resident LRU row blocks (ISSUE 14).

WideDeep's stacked tables are the one serving operand that does NOT
amortize across tenants: a ``(total_vocab, emb_dim)`` table per tenant
at production vocab sizes exhausts HBM long before the chip runs out of
compute.  Zipfian traffic is the way out — most lookups hit a small hot
set — so :class:`EmbeddingRowCache` keeps only the HOT row blocks
device-resident and streams cold blocks in on demand:

- **Fixed device pools.**  One preallocated pool per table, shape
  ``(capacity_blocks, block_rows, *row_shape)``.  All device programs
  see CONSTANT shapes: a miss writes a block into a pool slot through
  one jitted ``dynamic_update_slice`` (compiled once per table), and a
  batch lookup is one jitted ``pool[slots, locals]`` gather (compiled
  once per request bucket) — zero steady-state retraces however the
  resident set churns.
- **LRU over blocks, not rows.**  The slot map (``block_id -> slot``)
  and recency order live on the host; eviction frees the least
  recently TOUCHED block's slot (touch = any lookup that read the
  block).  Rows inside a block ride together — the block is the
  device-transfer and residency granule, which is what makes the
  zipfian head cheap (hot ids cluster into few blocks).
- **Exactness.**  A cached gather returns bitwise the same rows as
  indexing the host table: blocks are exact ``device_put`` copies and
  the gather is pure indexing.  ``CachedWideDeepServable`` feeds the
  gathered rows through the SAME ``forward_from_rows`` expression the
  full-table forward uses, so served scores are bit-exact with
  ``model.transform`` (asserted in ``tests/test_scheduler.py``).

**Int8 row pools** (ISSUE 18): ``precision="int8"`` stores matrix-row
tables as int8 CODES plus one f32 per-row scale, quantized ONCE from the
host table at construction (publish-time calibration — ``rebind``'s
fresh cache re-calibrates each generation).  The codes pool plus the
scales pool cost ~(1 + 4/row_dim)/4 of the f32 pool at the same
``capacity_blocks`` — so at a FIXED device byte budget an int8 cache
holds more resident rows (the models-per-chip multiplier).  A lookup
gathers codes and scales and
dequantizes the gathered rows in-program (one exact cast + one f32
multiply; the f32 table never materializes); the oversized-batch bypass
dequantizes the SAME codes host-side, so cached and bypassed batches
return identical bits.  Scalar-row (1-d) tables — WideDeep's
``wide_cat`` — stay f32: codes + a per-row scale would cost more than
the f32 they replace.

**Single-consumer contract**: ``lookup`` mutates the slot map and the
pools without a lock — exactly one thread may call it (the scheduler's
serve loop / an endpoint's serve thread; warm-up of a NEW servable
sharing a cache with a concurrently-serving one is NOT supported — give
each generation its own cache, which ``rebind`` does automatically).
Hit/miss/eviction counters publish as gauges for the PR 13 metrics tree
(``snapshot()`` is a ``MetricsTree`` provider).
"""

from __future__ import annotations

import time

from collections import OrderedDict
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

from ..data.table import Table
from ..kernels.registry import tpu_only
from .executor import ServableModel

__all__ = ["EmbeddingRowCache", "CachedWideDeepServable"]


_POOL_SET: list = []
_POOL_GATHER: list = []
_POOL_GATHER_DEQ: list = []


def _pool_setter():
    """ONE jitted slot write per process: ``pool.at[slot].set(block)``
    with the slot as a runtime scalar — every miss of every cache hits
    the same compiled program (per pool shape).  Donated on TPU so the
    update is in-place in HBM; CPU ignores donation (skipped to avoid
    the spurious warning — the executor stance)."""
    if not _POOL_SET:
        donate = (0,) if tpu_only() else ()
        _POOL_SET.append(jax.jit(
            lambda pool, slot, block: pool.at[slot].set(block),
            donate_argnums=donate))
    return _POOL_SET[0]


def _pool_gather():
    if not _POOL_GATHER:
        _POOL_GATHER.append(jax.jit(
            lambda pool, slots, local: pool[slots, local]))
    return _POOL_GATHER[0]


def _pool_gather_deq():
    """The int8-pool gather: codes and per-row scales gather together
    and the GATHERED rows dequantize in the same program — the f32
    table (or block) never materializes on device."""
    if not _POOL_GATHER_DEQ:
        import jax.numpy as jnp

        _POOL_GATHER_DEQ.append(jax.jit(
            lambda pool, spool, slots, local:
            pool[slots, local].astype(jnp.float32)
            * spool[slots, local][..., None]))
    return _POOL_GATHER_DEQ[0]


class EmbeddingRowCache:
    """LRU of device-resident row blocks over host-resident tables
    (module doc).  ``tables`` maps name -> host array sharing one
    leading (vocab) dim — WideDeep passes ``{"wide_cat": (V,),
    "emb": (V, E)}``."""

    def __init__(self, tables: Dict[str, Any], *, block_rows: int = 512,
                 capacity_blocks: int = 64, precision: str = "f32"):
        if not tables:
            raise ValueError("tables must not be empty")
        if block_rows <= 0:
            raise ValueError("block_rows must be positive")
        if capacity_blocks <= 0:
            raise ValueError("capacity_blocks must be positive")
        if precision not in ("f32", "int8"):
            raise ValueError(f"unknown cache precision {precision!r}")
        self.precision = precision
        self._host = {name: np.asarray(t) for name, t in tables.items()}
        sizes = {name: t.shape[0] for name, t in self._host.items()}
        if len(set(sizes.values())) != 1:
            raise ValueError(
                f"tables must share one vocab dim, got {sizes}")
        self.vocab = next(iter(sizes.values()))
        if self.vocab == 0:
            raise ValueError("tables must carry at least one row")
        # int8: matrix-row tables become codes + per-row scales, ONCE,
        # from this generation's host table (publish-time calibration;
        # module doc).  Scalar-row tables stay f32.
        self._host_scales: Dict[str, np.ndarray] = {}
        if precision == "int8":
            from ..kernels.quantize import quantize_rows

            for name, t in self._host.items():
                if t.ndim >= 2:
                    codes, scales = quantize_rows(t)
                    self._host[name] = codes
                    self._host_scales[name] = scales
        self.block_rows = block_rows
        self.n_blocks = -(-self.vocab // block_rows)
        #: a cache bigger than the table is just the table — cap it so
        #: the accounting (resident fraction, pool bytes) stays honest
        self.capacity_blocks = min(capacity_blocks, self.n_blocks)
        self._pools = {
            name: jax.device_put(np.zeros(
                (self.capacity_blocks, block_rows) + t.shape[1:],
                t.dtype))
            for name, t in self._host.items()}
        self._scale_pools = {
            name: jax.device_put(np.zeros(
                (self.capacity_blocks, block_rows), np.float32))
            for name in self._host_scales}
        self._slot_of: Dict[int, int] = {}
        self._lru: "OrderedDict[int, int]" = OrderedDict()
        self._free = list(range(self.capacity_blocks - 1, -1, -1))
        self.hits = 0            # per-id lookups served from a resident block
        self.misses = 0          # per-id lookups that had to fault a block in
        self.block_faults = 0    # blocks transferred host -> device
        self.evictions = 0
        self.lookups = 0         # lookup() calls
        self.bypasses = 0        # batches served uncached (working set
        #                          bigger than the whole cache)
        self._fault_s = 0.0

    # -- core ----------------------------------------------------------------
    def _pad_block(self, table: np.ndarray, block: int) -> np.ndarray:
        lo = block * self.block_rows
        chunk = table[lo:lo + self.block_rows]
        if chunk.shape[0] == self.block_rows:
            return chunk
        pad = np.zeros((self.block_rows - chunk.shape[0],)
                       + table.shape[1:], table.dtype)
        return np.concatenate([chunk, pad], axis=0)

    def _host_block(self, name: str, block: int) -> np.ndarray:
        return self._pad_block(self._host[name], block)

    def _admit(self, block: int, pinned) -> int:
        """Fault one block in (single-consumer; see module doc).
        ``pinned`` blocks — the ones the CURRENT lookup touches — are
        exempt from eviction: they must all be resident simultaneously
        when the batch gather runs after the admit loop."""
        if self._free:
            slot = self._free.pop()
        else:
            for old_block in self._lru:
                if old_block not in pinned:
                    break
            else:  # unreachable: lookup() bypasses oversized batches
                raise RuntimeError("no evictable block")
            slot = self._lru.pop(old_block)
            del self._slot_of[old_block]
            self.evictions += 1
        t0 = time.perf_counter()
        setter = _pool_setter()
        slot_idx = np.int32(slot)
        for name in self._pools:
            self._pools[name] = setter(self._pools[name], slot_idx,
                                       self._host_block(name, block))
        for name in self._scale_pools:
            self._scale_pools[name] = setter(
                self._scale_pools[name], slot_idx,
                self._pad_block(self._host_scales[name], block))
        self._fault_s += time.perf_counter() - t0
        self.block_faults += 1
        self._slot_of[block] = slot
        self._lru[block] = slot
        return slot

    def lookup(self, ids: Any) -> Dict[str, jax.Array]:
        """Device rows for ``ids`` (any int shape), one entry per table:
        output shape is ``ids.shape + row_shape``.  Faults missing
        blocks in (LRU-evicting), touches resident ones."""
        ids = np.asarray(ids)
        if ids.size == 0:
            raise ValueError("lookup needs at least one id")
        if ids.min() < 0 or ids.max() >= self.vocab:
            raise ValueError(
                f"id out of range [0, {self.vocab}) — offset/validate "
                "ids before the cache (WideDeep's _validate_cat_ids)")
        self.lookups += 1
        blocks = ids // self.block_rows
        local = ids % self.block_rows
        unique, inverse, counts = np.unique(
            blocks, return_inverse=True, return_counts=True)
        if unique.shape[0] > self.capacity_blocks:
            # one batch's working set exceeds the whole cache: every
            # admit would evict a block THIS gather still needs.  Serve
            # the batch uncached (exact host gather — bitwise the same
            # rows), leave the resident set untouched, and account it:
            # a rising bypass counter says capacity_blocks is undersized
            # for the traffic, not that results degraded.
            self.bypasses += 1
            self.misses += int(ids.size)
            # int8 tables dequantize host-side from the SAME codes the
            # pools hold — one f32 cast + one f32 multiply, elementwise,
            # so bypassed batches are bitwise the cached batches
            return {
                name: jax.device_put(
                    table[ids].astype(np.float32)
                    * self._host_scales[name][ids][..., None]
                    if name in self._host_scales else table[ids])
                for name, table in self._host.items()}
        pinned = {int(b) for b in unique}
        slots = np.empty((unique.shape[0],), np.int32)
        for i, block in enumerate(unique):
            block = int(block)
            slot = self._slot_of.get(block)
            if slot is None:
                slot = self._admit(block, pinned)
                self.misses += int(counts[i])
            else:
                self._lru.move_to_end(block)
                self.hits += int(counts[i])
            slots[i] = slot
        slot_ids = slots[inverse].reshape(ids.shape)
        local = local.astype(np.int32)
        gather = _pool_gather()
        gather_deq = _pool_gather_deq() if self._scale_pools else None
        return {
            name: gather_deq(pool, self._scale_pools[name], slot_ids,
                             local)
            if name in self._scale_pools else gather(pool, slot_ids,
                                                     local)
            for name, pool in self._pools.items()}

    # -- observability -------------------------------------------------------
    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else float("nan")

    @property
    def resident_blocks(self) -> int:
        return len(self._lru)

    @property
    def pool_bytes(self) -> int:
        import itertools

        return sum(int(np.prod(p.shape)) * p.dtype.itemsize
                   for p in itertools.chain(self._pools.values(),
                                            self._scale_pools.values()))

    def snapshot(self) -> Dict[str, Any]:
        return {
            "hits": self.hits,
            "misses": self.misses,
            "hit_rate": round(self.hit_rate, 4)
            if self.hits + self.misses else None,
            "block_faults": self.block_faults,
            "evictions": self.evictions,
            "lookups": self.lookups,
            "bypasses": self.bypasses,
            "fault_ms": round(self._fault_s * 1e3, 3),
            "resident_blocks": self.resident_blocks,
            "capacity_blocks": self.capacity_blocks,
            "n_blocks": self.n_blocks,
            "block_rows": self.block_rows,
            "pool_bytes": self.pool_bytes,
            "precision": self.precision,
        }

    def publish(self, group) -> None:
        """Refresh gauges on ``group`` (the ``KernelStats.publish``
        idiom) — hit/miss/eviction visibility on the PR 13 metrics
        tree."""
        snap = self.snapshot()
        for name in ("hits", "misses", "block_faults", "evictions",
                     "lookups", "bypasses", "resident_blocks",
                     "capacity_blocks", "pool_bytes"):
            group.gauge(name).set(snap[name])
        group.gauge("hit_rate").set(
            snap["hit_rate"] if snap["hit_rate"] is not None
            else float("nan"))


# ---------------------------------------------------------------------------
# the WideDeep adopter
# ---------------------------------------------------------------------------

@jax.jit
def _cached_scores(rest, dense, wide_rows, emb_rows):
    """Expression-identical to the model's ``_jit_scores`` with the
    table gathers hoisted out: ``forward`` IS
    ``forward_from_rows(params, dense, wide_cat[ids], emb[ids])``, so
    feeding cache-gathered rows through the same function scores
    bit-exactly."""
    from ..models.recommendation.widedeep import forward_from_rows

    return jax.nn.sigmoid(forward_from_rows(rest, dense, wide_rows,
                                            emb_rows))


@jax.jit
def _cached_scores_int8(qrest, dense, wide_rows, emb_rows):
    """The int8 twin of ``_cached_scores``: the gathered rows arrive
    already dequantized (the cache pools' gather-then-dequantize), the
    dense-tower matrices dequantize here, and the expression after the
    rebuild is the SAME ``forward_from_rows`` — so a generation's
    scores are bit-stable call-to-call while tracking f32 within the
    parity matrix's accuracy envelope."""
    from ..kernels.quantize import dequantize_widedeep_rest
    from ..models.recommendation.widedeep import forward_from_rows

    return jax.nn.sigmoid(forward_from_rows(
        dequantize_widedeep_rest(qrest), dense, wide_rows, emb_rows))


class CachedWideDeepServable(ServableModel):
    """WideDeep serving through the embedding-row cache: only hot table
    blocks are device-resident; scores are bit-exact with
    ``model.transform`` (module doc).  ``rebind`` (delta publish) gets a
    FRESH cache over the new generation's tables — cached rows of the
    old generation must never serve the new one."""

    rebind_safe = True
    supported_precisions = ("f32", "int8")

    def __init__(self, model, example: Table, *,
                 cache_block_rows: int = 512,
                 cache_capacity_blocks: int = 64, **kwargs: Any):
        super().__init__(model, example, **kwargs)
        self._cache_block_rows = cache_block_rows
        self._cache_capacity_blocks = cache_capacity_blocks
        self._bind(model)

    def _bind(self, model) -> None:
        model._require_model()
        params = model._params
        self._vocab_sizes = model._vocab_sizes
        # int8 calibration capture point for the cached path: the cache
        # quantizes THIS generation's tables and the dense tower
        # quantizes here — rebind() re-binds the clone, so every delta
        # publish re-derives scales before the swap (stale scales never
        # serve)
        self.cache = EmbeddingRowCache(
            {"wide_cat": params["wide_cat"], "emb": params["emb"]},
            block_rows=self._cache_block_rows,
            capacity_blocks=self._cache_capacity_blocks,
            precision=self.precision)
        if self.precision == "int8":
            from ..kernels.quantize import quantize_widedeep_rest

            self._rest = jax.device_put(quantize_widedeep_rest(params))
            self._scores = _cached_scores_int8
        else:
            self._rest = jax.device_put({
                k: params[k] for k in ("wide_dense", "wide_b", "mlp")})
            self._scores = _cached_scores

    def rebind(self, model) -> "ServableModel":
        clone = super().rebind(model)
        clone._bind(model)
        return clone

    def _run(self, table: Table) -> Table:
        from ..models.recommendation.widedeep import _validate_cat_ids
        from ..utils.padding import pad_rows_to_bucket

        model = self.model
        dense = np.asarray(table[model.DENSE_FEATURES_COL], np.float32)
        cat = np.asarray(table[model.CAT_FEATURES_COL], np.int32)
        gids = _validate_cat_ids(cat, self._vocab_sizes)
        # pad ids are 0 = the first stacked slot, always a valid row
        # (the transform stance); pad rows slice away below
        (dense_p, gids_p), n = pad_rows_to_bucket(
            (dense, gids), min_bucket=self.min_bucket)
        rows = self.cache.lookup(gids_p)
        scores = np.asarray(
            self._scores(self._rest, dense_p, rows["wide_cat"],
                         rows["emb"]), np.float64)[:n]
        out = table.with_column(model.get_raw_prediction_col(), scores)
        return out.with_column(model.get_prediction_col(),
                               (scores > 0.5).astype(np.int64))
