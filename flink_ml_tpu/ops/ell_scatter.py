"""ELL-format scatter-add — the Pallas hot path behind the mixed-layout
linear trainers.

Problem: one SGD step on the Criteo-shaped mixed layout must apply
``w[cat[b,j]] += -lr * r[b]`` for ~1M random (slot -> weight) pairs per
batch.  XLA's scatter on TPU issues one random HBM read-modify-write per
slot (~6 ms per 850k slots measured on v5e — the whole step budget), and
a sort at runtime costs more than the scatter.  But the trainers replay
the SAME epoch tensor every epoch (``models/common/sgd.py`` builds it
once), so the slot->row routing is **static**: we pay one host/device
sort per fit and turn every training step's scatter into dense,
vectorized VMEM work.

Layout (built once per step by :func:`ell_layout`): flatten the
``(batch, nnz)`` categorical indices, sort by index, and bucket by
weight-table row ``idx >> 7`` (the table viewed as ``(d/128, 128)``
lanes).  Each table row gets up to 128 slots (``src`` = which batch row
each slot charges, ``lo`` = the lane it hits, sorted ascending within
the row); rows with more slots spill to a small overflow list (heavy
hitters — e.g. a label-marker feature — land there).

The step then computes, per row, the per-lane update total
``delta[row, l] = sum_s upd[row, s] * [lo[row, s] == l]`` with NO random
writes: because ``lo`` is sorted within the row, the lane totals are
differences of the running cumulative sum of ``upd`` picked at static
positions::

    C    = cumsum(upd, lanes)          # 7 shifted adds, exact f32
    G    = C[P] * M                    # one lane-local take_along_axis
    delta = G - shift(G, 1 lane)       # static boundary differences

where ``P[row, l]`` = position of the last slot with ``lo <= l`` (static,
precomputed; clamped to 0 and masked by ``M`` when no such slot).  All
three stages are lane-local vector ops Mosaic executes at VPU rate
(~0.3 ms per 1M slots on v5e vs ~6 ms for the XLA scatter).  The kernel
result is bit-identical to a sorted-order scatter; it differs from
XLA's scatter only in f32 summation order.

The reference has no analog (its updates ride keyed network shuffles,
``flink-ml-lib/.../clustering/kmeans/KMeans.java:172-196``); this is the
TPU-native replacement for that reduction machinery at the per-element
scale the Criteo config (BASELINE.md) demands.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, replace
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["EllLayout", "ell_layout", "ell_layout_device",
           "ell_scatter_apply", "supported", "ELL_WIDTH"]

ELL_WIDTH = 128          # slots per table row = one lane tile
#: Table rows per Mosaic grid step in the fused kernels.  8 measured
#: best in the r4 block sweep; the per-row one-hot transients are
#: block-size-independent, so this only trades grid overhead against
#: scheduling granularity.
_FUSED_BLOCK_ROWS = 8
_LANES = 128             # table view (d // 128, 128)


def supported(num_features: int) -> bool:
    """Kernel precondition: the weight table reshapes into at least 128
    whole 128-lane rows (``_pick_block_rows`` then always finds a valid
    power-of-two grid block, down to a single block of all rows)."""
    return num_features % _LANES == 0 and num_features // _LANES >= 128


def _pick_block_rows(rows: int) -> int:
    for br in (2048, 1024, 512, 256, 128):
        if rows % br == 0 and rows >= br:
            return br
    return rows


@dataclass
class EllLayout:
    """Static per-step routing for :func:`ell_scatter_apply`.

    All arrays are per-step stacks: leading dim = steps.

    Heavy hitters: an index occurring more than ``heavy_threshold`` times
    in a step (power-law categories — label markers, dominant tokens;
    real Criteo categorical frequencies are Zipfian) would flood a
    per-slot path, so ALL its slots leave the ELL grid for a dense count
    matrix: its update is ``-lr * (counts @ r)`` — one tiny matmul plus
    an H-element scatter instead of thousands of per-slot ops.
    """
    src: jnp.ndarray       # (steps, rows, 128) i32: batch row charged, or
                           #   ``batch`` (points at the zero pad of r_ext)
    pos: jnp.ndarray       # (steps, rows, 128) i32: clamped csum pick P
    mask: jnp.ndarray      # (steps, rows, 128) f32: 0 where P was empty
    ovf_idx: jnp.ndarray   # (steps, cap) i32: overflow weight indices (0 pad)
    ovf_src: jnp.ndarray   # (steps, cap) i32: overflow batch rows (batch pad)
    heavy_idx: jnp.ndarray  # (steps, H) i32: heavy indices (0 pad)
    heavy_cnt: jnp.ndarray  # (steps, H, batch): per-row counts (i16), or
                            #   per-row VALUE SUMS (f32) with `values`
                            #   (all-zero rows for padding entries)
    batch: int             # rows per batch (r vector length)
    num_features: int
    # generic (indices, values) sparse layout only (None for the mixed
    # implicit-1.0 layout):
    val: Optional[jnp.ndarray] = None      # (steps, rows, 128) f32
    ovf_val: Optional[jnp.ndarray] = None  # (steps, cap) f32
    # capacity bookkeeping: slots NEEDED per step, regardless of what
    # the static caps could hold.  Populated by every builder since r4
    # (the host builders additionally raise when a FORCED cap is
    # exceeded; the device builder only records, see assert_capacities)
    need_ovf: Optional[jnp.ndarray] = None    # (steps,) i32
    need_heavy: Optional[jnp.ndarray] = None  # (steps,) i32

    @property
    def steps(self) -> int:
        return self.src.shape[0]

    def assert_capacities(self) -> "EllLayout":
        """Fail loudly if the device builder dropped slots: any step whose
        required overflow/heavy slots exceed the static caps produced a
        silently-wrong layout (ADVICE r3).  One tiny device->host read."""
        if self.need_ovf is not None:
            cap = self.ovf_idx.shape[1]
            worst = int(jnp.max(self.need_ovf))
            if worst > cap:
                raise ValueError(
                    f"ELL overflow needs {worst} slots in some step > "
                    f"ovf_cap {cap}; gradients would silently drop slots "
                    "— raise ovf_cap")
        if self.need_heavy is not None:
            hcap = self.heavy_idx.shape[1]
            worst_h = int(jnp.max(self.need_heavy))
            if worst_h > hcap:
                raise ValueError(
                    f"ELL heavy path needs {worst_h} indices in some step "
                    f"> heavy_cap {hcap}; raise heavy_cap")
        return self

    def trim_overflow(self, margin: int = 2) -> "EllLayout":
        """Slice the overflow arrays down to the measured need (x
        ``margin``, rounded to 8).  The XLA overflow scatter's cost
        scales with the STATIC cap, not the real spill count — a
        generous 2^13 cap measured ~1.8 ms/step against a need of 180
        (r4 chip run, 2026-07-31) — and every builder front-compacts the
        real entries, so slicing is exact.  No-op when the cap is
        already tight or the need is unknown."""
        if self.need_ovf is None:
            return self
        cap = max(8, int(np.asarray(self.need_ovf).max()) * margin)
        cap += (-cap) % 8
        if cap >= self.ovf_idx.shape[1]:
            return self
        return replace(
            self, ovf_idx=self.ovf_idx[:, :cap],
            ovf_src=self.ovf_src[:, :cap],
            ovf_val=None if self.ovf_val is None
            else self.ovf_val[:, :cap])


HEAVY_THRESHOLD = 512   # slots per index per step before the dense path


def _check_heavy_threshold(heavy_threshold: int) -> None:
    """A threshold below ELL_WIDTH would let a heavy run inflate the raw
    ``pos`` of kept same-row slots past their rank among kept slots, so
    their cumsum picks would read the zero pad — silently dropped
    updates.  With threshold >= ELL_WIDTH every slot after a heavy run
    has pos > 127 and routes to overflow, which is exact."""
    if heavy_threshold < ELL_WIDTH:
        raise ValueError(
            f"heavy_threshold must be >= ELL_WIDTH ({ELL_WIDTH}); "
            f"got {heavy_threshold}")


def _ell_one_step(flat: np.ndarray, batch: int, nnz: int, rows: int,
                  heavy_threshold: int,
                  values: "Optional[np.ndarray]" = None
                  ) -> Tuple[np.ndarray, ...]:
    """Host layout for one step's flattened indices (batch*nnz,).  With
    ``values`` (same flat shape), each slot carries a coefficient: the
    layout also emits the value arrays and the heavy matrix holds VALUE
    SUMS instead of counts (the (indices, values) sparse layout)."""
    b_of = np.repeat(np.arange(batch, dtype=np.int32), nnz)
    # sentinel indices (>= num_features, e.g. padding rows marked by the
    # streaming trainer) drop out of the layout entirely — a zero-pad
    # would fabricate an artificially heavy index 0
    in_range = flat < rows * _LANES
    if not in_range.all():
        flat = flat[in_range]
        b_of = b_of[in_range]
        if values is not None:
            values = values[in_range]
    order = np.argsort(flat, kind="stable")
    sidx = flat[order]
    ssrc = b_of[order]
    svals = values[order] if values is not None else None
    row = sidx >> 7
    lo = (sidx & 127).astype(np.int32)
    starts = np.searchsorted(row, np.arange(rows, dtype=np.int64))
    pos = np.arange(flat.size, dtype=np.int64) - starts[row]
    # heavy indices: the whole run leaves the per-slot paths (positions of
    # later same-row slots keep counting past them — a heavy row's other
    # slots simply overflow, a negligible cost next to the run itself)
    run_start = np.searchsorted(sidx, sidx, side="left")
    run_end = np.searchsorted(sidx, sidx, side="right")
    heavy_slot = (run_end - run_start) > heavy_threshold
    keep = (pos < ELL_WIDTH) & ~heavy_slot

    src = np.full((rows, ELL_WIDTH), batch, np.int32)
    src[row[keep], pos[keep]] = ssrc[keep]
    val = None
    if svals is not None:
        val = np.zeros((rows, ELL_WIDTH), np.float32)
        val[row[keep], pos[keep]] = svals[keep]
    hist = np.zeros((rows, 128), np.int64)
    np.add.at(hist, (row[keep], lo[keep]), 1)
    P = np.cumsum(hist, axis=1) - 1
    mask = (P >= 0).astype(np.float32)
    Pc = np.maximum(P, 0).astype(np.int32)

    spill = ~keep & ~heavy_slot
    ovf_idx = sidx[spill].astype(np.int32)
    ovf_src = ssrc[spill]
    ovf_val = svals[spill].astype(np.float32) if svals is not None else None

    h_idx = np.unique(sidx[heavy_slot]).astype(np.int32)
    h_cnt = np.zeros((h_idx.size, batch),
                     np.int16 if svals is None else np.float32)
    if h_idx.size:
        h_rank = np.searchsorted(h_idx, sidx[heavy_slot])
        np.add.at(h_cnt, (h_rank, ssrc[heavy_slot]),
                  1 if svals is None else svals[heavy_slot])
    return src, Pc, mask, ovf_idx, ovf_src, h_idx, h_cnt, val, ovf_val


_ELL_NATIVE = None
_ELL_NATIVE_TRIED = False


def _native_ell():
    """The C++ builder (native/ell_layout.cpp) or None (numpy fallback).
    ~1.2 us/slot numpy vs ~0.06 us/slot native — the layout build is the
    host hot path of fit() (32 s -> ~1.5 s at the default product shape)."""
    global _ELL_NATIVE, _ELL_NATIVE_TRIED
    if not _ELL_NATIVE_TRIED:
        _ELL_NATIVE_TRIED = True
        from ..utils.native_lib import load_native_lib

        _ELL_NATIVE = load_native_lib("ell_layout")
    return _ELL_NATIVE


def _ell_layout_native(lib, cat_indices: np.ndarray, num_features: int,
                       heavy_threshold: int,
                       values: "Optional[np.ndarray]",
                       pad_ovf_cap: Optional[int],
                       pad_heavy_cap: Optional[int]):
    """Native counting-sort build; semantics identical to the numpy path
    (heavy f32 value-sums may differ in summation order only)."""
    import ctypes

    steps, batch, nnz = cat_indices.shape
    rows = num_features // _LANES
    flat = np.ascontiguousarray(cat_indices, np.int32)
    with_values = values is not None
    vals = (np.ascontiguousarray(values, np.float32) if with_values
            else None)

    src = np.empty((steps, rows, ELL_WIDTH), np.int32)
    pos = np.empty((steps, rows, ELL_WIDTH), np.int32)
    mask = np.empty((steps, rows, ELL_WIDTH), np.float32)
    val = (np.empty((steps, rows, ELL_WIDTH), np.float32) if with_values
           else None)
    need_o = np.zeros((steps,), np.int32)
    need_h = np.zeros((steps,), np.int32)

    def run(ovf_cap: int, heavy_cap: int):
        ovf_idx = np.empty((steps, ovf_cap), np.int32)
        ovf_src = np.empty((steps, ovf_cap), np.int32)
        ovf_val = (np.empty((steps, ovf_cap), np.float32) if with_values
                   else None)
        heavy_idx = np.empty((steps, heavy_cap), np.int32)
        heavy_cnt = np.empty((steps, heavy_cap, batch),
                             np.float32 if with_values else np.int16)

        def ptr(a, typ):
            return (a.ctypes.data_as(ctypes.POINTER(typ))
                    if a is not None else None)

        rc = lib.ell_build(
            ptr(flat, ctypes.c_int32), ptr(vals, ctypes.c_float),
            ctypes.c_int64(steps), ctypes.c_int64(batch),
            ctypes.c_int64(nnz), ctypes.c_int64(rows),
            ctypes.c_int64(heavy_threshold),
            ctypes.c_int64(ovf_cap), ctypes.c_int64(heavy_cap),
            ptr(src, ctypes.c_int32), ptr(pos, ctypes.c_int32),
            ptr(mask, ctypes.c_float), ptr(val, ctypes.c_float),
            ptr(ovf_idx, ctypes.c_int32), ptr(ovf_src, ctypes.c_int32),
            ptr(ovf_val, ctypes.c_float), ptr(heavy_idx, ctypes.c_int32),
            heavy_cnt.ctypes.data_as(ctypes.c_void_p),
            ptr(need_o, ctypes.c_int32), ptr(need_h, ctypes.c_int32))
        return rc, ovf_idx, ovf_src, ovf_val, heavy_idx, heavy_cnt

    # first call: forced caps verbatim, else a generous guess; a capacity
    # miss reports exact needs and one retry lands it
    cap0 = pad_ovf_cap if pad_ovf_cap is not None else max(1024, batch)
    cap0 += (-cap0) % 8
    h0 = pad_heavy_cap if pad_heavy_cap is not None else 16
    rc, ovf_idx, ovf_src, ovf_val, heavy_idx, heavy_cnt = run(cap0, h0)
    need_ovf, need_heavy = int(need_o.max()), int(need_h.max())
    # forced-cap contract: compare against the UNROUNDED caps regardless
    # of rc — rounding cap0 up to a multiple of 8 must never absorb a
    # need the caller's exact cap would have rejected
    if pad_ovf_cap is not None and need_ovf > pad_ovf_cap:
        raise ValueError(
            f"overflow needs {need_ovf} slots > forced cap "
            f"{pad_ovf_cap}; raise the cap (streaming: ell_ovf_cap)")
    if pad_heavy_cap is not None and need_heavy > pad_heavy_cap:
        raise ValueError(
            f"{need_heavy} heavy indices > forced cap "
            f"{pad_heavy_cap}; raise the cap (streaming: "
            "ell_heavy_cap)")
    if rc:
        cap0 = max(cap0, need_ovf + (-need_ovf) % 8)
        h0 = max(h0, need_heavy)
        rc, ovf_idx, ovf_src, ovf_val, heavy_idx, heavy_cnt = run(cap0, h0)
        assert rc == 0, "native ell_build retry with exact caps failed"

    # shrink to the numpy builder's exact cap arithmetic
    cap = pad_ovf_cap if pad_ovf_cap is not None else max(8, need_ovf)
    cap += (-cap) % 8
    H = pad_heavy_cap if pad_heavy_cap is not None else max(1, need_heavy)
    return (src, pos, mask,
            np.ascontiguousarray(ovf_idx[:, :cap]),
            np.ascontiguousarray(ovf_src[:, :cap]),
            None if not with_values
            else np.ascontiguousarray(ovf_val[:, :cap]),
            np.ascontiguousarray(heavy_idx[:, :H]),
            np.ascontiguousarray(heavy_cnt[:, :H]),
            val, need_o.copy(), need_h.copy())


def ell_layout(cat_indices: np.ndarray, num_features: int,
               heavy_threshold: int = HEAVY_THRESHOLD,
               values: "Optional[np.ndarray]" = None,
               pad_ovf_cap: Optional[int] = None,
               pad_heavy_cap: Optional[int] = None,
               device: bool = True) -> EllLayout:
    """Build the static routing from a ``(steps, batch, nnz)`` int epoch
    tensor of categorical indices (host numpy; one-time per fit).  Pass
    ``values`` (same shape, float) for the generic sparse layout —
    slots then scatter ``value * r`` instead of ``r``.

    ``pad_ovf_cap`` / ``pad_heavy_cap`` force EXACT capacities (for
    streaming callers whose every batch must share one compiled shape);
    a batch exceeding a forced cap raises rather than dropping slots.
    ``device=False`` keeps every array host numpy (streaming callers
    hand the layout to a prefetch pipeline that does the one
    device_put; a device round-trip per batch would defeat the
    overlap).  Indices >= num_features are sentinels and drop out of
    the layout (padding rows)."""
    _check_heavy_threshold(heavy_threshold)
    steps, batch, nnz = cat_indices.shape
    rows = num_features // _LANES
    wrap = jnp.asarray if device else np.asarray
    lib = _native_ell()
    if lib is not None:
        (n_src, n_pos, n_mask, n_oi, n_os, n_ov, n_hi, n_hc, n_val,
         need_o, need_h) = _ell_layout_native(
            lib, np.asarray(cat_indices), num_features, heavy_threshold,
            values, pad_ovf_cap, pad_heavy_cap)
        return EllLayout(
            src=wrap(n_src), pos=wrap(n_pos), mask=wrap(n_mask),
            ovf_idx=wrap(n_oi), ovf_src=wrap(n_os),
            heavy_idx=wrap(n_hi), heavy_cnt=wrap(n_hc),
            val=None if n_val is None else wrap(n_val),
            ovf_val=None if n_ov is None else wrap(n_ov),
            batch=batch, num_features=num_features,
            need_ovf=need_o, need_heavy=need_h)
    outs = [_ell_one_step(
        np.asarray(cat_indices[s], np.int64).reshape(-1), batch, nnz, rows,
        heavy_threshold,
        None if values is None
        else np.asarray(values[s], np.float32).reshape(-1))
        for s in range(steps)]
    need_ovf = max(o[3].size for o in outs)
    need_heavy = max(o[5].size for o in outs)
    if pad_ovf_cap is not None and need_ovf > pad_ovf_cap:
        raise ValueError(
            f"overflow needs {need_ovf} slots > forced cap {pad_ovf_cap}; "
            "raise the cap (streaming: ell_ovf_cap)")
    if pad_heavy_cap is not None and need_heavy > pad_heavy_cap:
        raise ValueError(
            f"{need_heavy} heavy indices > forced cap {pad_heavy_cap}; "
            "raise the cap (streaming: ell_heavy_cap)")
    cap = pad_ovf_cap if pad_ovf_cap is not None else max(8, need_ovf)
    cap += (-cap) % 8
    ovf_idx = np.zeros((steps, cap), np.int32)
    ovf_src = np.full((steps, cap), batch, np.int32)
    H = (pad_heavy_cap if pad_heavy_cap is not None
         else max(1, need_heavy))
    heavy_idx = np.zeros((steps, H), np.int32)
    heavy_cnt = np.zeros((steps, H, batch),
                         np.int16 if values is None else np.float32)
    val = ovf_val = None
    if values is not None:
        val = np.zeros((steps, rows, ELL_WIDTH), np.float32)
        ovf_val = np.zeros((steps, cap), np.float32)
    for s, o in enumerate(outs):
        ovf_idx[s, :o[3].size] = o[3]
        ovf_src[s, :o[4].size] = o[4]
        heavy_idx[s, :o[5].size] = o[5]
        heavy_cnt[s, :o[6].shape[0]] = o[6]
        if values is not None:
            val[s] = o[7]
            ovf_val[s, :o[8].size] = o[8]
    return EllLayout(
        src=wrap(np.stack([o[0] for o in outs])),
        pos=wrap(np.stack([o[1] for o in outs])),
        mask=wrap(np.stack([o[2] for o in outs])),
        ovf_idx=wrap(ovf_idx), ovf_src=wrap(ovf_src),
        heavy_idx=wrap(heavy_idx), heavy_cnt=wrap(heavy_cnt),
        val=None if val is None else wrap(val),
        ovf_val=None if ovf_val is None else wrap(ovf_val),
        batch=batch, num_features=num_features,
        need_ovf=np.asarray([o[3].size for o in outs], np.int32),
        need_heavy=np.asarray([o[5].size for o in outs], np.int32))


def ell_layout_device(cat_indices: jnp.ndarray, num_features: int,
                      ovf_cap: int = 1 << 16, heavy_cap: int = 8,
                      heavy_threshold: int = HEAVY_THRESHOLD,
                      values: Optional[jnp.ndarray] = None) -> EllLayout:
    """Device-side layout builder (jit, vmapped over steps) for callers
    whose epoch tensor already lives in HBM (e.g. the benchmark, which
    generates its data on device and never fetches it).  Overflow
    and heavy capacities are static; slots beyond them are DROPPED from
    the layout, so callers must either size ``ovf_cap``/``heavy_cap``
    generously or call :meth:`EllLayout.assert_capacities` on the result
    (the returned ``need_ovf``/``need_heavy`` record what each step
    actually required)."""
    _check_heavy_threshold(heavy_threshold)
    steps, batch, nnz = cat_indices.shape
    rows = num_features // _LANES
    b_of = jnp.repeat(jnp.arange(batch, dtype=jnp.int32), nnz)

    with_values = values is not None

    @functools.partial(jax.jit, static_argnums=())
    @jax.vmap
    def build(flat, fvals):
        order = jnp.argsort(flat)
        sidx = flat[order]
        ssrc = b_of[order]
        # implicit-1.0 callers skip all value plumbing at trace time
        svals = fvals[order] if with_values else None
        row = sidx >> 7
        lo = (sidx & 127).astype(jnp.int32)
        starts = jnp.searchsorted(row, jnp.arange(rows, dtype=sidx.dtype))
        pos = jnp.arange(flat.size, dtype=jnp.int32) - starts[row]
        run_start = jnp.searchsorted(sidx, sidx, side="left")
        run_end = jnp.searchsorted(sidx, sidx, side="right")
        heavy_slot = (run_end - run_start) > heavy_threshold
        keep = (pos < ELL_WIDTH) & ~heavy_slot
        src = jnp.full((rows, ELL_WIDTH), batch, jnp.int32)
        # overflow slots target column ELL_WIDTH, which mode="drop"
        # discards (an in-bounds dummy would race the real slot there)
        src = src.at[row, jnp.where(keep, pos, ELL_WIDTH)].set(
            ssrc, mode="drop")
        val = (jnp.zeros((rows, ELL_WIDTH), jnp.float32).at[
            row, jnp.where(keep, pos, ELL_WIDTH)].set(svals, mode="drop")
            if with_values else jnp.zeros((1, 1), jnp.float32))
        hist = jnp.zeros((rows, 128), jnp.int32).at[row, lo].add(
            keep.astype(jnp.int32), mode="drop")
        P = jnp.cumsum(hist, axis=1) - 1
        mask = (P >= 0).astype(jnp.float32)
        Pc = jnp.maximum(P, 0).astype(jnp.int32)
        spill = ~keep & ~heavy_slot
        ovf_slot = jnp.cumsum(spill.astype(jnp.int32)) - 1
        ovf_i = jnp.zeros((ovf_cap,), jnp.int32).at[
            jnp.where(spill, ovf_slot, ovf_cap)].set(
            jnp.where(spill, sidx.astype(jnp.int32), 0), mode="drop")
        ovf_s = jnp.full((ovf_cap,), batch, jnp.int32).at[
            jnp.where(spill, ovf_slot, ovf_cap)].set(
            jnp.where(spill, ssrc, batch), mode="drop")
        ovf_v = (jnp.zeros((ovf_cap,), jnp.float32).at[
            jnp.where(spill, ovf_slot, ovf_cap)].set(
            jnp.where(spill, svals, 0.0), mode="drop")
            if with_values else jnp.zeros((1,), jnp.float32))
        # heavy runs: rank = number of heavy runs starting at or before
        # this slot - 1 (first-occurrence compaction)
        is_first = jnp.arange(flat.size, dtype=jnp.int32) == run_start
        h_rank = jnp.cumsum((is_first & heavy_slot).astype(jnp.int32)) - 1
        h_i = jnp.zeros((heavy_cap,), jnp.int32).at[
            jnp.where(is_first & heavy_slot, h_rank, heavy_cap)].set(
            jnp.where(heavy_slot, sidx.astype(jnp.int32), 0), mode="drop")
        if with_values:
            h_c = jnp.zeros((heavy_cap, batch), jnp.float32).at[
                jnp.where(heavy_slot, h_rank, heavy_cap), ssrc].add(
                svals, mode="drop")
        else:
            h_c = jnp.zeros((heavy_cap, batch), jnp.int16).at[
                jnp.where(heavy_slot, h_rank, heavy_cap), ssrc].add(
                1, mode="drop")
        n_ovf = jnp.sum(spill.astype(jnp.int32))
        n_heavy = jnp.sum((is_first & heavy_slot).astype(jnp.int32))
        return src, Pc, mask, ovf_i, ovf_s, h_i, h_c, val, ovf_v, \
            n_ovf, n_heavy

    flat_steps = cat_indices.reshape(steps, -1).astype(jnp.int32)
    fvals = (values.reshape(steps, -1).astype(jnp.float32) if with_values
             else jnp.zeros((steps, 1), jnp.float32))  # unused placeholder
    src, Pc, mask, ovf_i, ovf_s, h_i, h_c, val, ovf_v, n_ovf, n_heavy = \
        build(flat_steps, fvals)
    return EllLayout(src=src, pos=Pc, mask=mask, ovf_idx=ovf_i,
                     ovf_src=ovf_s, heavy_idx=h_i, heavy_cnt=h_c,
                     val=val if with_values else None,
                     ovf_val=ovf_v if with_values else None,
                     batch=batch, num_features=num_features,
                     need_ovf=n_ovf, need_heavy=n_heavy)


def _csum_pick_tail(x, p, m, w, block_rows: int):
    """THE scatter tail shared by both Mosaic kernels: exact inclusive
    cumsum along lanes (7 shifted adds — fixed f32 order, deterministic,
    no MXU rounding), static-position pick, boundary difference."""
    for k in (1, 2, 4, 8, 16, 32, 64):
        x = x + jnp.concatenate(
            [jnp.zeros((block_rows, k), jnp.float32), x[:, :-k]],
            axis=1)
    G = jnp.take_along_axis(x, p, axis=1) * m
    Gs = jnp.concatenate(
        [jnp.zeros((block_rows, 1), jnp.float32), G[:, :-1]], axis=1)
    return w + G - Gs


def _kernel(block_rows: int):
    def kern(u_ref, p_ref, m_ref, w_ref, out_ref):
        out_ref[:] = _csum_pick_tail(u_ref[:], p_ref[:], m_ref[:],
                                     w_ref[:], block_rows)
    return kern


@functools.partial(jax.jit, static_argnames=("interpret",))
def ell_scatter_apply(w: jnp.ndarray, upd: jnp.ndarray, pos: jnp.ndarray,
                      mask: jnp.ndarray, *, interpret: bool = False
                      ) -> jnp.ndarray:
    """``w + scatter(upd)`` where ``upd (rows, 128)`` holds per-slot update
    values in ELL order and ``pos``/``mask`` are the static csum picks from
    :func:`ell_layout`.  ``w`` is flat ``(rows*128,)``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows = upd.shape[0]
    br = _pick_block_rows(rows)
    w2 = w.reshape(rows, _LANES)
    out = pl.pallas_call(
        _kernel(br), grid=(rows // br,),
        in_specs=[pl.BlockSpec((br, 128), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)] * 4,
        out_specs=pl.BlockSpec((br, 128), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.float32),
        interpret=interpret,
    )(upd, pos, mask, w2)
    return out.reshape(-1)


def _fused_kernel(block_rows: int, r_rows: int, precision,
                  with_val: bool):
    """Compute the u-gather ``u = -lr * r_ext[src]`` INSIDE the kernel
    via a one-hot MXU matmul + lane-local pick, then run the csum/pick/
    diff scatter.  Rationale: the XLA blocked gather is DMA-transaction-
    bound (~1.7-2.5 ns/slot = ~2-2.5 ms/step at 1M slots — confirmed the
    dominant step cost by the r4 ablation: dropping it moved the full
    step 7.79 -> 2.17 ms), while r_ext is tiny (fits VMEM): per 128-slot
    row the one-hot contraction against the (r_rows, 128) view of r_ext
    costs ~33 kMAC/slot — MXU work instead of the transaction stall
    (measured: full step 6.53 ms fused vs 8.92 XLA-oracle, r4 ablation).
    ``with_val`` multiplies each slot by a per-slot value (the generic
    sparse layout's explicit feature values)."""
    def kern(src_ref, p_ref, m_ref, r2dt_ref, w_ref, *rest):
        (val_ref, out_ref) = rest if with_val else (None, rest[0])
        src = src_ref[:]                       # (block_rows, 128) i32
        r2dt = r2dt_ref[:]                     # (128, r_rows) f32: the
        hi = src // 128                        #   PRE-SCALED -lr*r_ext,
        lo = src % 128                         #   lane-major
        # everything below is built in its CONSUMED orientation — no
        # transposes or (128, 1) concats anywhere (per-iteration Mosaic
        # relayouts measured ~10x the contraction's MXU floor, r4 chip
        # run)
        lane0 = jax.lax.broadcasted_iota(jnp.int32, (128, 128), 0)
        rows_out = []
        for r in range(block_rows):
            # OHT[j, s] = [hi[r, s] == j] over the r_ext rows
            oht = (jax.lax.broadcasted_iota(jnp.int32, (r_rows, 128), 0)
                   == hi[r][None, :]).astype(jnp.float32)
            # G1T[l, s] = r_ext2d[l, hi[r, s]]
            g1t = jnp.dot(r2dt, oht, preferred_element_type=jnp.float32,
                          precision=precision)
            # pick each slot's lane via masked column-sum (Mosaic's
            # gather lowering rejects (128, 1)-index take_along_axis)
            pick = jnp.where(lane0 == lo[r][None, :], g1t, 0.0)
            rows_out.append(jnp.sum(pick, axis=0, keepdims=True))
        u = jnp.concatenate(rows_out, axis=0)  # (block_rows, 128)
        if with_val:
            u = u * val_ref[:]
        out_ref[:] = _csum_pick_tail(u, p_ref[:], m_ref[:], w_ref[:],
                                     block_rows)
    return kern


@functools.partial(jax.jit, static_argnames=("interpret", "precision"))
def ell_scatter_apply_fused(w: jnp.ndarray, r_ext: jnp.ndarray,
                            src: jnp.ndarray, pos: jnp.ndarray,
                            mask: jnp.ndarray, *, lr,
                            val: Optional[jnp.ndarray] = None,
                            precision: str = "default",
                            interpret: bool = False) -> jnp.ndarray:
    """``w + scatter(-lr * val * r_ext[src])`` with the gather fused into
    the Mosaic kernel (see :func:`_fused_kernel`).  ``r_ext`` length must
    be a multiple of 128 (:func:`sgd._extended_r` pads to 256) and the
    table must have a multiple of 8 rows (every ``supported()`` power-of
    -two size does).  ``lr`` is traced — it scales ``r_ext`` OUTSIDE the
    kernel, so learning-rate sweeps share one compiled executable.
    Small block (8 rows) keeps the per-block one-hot tile in VMEM.
    ``val`` is an optional per-slot ``(rows, 128)`` multiplier (the
    explicit feature values of the generic sparse layout); None means
    the mixed layout's implicit 1.0.

    ``precision`` sets the one-hot contraction's MXU mode: ``"default"``
    (single bf16 pass — gathered values carry ~2^-8 relative truncation,
    harmless gradient noise for SGD) or ``"highest"`` (multi-pass f32 —
    exact parity with the XLA gather, ~3x the contraction's MXU cost)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows = src.shape[0]
    if r_ext.shape[0] % 128:
        raise ValueError(
            f"fused kernel needs len(r_ext) % 128 == 0, got "
            f"{r_ext.shape[0]}; pad with sgd._extended_r")
    r_rows = r_ext.shape[0] // 128
    br = _FUSED_BLOCK_ROWS
    if rows % br:
        raise ValueError(
            f"fused kernel needs rows % {br} == 0, got {rows}; use "
            "ell_scatter_apply")
    # lane-major view of the scaled residuals, transposed ONCE here so
    # the kernel's per-row contraction consumes it without relayout
    r2dt = ((-lr) * r_ext).reshape(r_rows, 128).T
    w2 = w.reshape(rows, _LANES)
    block = pl.BlockSpec((br, 128), lambda i: (i, 0),
                         memory_space=pltpu.VMEM)
    operands = [src, pos, mask, r2dt, w2]
    in_specs = [block, block, block,
                pl.BlockSpec((128, r_rows), lambda i: (0, 0),
                             memory_space=pltpu.VMEM),
                block]
    if val is not None:
        operands.append(val)
        in_specs.append(block)
    out = pl.pallas_call(
        _fused_kernel(br, r_rows, precision, val is not None),
        grid=(rows // br,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((br, 128), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.float32),
        interpret=interpret,
    )(*operands)
    return out.reshape(-1)


def ell_scatter_apply_xla(w: jnp.ndarray, upd: jnp.ndarray,
                          pos: jnp.ndarray, mask: jnp.ndarray
                          ) -> jnp.ndarray:
    """Pure-XLA twin of :func:`ell_scatter_apply` (same csum/pick math) for
    backends without Mosaic.  Used by CPU tests and as the correctness
    oracle."""
    rows = upd.shape[0]
    x = jnp.cumsum(upd, axis=1)
    G = jnp.take_along_axis(x, pos, axis=1) * mask
    Gs = jnp.concatenate(
        [jnp.zeros((rows, 1), jnp.float32), G[:, :-1]], axis=1)
    return (w.reshape(rows, _LANES) + G - Gs).reshape(-1)


# ---------------------------------------------------------------------------
# Forward (margin) path over the SAME layout: the ``w[cat]`` forward
# gather is the other transaction-bound half of the mixed step.  Every
# slot's table
# position is already encoded in pos/mask (slots sorted by lane within a
# row; ``pos[l]`` = last slot with lane <= l, mask = lane non-empty), so
# the margin contribution of the in-grid slots is computable with zero
# extra layout state: recover each slot's own lane as
# ``lane(s) = #{l : pos_eff[l] < s}`` (pos_eff = pos restored to -1 on
# masked lanes), pick ``w`` at that lane (a full-shape lane-local
# take_along_axis — the Mosaic-supported gather form), and accumulate
# per-sample sums with two one-hot MXU contractions into an extended
# margin table (pad slots carry ``src == batch`` and land in the
# discarded pad region, exactly like the backward path's r_ext pad).
# ---------------------------------------------------------------------------

def _slot_lanes_xla(pos: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Per-slot lane recovery, XLA form: vmapped searchsorted over rows.
    ``pos_eff`` is nondecreasing per row, so ``#{l : pos_eff[l] < s}`` is
    a left-insertion point.  Clamped to 127: pad slots (beyond every
    boundary) pick an arbitrary real lane and are discarded via their
    ``src == batch`` routing."""
    pos_eff = pos + mask.astype(jnp.int32) - 1
    s_iota = jnp.arange(ELL_WIDTH, dtype=jnp.int32)
    lanes = jax.vmap(
        lambda p: jnp.searchsorted(p, s_iota, side="left"))(pos_eff)
    return jnp.minimum(lanes, ELL_WIDTH - 1).astype(jnp.int32)


def ell_margin_xla(w: jnp.ndarray, src: jnp.ndarray, pos: jnp.ndarray,
                   mask: jnp.ndarray, m_len: int,
                   val: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """In-grid margin contributions, scattered to an ``(m_len,)`` extended
    per-sample table (``m_len`` = the :func:`sgd._extended_r` length;
    callers slice ``[:batch]``).  Pure-XLA twin of
    :func:`ell_margin_fused` for CPU backends and as the oracle."""
    lanes = _slot_lanes_xla(pos, mask)
    g = jnp.take_along_axis(w.reshape(-1, _LANES), lanes, axis=1)
    if val is not None:
        g = g * val
    return jnp.zeros((m_len,), jnp.float32).at[src.reshape(-1)].add(
        g.reshape(-1), mode="drop")


def _margin_kernel(block_rows: int, m_rows: int, precision,
                   with_val: bool):
    """Mosaic margin kernel: per block of ``block_rows`` table rows,
    recover slot lanes from pos/mask (VPU compare + row-sum), pick the
    block's weights at those lanes (full-shape lane-local gather), and
    accumulate ``margin_ext[m, l] += sum_s [src==m*128+l] * g[s]`` via a
    per-row one-hot MXU contraction into the grid-shared accumulator."""
    from jax.experimental import pallas as pl

    def kern(src_ref, p_ref, m_ref, w_ref, *rest):
        (val_ref, out_ref) = rest if with_val else (None, rest[0])
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            out_ref[:] = jnp.zeros_like(out_ref)

        src = src_ref[:]                        # (block_rows, 128) i32
        p_eff = p_ref[:] + m_ref[:].astype(jnp.int32) - 1
        s_iota = jax.lax.broadcasted_iota(
            jnp.int32, (ELL_WIDTH, ELL_WIDTH), 1)   # [l, s] = s
        lane_rows = []
        for r in range(block_rows):
            # lane(s) = #{l : p_eff[l] < s}; (1, 128) row, no transpose
            cmp = (p_eff[r][:, None] < s_iota).astype(jnp.int32)
            lane_rows.append(jnp.sum(cmp, axis=0, keepdims=True))
        lanes = jnp.minimum(jnp.concatenate(lane_rows, axis=0),
                            ELL_WIDTH - 1)
        g = jnp.take_along_axis(w_ref[:], lanes, axis=1)
        if with_val:
            g = g * val_ref[:]
        hi = src // 128
        lo = src % 128
        acc = jnp.zeros((m_rows, ELL_WIDTH), jnp.float32)
        for r in range(block_rows):
            # AT[m, s] = [hi[s] == m] * g[s];  B[s, l] = [lo[s] == l] —
            # both built in the dot's consumed orientation (a dim-0
            # dot_general contraction forces a per-iteration Mosaic
            # relayout, measured ~10x the MXU floor, r4 breakdown)
            at = jnp.where(
                jax.lax.broadcasted_iota(
                    jnp.int32, (m_rows, ELL_WIDTH), 0) == hi[r][None, :],
                g[r][None, :], 0.0)
            b = (lo[r][:, None] == jax.lax.broadcasted_iota(
                jnp.int32, (ELL_WIDTH, ELL_WIDTH), 1)).astype(jnp.float32)
            acc = acc + jnp.dot(at, b,
                                preferred_element_type=jnp.float32,
                                precision=precision)
        out_ref[:] += acc
    return kern


@functools.partial(jax.jit, static_argnames=("m_len", "interpret",
                                             "precision"))
def ell_margin_fused(w: jnp.ndarray, src: jnp.ndarray, pos: jnp.ndarray,
                     mask: jnp.ndarray, *, m_len: int,
                     val: Optional[jnp.ndarray] = None,
                     precision: str = "default",
                     interpret: bool = False) -> jnp.ndarray:
    """Forward twin of :func:`ell_scatter_apply_fused`: per-sample margin
    contributions of the in-grid slots, on the MXU instead of the
    transaction-bound ``w[cat]`` gather.  Returns a flat f32 table of
    length >= ``m_len`` (rounded up to whole 8x128 tiles — callers slice
    ``[:batch]``).  ``val`` is the per-slot explicit-value multiplier of
    the generic sparse layout; ``precision`` as in
    :func:`ell_scatter_apply_fused`."""
    rows = src.shape[0]
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    br = _FUSED_BLOCK_ROWS
    if rows % br:
        raise ValueError(
            f"fused margin kernel needs rows % {br} == 0, got {rows}; "
            "use ell_margin_xla")
    if m_len % 128:
        raise ValueError(
            f"m_len must be a multiple of 128, got {m_len}; use the "
            "sgd._extended_r length")
    m_rows = m_len // 128
    m_rows += (-m_rows) % 8          # whole sublane tiles for the MXU
    w2 = w.reshape(rows, _LANES)
    block = pl.BlockSpec((br, 128), lambda i: (i, 0),
                         memory_space=pltpu.VMEM)
    operands = [src, pos, mask, w2]
    in_specs = [block] * 4
    if val is not None:
        operands.append(val)
        in_specs.append(block)
    out = pl.pallas_call(
        _margin_kernel(br, m_rows, precision, val is not None),
        grid=(rows // br,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((m_rows, 128), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((m_rows, ELL_WIDTH), jnp.float32),
        interpret=interpret,
    )(*operands)
    return out.reshape(-1)


# ---------------------------------------------------------------------------
# kernel-registry entries (kernels/registry.py): the ELL hot paths under
# ONE uniform signature per op, so the training step builders resolve
# their implementation with a lookup instead of branching on a
# ``use_pallas`` flag by hand.  Backend selection mirrors the legacy
# branches exactly: fully-fused Mosaic when the table grid divides into
# 8-row blocks, the gather + Mosaic-scatter pair otherwise, pure XLA off
# TPU (and as the forced oracle).
# ---------------------------------------------------------------------------

def ell_margin_xla_entry(w, src, pos, mask, *, m_len: int, val=None,
                         precision: str = "default", interpret: bool = False):
    """XLA backend of op ``ell_margin`` (registry signature; ``precision``
    and ``interpret`` are Mosaic knobs the XLA lowering has no use for —
    it always accumulates in f32)."""
    return ell_margin_xla(w, src, pos, mask, m_len, val=val)


# -- lane-blocked weight gather ---------------------------------------------
# Shared with the model layer (sgd.py re-imports these): ops/ owns the
# device-kernel helpers, models look them up — never the other way
# around (an ops -> models import would cycle through the kernels
# catalog the moment a lazy import is hoisted).  Blocked and elementwise
# paths produce bitwise-equal values; blocking only changes the lowering
# (lane-aligned row-gather + one-hot lane select instead of XLA's
# per-element gather).

_GATHER_LANES = 256


def use_blocked(d: int) -> bool:
    return d % _LANES == 0 and d >= _LANES


def blocked_gather(w, idx):
    """``w[idx]`` via lane-aligned row-gather + one-hot lane select."""
    d = w.shape[0]
    lanes = (_GATHER_LANES if d % _GATHER_LANES == 0 and d >= _GATHER_LANES
             else _LANES)
    flat = idx.reshape(-1)
    hi, lo = flat // lanes, flat % lanes
    onehot = lo[:, None] == jnp.arange(lanes, dtype=lo.dtype)[None, :]
    rows = w.reshape(-1, lanes)[hi]
    return jnp.sum(jnp.where(onehot, rows, 0), axis=-1).reshape(idx.shape)


def gather_weights(w, idx):
    return blocked_gather(w, idx) if use_blocked(w.shape[0]) else w[idx]


def _ell_pair_update(r_ext, src, lr, val):
    g = gather_weights(r_ext, src)
    return (-lr) * (g if val is None else val * g)


def ell_scatter_apply_pair(w, r_ext, src, pos, mask, *, lr, val=None,
                           precision: str = "default",
                           interpret: bool = False):
    """``pallas-pair`` backend of op ``ell_scatter_apply``: the XLA slot
    gather feeding the Mosaic csum/pick scatter kernel — the fallback for
    table grids the 8-row fused kernel cannot block."""
    return ell_scatter_apply(w, _ell_pair_update(r_ext, src, lr, val),
                             pos, mask, interpret=interpret)


def ell_scatter_apply_xla_entry(w, r_ext, src, pos, mask, *, lr, val=None,
                                precision: str = "default",
                                interpret: bool = False):
    """XLA backend of op ``ell_scatter_apply`` (gather + csum/pick in pure
    XLA — the CPU path and the parity oracle)."""
    return ell_scatter_apply_xla(w, _ell_pair_update(r_ext, src, lr, val),
                                 pos, mask)


def _fused_blockable(sig: tuple) -> bool:
    """Shape contract of the fused ELL kernels: ``sig = (table_rows,)``
    must divide into the 8-row Mosaic grid blocks."""
    return bool(sig) and sig[0] % _FUSED_BLOCK_ROWS == 0


def _register_ell_kernels() -> None:
    from ..kernels.registry import register_kernel, tpu_only

    register_kernel("ell_margin", "pallas", ell_margin_fused,
                    priority=20, supports=_fused_blockable,
                    available=tpu_only)
    register_kernel("ell_margin", "xla", ell_margin_xla_entry)
    register_kernel("ell_scatter_apply", "pallas", ell_scatter_apply_fused,
                    priority=30, supports=_fused_blockable,
                    available=tpu_only)
    register_kernel("ell_scatter_apply", "pallas-pair",
                    ell_scatter_apply_pair, priority=20,
                    available=tpu_only)
    register_kernel("ell_scatter_apply", "xla", ell_scatter_apply_xla_entry)


_register_ell_kernels()
