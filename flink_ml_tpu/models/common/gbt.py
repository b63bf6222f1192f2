"""Histogram-based gradient-boosted trees — the shared trainer.

Member of the later Flink ML 2.x library line (GBTClassifier/GBTRegressor).
CPU GBT implementations walk rows per node; the TPU-native formulation is
the histogram method with everything vectorized over rows:

- **Binning** (host, once): per-feature quantile bins -> int32 bin ids.
- **Histograms** (device): per level, one ``segment_sum`` over the flattened
  ``(node, feature, bin)`` key accumulates (grad, hess, count) for ALL nodes
  and features at once — the analog of the keyed shuffle+reduce a dataflow
  engine would run, fused on-chip.
- **Split finding** (device): cumulative sums over bins give every candidate
  split's left/right (G, H); the XGBoost gain
  ``G_L^2/(H_L+l) + G_R^2/(H_R+l) - G^2/(H+l)`` is argmaxed per node.
- **Routing** (device): rows step to ``2*node+1 (+1)`` by comparing their
  bin to the split threshold — no gather-scatter trees, just arrays.

Trees are complete binary arrays (node i's children are 2i+1/2i+2), so one
jitted ``build_level`` per depth serves every tree; the boosting loop runs
hosted (each tree depends on the previous residuals).
"""

from __future__ import annotations

import os

from dataclasses import dataclass
from functools import partial
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...kernels.aot import aot_jit

__all__ = ["GBTConfig", "bin_features", "train_forest", "predict_forest",
           "Forest", "SoftmaxForest", "train_forest_softmax",
           "predict_forest_softmax"]


@dataclass
class GBTConfig:
    num_trees: int = 20
    max_depth: int = 4            # levels of internal nodes
    learning_rate: float = 0.1
    max_bins: int = 64
    reg_lambda: float = 1.0
    min_child_weight: float = 1e-3
    #: out-of-core chunked dispatch: stack this many streamed batches
    #: into one device chunk and run each pass's per-batch device work
    #: as ONE jitted lax.scan — every histogram/leaf/margin pass costs
    #: ``ceil(n_batches / W)`` dispatches (and device transfers)
    #: instead of ``n_batches``.  Short final chunks pad with zero-
    #: gradient batches, which are inert in every additive pass.  1 =
    #: one dispatch per batch through the same scan program.  In-core
    #: training ignores it.  NOTE the device-memory trade: each transfer
    #: stages a ``(W, batch_device_rows, d)`` chunk — W times the
    #: per-batch staging — so deployments that sized
    #: ``batch_device_rows`` to fit HBM must either shrink it by W or
    #: set ``steps_per_dispatch=1`` to keep the old footprint.
    steps_per_dispatch: int = 8


@dataclass
class Forest:
    """(trees, nodes) arrays; node i's children are 2i+1 / 2i+2."""

    feature: np.ndarray       # (T, n_nodes) int32, -1 for leaf
    threshold: np.ndarray     # (T, n_nodes) int32 bin id: go left if <= thr
    value: np.ndarray         # (T, n_nodes) f32 leaf value
    bin_edges: np.ndarray     # (d, max_bins - 1) f64 quantile edges
    base_score: float
    learning_rate: float


def quantile_edges(X: np.ndarray, max_bins: int) -> np.ndarray:
    """Per-feature quantile edges (d, bins-1) — the sketch half of
    :func:`bin_features` (the out-of-core trainer needs only this from
    its bounded leading sample)."""
    d = X.shape[1]
    edges = np.empty((d, max_bins - 1))
    qs = np.linspace(0, 1, max_bins + 1)[1:-1]
    for j in range(d):
        # duplicates collapse constant regions
        edges[j] = np.quantile(X[:, j], qs)
    return edges


def bin_features(X: np.ndarray, max_bins: int) -> Tuple[np.ndarray, np.ndarray]:
    """Quantile binning on host: (binned int32 (n, d), edges (d, bins-1))."""
    edges = quantile_edges(X, max_bins)
    return apply_bins(X, edges), edges


def apply_bins(X: np.ndarray, edges: np.ndarray) -> np.ndarray:
    binned = np.empty(X.shape, np.int32)
    for j in range(X.shape[1]):
        binned[:, j] = np.searchsorted(edges[j], X[:, j], side="left")
    return binned


@jax.jit
def apply_bins_device(X: jnp.ndarray, edges: jnp.ndarray) -> jnp.ndarray:
    """Vectorized on-device twin of :func:`apply_bins`:
    ``bin = #edges strictly below x`` (== searchsorted side='left' for
    quantile edges), with NaN routed to the LAST bin exactly as
    np.searchsorted sorts it.  One fused (n, d, bins-1) compare+sum
    instead of a per-feature loop.

    Precision caveat: runs at the device dtype (f32 without jax x64), so
    rows within f32 rounding of an edge can bin differently from the
    f64 host path — use it for f32-native device-resident pipelines; the
    out-of-core trainer host-bins to stay bit-identical with in-core
    training AND with predict-time binning."""
    count = jnp.sum(X[:, :, None] > edges[None, :, :], axis=-1,
                    dtype=jnp.int32)
    return jnp.where(jnp.isnan(X), edges.shape[1], count)


#: histogram implementation: "auto" (the kernel registry picks — XLA
#: segment_sum everywhere today, see the registrations at the end of
#: this module), "segsum" (force the XLA scatter-adds, the r1-r4 path)
#: or "mxu" (force the double one-hot matmul: exact up to f32 summation
#: order off TPU, bf16-truncated addends on the MXU).  Neither forced
#: value is timed on the chip (ROADMAP S12).
HIST_IMPL = "auto"


@partial(jax.jit, static_argnames=("n_nodes", "d", "bins"))
def _level_histograms_segsum(binned, node_ids, grad, hess, n_nodes: int,
                             d: int, bins: int):
    """segment_sum form: one scatter-add per (row, feature) key."""
    live = node_ids >= 0
    safe_node = jnp.where(live, node_ids, 0)
    # (node, feature, bin) -> flat key; dead rows land in a scratch key 0
    # with zero weights
    keys = (safe_node[:, None] * (d * bins)
            + jnp.arange(d, dtype=jnp.int32)[None, :] * bins
            + binned)                                           # (n, d)
    w = live.astype(grad.dtype)
    seg = n_nodes * d * bins
    flat = keys.reshape(-1)
    g_hist = jax.ops.segment_sum((grad * w)[:, None].repeat(d, 1).reshape(-1),
                                 flat, seg)
    h_hist = jax.ops.segment_sum((hess * w)[:, None].repeat(d, 1).reshape(-1),
                                 flat, seg)
    return (g_hist.reshape(n_nodes, d, bins),
            h_hist.reshape(n_nodes, d, bins))


@partial(jax.jit, static_argnames=("n_nodes", "d", "bins"))
def _level_histograms_mxu(binned, node_ids, grad, hess, n_nodes: int,
                          d: int, bins: int):
    """MXU form: hist[node, f, bin] = (onehot_node * value)^T @
    onehot_bin_f — histogramming as n x n_nodes x bins matmul
    contractions (no scatter anywhere), scanned over features so the
    transient one-hots stay at (n, n_nodes) + (n, bins).  ~2*n*nodes*
    bins MAC per (feature, value) — MXU work standing in for
    segment_sum's per-element random accumulation."""
    live = node_ids >= 0
    safe_node = jnp.where(live, node_ids, 0)
    w = live.astype(grad.dtype)
    # (n, n_nodes) one-hots pre-scaled by the two accumulated values —
    # rows of dead nodes carry zeros, so scratch-node pollution is moot
    node_oh = (safe_node[:, None]
               == jnp.arange(n_nodes, dtype=jnp.int32)[None, :])
    gv = jnp.where(node_oh, (grad * w)[:, None], 0.0)   # (n, n_nodes)
    hv = jnp.where(node_oh, (hess * w)[:, None], 0.0)

    def per_feature(_, f):
        bin_oh = (binned[:, f][:, None]
                  == jnp.arange(bins, dtype=jnp.int32)[None, :]
                  ).astype(grad.dtype)                  # (n, bins)
        g_f = jax.lax.dot_general(
            gv, bin_oh, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # (n_nodes, bins)
        h_f = jax.lax.dot_general(
            hv, bin_oh, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return None, (g_f, h_f)

    _, (g_hist, h_hist) = jax.lax.scan(
        per_feature, None, jnp.arange(d, dtype=jnp.int32))
    # scan stacks (d, n_nodes, bins) -> (n_nodes, d, bins)
    return (jnp.transpose(g_hist, (1, 0, 2)),
            jnp.transpose(h_hist, (1, 0, 2)))


#: the dispatch table — unknown HIST_IMPL values raise KeyError instead
#: of silently running the wrong implementation
_HIST_IMPLS = {"segsum": _level_histograms_segsum,
               "mxu": _level_histograms_mxu}


def resolve_hist_impl(name: str = None) -> str:
    """Resolve a histogram impl name ("auto" -> the kernel registry's
    pick for this backend; "segsum"/"mxu" force) to a concrete
    ``_HIST_IMPLS`` key.  Unknown names raise KeyError — never a silent
    fallback."""
    name = HIST_IMPL if name is None else name
    if name == "auto":
        from ...kernels.registry import lookup

        backend = lookup("gbt_level_histograms").backend
        return {"xla": "segsum"}.get(backend, backend)
    if name not in _HIST_IMPLS:
        raise KeyError(name)
    return name


def _level_histograms(binned, node_ids, grad, hess, n_nodes: int,
                      d: int, bins: int):
    """Per-(node, feature, bin) grad/hess sums for one level — the
    ADDITIVE piece of split finding: the out-of-core trainer accumulates
    these over streamed batches and decides splits from the totals.
    Dispatches on :data:`HIST_IMPL` through :func:`resolve_hist_impl`."""
    return _HIST_IMPLS[resolve_hist_impl()](binned, node_ids, grad, hess,
                                            n_nodes, d, bins)


def _level_splits(g_hist, h_hist, reg_lambda: float,
                  min_child_weight: float):
    """Best (feature, bin, gain) per node from the level histograms."""
    n_nodes, d, bins = g_hist.shape
    g_tot = jnp.sum(g_hist, axis=(1, 2)) / d                    # per node
    h_tot = jnp.sum(h_hist, axis=(1, 2)) / d

    # candidate split at bin b: left = bins <= b (cumsum), right = rest
    g_left = jnp.cumsum(g_hist, axis=2)
    h_left = jnp.cumsum(h_hist, axis=2)
    g_right = g_tot[:, None, None] - g_left
    h_right = h_tot[:, None, None] - h_left

    def score(g, h):
        return g * g / (h + reg_lambda)

    gain = (score(g_left, h_left) + score(g_right, h_right)
            - score(g_tot, h_tot)[:, None, None])               # (nodes,d,bins)
    viable = ((h_left >= min_child_weight)
              & (h_right >= min_child_weight))
    gain = jnp.where(viable, gain, -jnp.inf)
    # never split on the last bin (empty right side by construction)
    gain = gain.at[:, :, -1].set(-jnp.inf)

    flat_gain = gain.reshape(n_nodes, d * bins)
    best = jnp.argmax(flat_gain, axis=1)
    best_gain = jnp.take_along_axis(flat_gain, best[:, None], 1)[:, 0]
    best_feature = (best // bins).astype(jnp.int32)
    best_bin = (best % bins).astype(jnp.int32)
    return best_feature, best_bin, best_gain


def _apply_split(binned, node_ids, best_feature, best_bin, best_gain):
    """Route live rows through the level's chosen splits: 2*node (+1 for
    right) in the next level's local numbering, -1 where the node did not
    split."""
    live = node_ids >= 0
    safe_node = jnp.where(live, node_ids, 0)
    row_bin = jnp.take_along_axis(binned, best_feature[safe_node][:, None],
                                  1)[:, 0]
    goes_right = row_bin > best_bin[safe_node]
    node_split = best_gain[safe_node] > 0
    return jnp.where(live & node_split,
                     2 * safe_node + goes_right.astype(jnp.int32), -1)


@partial(aot_jit, static_argnames=("n_nodes", "d", "bins", "reg_lambda",
                                   "min_child_weight", "hist_impl"))
def _build_level(binned, node_ids, grad, hess, n_nodes: int,
                 d: int, bins: int, reg_lambda: float,
                 min_child_weight: float, hist_impl: str = "segsum"):
    """One tree level for all ``n_nodes`` nodes at once
    (histograms -> splits -> routing; the three pieces are separate
    functions so the out-of-core trainer can accumulate histograms over
    batches and reuse the identical split/routing math).

    Returns (feature (n_nodes,), threshold (n_nodes,), gain (n_nodes,),
    new_node_ids (n,)).  ``node_ids`` are level-local in [0, n_nodes) with
    -1 marking rows already settled in a leaf.
    """
    g_hist, h_hist = _HIST_IMPLS[resolve_hist_impl(hist_impl)](
        binned, node_ids, grad, hess, n_nodes, d, bins)
    best_feature, best_bin, best_gain = _level_splits(
        g_hist, h_hist, reg_lambda, min_child_weight)
    new_ids = _apply_split(binned, node_ids, best_feature, best_bin,
                           best_gain)
    return best_feature, best_bin, best_gain, new_ids


@partial(aot_jit, static_argnames=("n_nodes", "reg_lambda"))
def _leaf_values(node_ids, grad, hess, n_nodes: int, reg_lambda: float):
    """Newton leaf weights -G/(H+lambda) for every level-local node."""
    live = node_ids >= 0
    safe = jnp.where(live, node_ids, 0)
    w = live.astype(grad.dtype)
    g = jax.ops.segment_sum(grad * w, safe, n_nodes)
    h = jax.ops.segment_sum(hess * w, safe, n_nodes)
    return -g / (h + reg_lambda)


def _train_one_tree(binned, g, h, d: int, config: GBTConfig):
    """Grow one tree against device gradients/hessians; returns the host
    (feature, threshold, value) node rows plus the tree's DEVICE in-sample
    prediction (margin scale, before learning-rate shrinkage)."""
    n = binned.shape[0]
    bins = config.max_bins
    depth = config.max_depth
    n_nodes_total = 2 ** (depth + 1) - 1
    feature_row = np.full((n_nodes_total,), -1, np.int32)
    threshold_row = np.zeros((n_nodes_total,), np.int32)
    value_row = np.zeros((n_nodes_total,), np.float32)

    node_ids = jnp.zeros((n,), jnp.int32)
    level_feature: List[np.ndarray] = []
    level_bin: List[np.ndarray] = []
    level_gain: List[np.ndarray] = []
    level_ids = [node_ids]
    for level in range(depth):
        n_nodes = 2 ** level
        # hist impl resolved to a CONCRETE name before it becomes a
        # static arg: "auto" would be ambiguous in the persistent AOT
        # key (the registry/autotune pick can differ across processes)
        f, b, gain, node_ids = _build_level(
            binned, node_ids, g, h, n_nodes, d, bins,
            config.reg_lambda, config.min_child_weight,
            hist_impl=resolve_hist_impl())
        level_feature.append(np.asarray(f))
        level_bin.append(np.asarray(b))
        level_gain.append(np.asarray(gain))
        level_ids.append(node_ids)

    # assemble the tree: internal nodes that actually split get
    # (feature, threshold); everything else becomes a leaf holding the
    # Newton value of the rows that stopped there
    base = 0
    for level in range(depth):
        n_nodes = 2 ** level
        split = level_gain[level] > 0
        feature_row[base:base + n_nodes] = np.where(
            split, level_feature[level], -1)
        threshold_row[base:base + n_nodes] = level_bin[level]
        # leaf value for rows that STOP at this level (their node did not
        # split): computed from the ids entering the level
        vals = np.asarray(_leaf_values(level_ids[level], g, h, n_nodes,
                                       config.reg_lambda))
        value_row[base:base + n_nodes] = np.where(split, 0.0, vals)
        base += n_nodes
    # deepest level: always leaves
    n_nodes = 2 ** depth
    vals = np.asarray(_leaf_values(level_ids[depth], g, h, n_nodes,
                                   config.reg_lambda))
    value_row[base:base + n_nodes] = vals

    # in-sample update reuses the DEVICE binned copy — predicting from the
    # host matrix would re-upload it once per tree
    pred = _predict_tree_jit(binned, jnp.asarray(feature_row),
                             jnp.asarray(threshold_row),
                             jnp.asarray(value_row), depth)
    return feature_row, threshold_row, value_row, pred


def _maybe_autotune_hist(binned, g, h, d: int, bins: int) -> None:
    """First-encounter autotune of the histogram backend (ISSUE 12):
    when several registry backends are AVAILABLE on this device (none
    today: ``mxu`` is forced-lookup only, so there is nothing to search)
    and a persistent cache root is configured, time both on a probe slice of the REAL binned
    data and record the winner — ``resolve_hist_impl("auto")`` then
    resolves through ``registry.lookup``, which honors the decision in
    this and every later process.  A recorded decision short-circuits
    (zero search cost)."""
    from ...kernels import autotune
    from ...kernels.registry import backends, lookup

    if HIST_IMPL != "auto" or not autotune.enabled():
        return
    avail = [b for b in backends("gbt_level_histograms")
             if lookup("gbt_level_histograms", backend=b).is_available()]
    if len(avail) < 2:
        return
    rows = min(int(binned.shape[0]), 8192)
    bp, gp, hp = binned[:rows], g[:rows], h[:rows]
    ids = jnp.zeros((rows,), jnp.int32)
    impl_of = {"xla": "segsum"}

    def runner(backend):
        impl = _HIST_IMPLS[impl_of.get(backend, backend)]
        return lambda: impl(bp, ids, gp, hp, 4, d, bins)

    autotune.choose("gbt_level_histograms", (),
                    {b: runner(b) for b in avail},
                    probe=f"real-data slice rows={rows} d={d} bins={bins} "
                          "n_nodes=4")


def train_forest(X: np.ndarray, y: np.ndarray,
                 grad_hess: Callable[[np.ndarray, np.ndarray],
                                     Tuple[np.ndarray, np.ndarray]],
                 base_score: float, config: GBTConfig) -> Forest:
    """Boost ``num_trees`` trees against ``grad_hess(y, pred)``."""
    n, d = X.shape
    binned_host, edges = bin_features(X, config.max_bins)
    binned = jnp.asarray(binned_host)
    n_nodes_total = 2 ** (config.max_depth + 1) - 1

    features = np.full((config.num_trees, n_nodes_total), -1, np.int32)
    thresholds = np.zeros((config.num_trees, n_nodes_total), np.int32)
    values = np.zeros((config.num_trees, n_nodes_total), np.float32)

    pred = np.full((n,), base_score, np.float64)
    for t in range(config.num_trees):
        g, h = grad_hess(y, pred)
        gd = jnp.asarray(g, jnp.float32)
        hd = jnp.asarray(h, jnp.float32)
        if t == 0:
            _maybe_autotune_hist(binned, gd, hd, d, config.max_bins)
        features[t], thresholds[t], values[t], tree_pred = _train_one_tree(
            binned, gd, hd, d, config)
        pred = pred + config.learning_rate * np.asarray(tree_pred, np.float64)

    return Forest(features, thresholds, values, edges, base_score,
                  config.learning_rate)


@partial(jax.jit, static_argnames=("n_nodes",))
def _leaf_sums(node_ids, grad, hess, n_nodes: int):
    """Per-node (G, H) sums — the additive form of :func:`_leaf_values`
    for streamed batches."""
    live = node_ids >= 0
    safe = jnp.where(live, node_ids, 0)
    w = live.astype(grad.dtype)
    return (jax.ops.segment_sum(grad * w, safe, n_nodes),
            jax.ops.segment_sum(hess * w, safe, n_nodes))


@partial(jax.jit, static_argnames=("level",))
def _route_to_level(binned, feature_rows, threshold_rows, level: int):
    """Node ids entering ``level`` by walking the assembled tree-so-far
    (level-major layout; ``feature == -1`` marks a non-splitting node,
    matching :func:`_apply_split`'s ``gain > 0`` routing exactly)."""
    ids = jnp.zeros((binned.shape[0],), jnp.int32)
    base = 0
    for lvl in range(level):
        live = ids >= 0
        safe = jnp.where(live, ids, 0)
        gnode = base + safe
        f = feature_rows[gnode]
        thr = threshold_rows[gnode]
        split = f >= 0
        row_bin = jnp.take_along_axis(binned, jnp.maximum(f, 0)[:, None],
                                      1)[:, 0]
        ids = jnp.where(live & split,
                        2 * safe + (row_bin > thr).astype(jnp.int32), -1)
        base += 2 ** lvl
    return ids


@partial(jax.jit, static_argnames=("level", "n_nodes", "d", "bins",
                                   "hist_impl"))
def _chunk_level_histograms(binned_c, g_c, h_c, feature_rows,
                            threshold_rows, g_init, h_init, level: int,
                            n_nodes: int, d: int, bins: int,
                            hist_impl: str):
    """Chunked histogram pass: one lax.scan accumulates the level
    histograms of a whole (W, rows, d) chunk in ONE dispatch — the
    per-batch route+histogram work is identical, only the dispatch
    boundary moves.  The RUNNING histograms ride in as the scan carry
    (``g_init``/``h_init``), so accumulation stays strictly per-batch
    sequential across chunk boundaries — f32 addition is
    non-associative, and summing each chunk separately would make the
    result W-dependent.  Zero-gradient (padding) batches add exact
    zeros."""
    def scan_step(carry, xs):
        gh_acc, hh_acc = carry
        b, g, h = xs
        ids = _route_to_level(b, feature_rows, threshold_rows, level)
        gh, hh = _HIST_IMPLS[resolve_hist_impl(hist_impl)](
            b, ids, g, h, n_nodes, d, bins)
        return (gh_acc + gh, hh_acc + hh), None

    (g_hist, h_hist), _ = jax.lax.scan(scan_step, (g_init, h_init),
                                       (binned_c, g_c, h_c))
    return g_hist, h_hist


@partial(jax.jit, static_argnames=("depth", "n_nodes"))
def _chunk_leaf_sums(binned_c, g_c, h_c, feature_rows, threshold_rows,
                     depth: int, n_nodes: int):
    """Chunked leaf-sum pass: stacked per-batch (G, H) node sums from one
    dispatch (kept per-batch so the host's f64 accumulation order matches
    the per-batch path exactly)."""
    def scan_step(_, xs):
        b, g, h = xs
        ids = _route_to_level(b, feature_rows, threshold_rows, depth)
        return None, _leaf_sums(ids, g, h, n_nodes)

    _, (gs, hs) = jax.lax.scan(scan_step, None, (binned_c, g_c, h_c))
    return gs, hs


@partial(jax.jit, static_argnames=("depth",))
def _chunk_tree_preds(binned_c, feature, threshold, value, depth: int):
    """Chunked margin pass: stacked (W, rows) tree predictions from one
    dispatch."""
    def scan_step(_, b):
        return None, _predict_tree_jit(b, feature, threshold, value, depth)

    _, preds = jax.lax.scan(scan_step, None, binned_c)
    return preds


def train_forest_outofcore(make_reader, grad_hess, base_score,
                           config: GBTConfig, *,
                           features_key: str = "features",
                           label_key: str = "label",
                           work_dir: Optional[str] = None,
                           sample_rows: int = 1 << 18,
                           batch_device_rows: int = 1 << 16) -> Forest:
    """Out-of-core :func:`train_forest`: the dataset streams from
    ``make_reader()`` (a fresh iterator of host batch dicts per call —
    the ``sgd_fit_outofcore`` protocol, but STRICTLY zero-arg and
    order-stable: unlike the sgd/kmeans streamers, epoch-aware or
    reshuffling factories are deliberately unsupported because the
    margin memmap is aligned to ROW ORDER across passes — every call
    must yield the same rows in the same order, or margins silently
    desynchronize.  A ``lambda epoch:`` factory fails loudly with a
    TypeError; a zero-arg factory that varies order per call is the
    caller's contract violation and cannot be detected here)
    instead of living in RAM/HBM, removing the one estimator family
    with a host-memory ceiling (VERDICT r2 task 9).

    Design: histogram building is ADDITIVE over row batches, so each tree
    level is one streamed pass accumulating ``_level_histograms`` on
    device, followed by the same ``_level_splits`` decision the in-core
    path uses — the classic out-of-core GBDT recipe, with the reference's
    replay-per-epoch posture (``ReplayOperator``) supplying the passes.

    - Bin edges come from the stream's leading ``sample_rows`` rows
      (quantile sketching on a bounded sample); each batch then bins
      through the HOST searchsorted (bit-identical to in-core training
      and to predict-time binning; see :func:`apply_bins_device` for why
      the f32 device variant is not used here).
    - The binned matrix is written once to a :class:`DataCacheWriter`
      cache in a fresh run directory under ``work_dir`` (uint8 when
      ``max_bins <= 256``: 4x smaller than the raw f32 stream), every
      later pass replays the cache, and the run directory is removed on
      return (margins included).
    - Per-row boosting margins live in a disk-backed memmap (float64,
      8 bytes/row — the only O(n) state).
    - ``base_score`` may be a float or a callable receiving the leading
      sample's labels (folds the estimator's base-score computation into
      pass A instead of an extra head read).

    Passes per tree: ``max_depth`` histogram passes + one leaf-sum pass +
    one margin-update pass.  Results match :func:`train_forest` on the
    same rows up to f32 accumulation order (asserted in tests).
    """
    import shutil
    import tempfile

    from ...data.datacache import DataCacheReader, DataCacheWriter

    bins = config.max_bins
    depth = config.max_depth

    # pass A: edges (and optionally the base score) from the leading sample
    sample: List[np.ndarray] = []
    sample_y: List[np.ndarray] = []
    seen = 0
    for batch in make_reader():
        sample.append(np.asarray(batch[features_key], np.float64))
        sample_y.append(np.asarray(batch[label_key], np.float64))
        seen += len(sample[-1])
        if seen >= sample_rows:
            break
    if not sample:
        raise ValueError("make_reader() returned an empty stream")
    Xs = np.concatenate(sample)[:sample_rows]
    d = Xs.shape[1]
    edges = quantile_edges(Xs, bins)
    if callable(base_score):
        base_score = float(base_score(np.concatenate(sample_y)[:sample_rows]))
    del sample, sample_y, Xs

    # pass B: binned cache + labels, in a unique per-fit run directory
    # (DataCacheWriter refuses dirty directories; retries and repeated
    # fits against one work_dir must each get a fresh cache)
    if work_dir is not None:
        os.makedirs(work_dir, exist_ok=True)
    run_dir = tempfile.mkdtemp(prefix="gbt-run-", dir=work_dir)
    try:
        cache_dir = os.path.join(run_dir, "binned")
        bin_dtype = np.uint8 if bins <= 256 else np.int32
        writer = DataCacheWriter(cache_dir, segment_rows=1 << 20)
        n = 0
        for batch in make_reader():
            X = np.asarray(batch[features_key], np.float64)
            b = apply_bins(X, edges).astype(bin_dtype)
            writer.append({"binned": b,
                           "label": np.asarray(batch[label_key],
                                               np.float64)})
            n += len(b)
        writer.finish()
        margins = np.memmap(os.path.join(run_dir, "margins.f64"),
                            np.float64, mode="w+", shape=(n,))
        margins[:] = base_score

        def cache_batches():
            """(slice, binned int32 HOST, y f64, margins f64) batches —
            host-side so the chunked passes stack W batches and pay one
            device transfer per chunk."""
            reader = DataCacheReader(cache_dir,
                                     batch_rows=batch_device_rows)
            start = 0
            for batch in reader:
                rows = len(batch["label"])
                sl = slice(start, start + rows)
                start += rows
                yield (sl, batch["binned"].astype(np.int32),
                       np.asarray(batch["label"], np.float64), margins[sl])

        return _boost_outofcore(cache_batches, margins, grad_hess,
                                base_score, edges, n, d, config)
    finally:
        shutil.rmtree(run_dir, ignore_errors=True)


def _boost_outofcore(cache_batches, margins, grad_hess, base_score: float,
                     edges: np.ndarray, n: int, d: int,
                     config: GBTConfig) -> Forest:
    bins = config.max_bins
    depth = config.max_depth
    W = max(1, int(config.steps_per_dispatch))

    # Chunked dispatch (config.steps_per_dispatch): every streamed pass
    # stacks W batches into one (W, rows, d) device chunk and runs the
    # per-batch route/histogram/predict work as ONE jitted lax.scan —
    # ceil(n_batches / W) dispatches + transfers per pass instead of
    # n_batches.  Rows pad to the first batch's count and short final
    # chunks pad with whole zero batches: zero gradients/hessians make
    # every padded slot an exact no-op in the additive passes, and the
    # margin pass writes back only each real batch's real rows.
    def chunked_batches(need_gh: bool):
        """Yield (sls, binned_c (W, R, d) device i32, g_c, h_c (W, R)
        device f32 or None): ``sls`` lists the real batches' row
        slices.  Grouping rides the prefetch pipeline's ``_grouped``
        (one W-grouping protocol in the repo)."""
        from ...data.prefetch import _grouped

        rows_full: Optional[int] = None

        def emit(group):
            R = rows_full
            sls = [sl for sl, _, _, _ in group]
            if (len(group) == W
                    and all(b.shape[0] == R for _, b, _, _ in group)):
                # the steady case: equal full batches stack in one copy
                binned_c = np.stack([b for _, b, _, _ in group])
                if need_gh:
                    g_c = np.stack([g for _, _, g, _ in group])
                    h_c = np.stack([h for _, _, _, h in group])
            else:
                # ragged tail: zero-pad short rows / missing batches
                binned_c = np.zeros((W, R, d), np.int32)
                g_c = np.zeros((W, R), np.float32) if need_gh else None
                h_c = np.zeros((W, R), np.float32) if need_gh else None
                for j, (_, b, g, h) in enumerate(group):
                    binned_c[j, :b.shape[0]] = b
                    if need_gh:
                        g_c[j, :b.shape[0]] = g
                        h_c[j, :b.shape[0]] = h
            return (sls, jnp.asarray(binned_c),
                    jnp.asarray(g_c) if need_gh else None,
                    jnp.asarray(h_c) if need_gh else None)

        def prepared():
            for sl, binned_b, y_b, m_b in cache_batches():
                if need_gh:
                    g, h = grad_hess(y_b, m_b)
                    yield (sl, binned_b, np.asarray(g, np.float32),
                           np.asarray(h, np.float32))
                else:
                    yield (sl, binned_b, None, None)

        for group in _grouped(prepared(), W):
            if rows_full is None:
                rows_full = group[0][1].shape[0]
            yield emit(group)

    n_nodes_total = 2 ** (depth + 1) - 1
    features = np.full((config.num_trees, n_nodes_total), -1, np.int32)
    thresholds = np.zeros((config.num_trees, n_nodes_total), np.int32)
    values = np.zeros((config.num_trees, n_nodes_total), np.float32)

    for t in range(config.num_trees):
        feature_row = np.full((n_nodes_total,), -1, np.int32)
        threshold_row = np.zeros((n_nodes_total,), np.int32)
        value_row = np.zeros((n_nodes_total,), np.float32)
        base = 0
        for level in range(depth):
            n_nodes = 2 ** level
            # running histograms thread through every chunk's scan carry
            # (strictly sequential per-batch accumulation, W-independent)
            g_hist = jnp.zeros((n_nodes, d, bins), jnp.float32)
            h_hist = jnp.zeros((n_nodes, d, bins), jnp.float32)
            f_dev = jnp.asarray(feature_row)
            thr_dev = jnp.asarray(threshold_row)
            for _, binned_c, g_c, h_c in chunked_batches(True):
                g_hist, h_hist = _chunk_level_histograms(
                    binned_c, g_c, h_c, f_dev, thr_dev, g_hist, h_hist,
                    level, n_nodes, d, bins, HIST_IMPL)
            bf, bb, bg = _level_splits(g_hist, h_hist, config.reg_lambda,
                                       config.min_child_weight)
            bf, bb, bg = np.asarray(bf), np.asarray(bb), np.asarray(bg)
            split = bg > 0
            feature_row[base:base + n_nodes] = np.where(split, bf, -1)
            threshold_row[base:base + n_nodes] = bb
            # leaf value for rows that STOP at this level: Newton step on
            # the per-node totals the histograms already carry
            g_tot = np.asarray(jnp.sum(g_hist, axis=(1, 2))) / d
            h_tot = np.asarray(jnp.sum(h_hist, axis=(1, 2))) / d
            vals = -g_tot / (h_tot + config.reg_lambda)
            value_row[base:base + n_nodes] = np.where(split, 0.0, vals)
            base += n_nodes

        # deepest level: always leaves — one leaf-sum pass (per-batch
        # sums come back stacked; the host's f64 accumulation order
        # stays per-batch, identical to the unchunked path)
        n_nodes = 2 ** depth
        G = np.zeros((n_nodes,), np.float64)
        H = np.zeros((n_nodes,), np.float64)
        f_dev = jnp.asarray(feature_row)
        thr_dev = jnp.asarray(threshold_row)
        for sls, binned_c, g_c, h_c in chunked_batches(True):
            gs, hs = _chunk_leaf_sums(binned_c, g_c, h_c, f_dev, thr_dev,
                                      depth, n_nodes)
            gs = np.asarray(gs, np.float64)
            hs = np.asarray(hs, np.float64)
            for j in range(len(sls)):
                G += gs[j]
                H += hs[j]
        value_row[base:base + n_nodes] = (
            -G / (H + config.reg_lambda)).astype(np.float32)

        # margin-update pass
        feat_dev = jnp.asarray(feature_row)
        thr_dev = jnp.asarray(threshold_row)
        val_dev = jnp.asarray(value_row)
        for sls, binned_c, _, _ in chunked_batches(False):
            preds = np.asarray(_chunk_tree_preds(binned_c, feat_dev,
                                                 thr_dev, val_dev, depth),
                               np.float64)
            for j, sl in enumerate(sls):
                margins[sl] += (config.learning_rate
                                * preds[j, :sl.stop - sl.start])
        features[t], thresholds[t], values[t] = (feature_row,
                                                 threshold_row, value_row)
    margins.flush()
    return Forest(features, thresholds, values, edges, base_score,
                  config.learning_rate)


@dataclass
class SoftmaxForest:
    """K-class boosted forest: ``num_trees`` rounds x ``n_classes`` trees
    (the standard softmax objective — one tree per class per round, the
    XGBoost ``multi:softmax`` formulation)."""

    feature: np.ndarray       # (T, K, n_nodes) int32, -1 for leaf
    threshold: np.ndarray     # (T, K, n_nodes) int32
    value: np.ndarray         # (T, K, n_nodes) f32
    bin_edges: np.ndarray     # (d, max_bins - 1) f64
    base_scores: np.ndarray   # (K,) f64 log-priors
    learning_rate: float

    @property
    def n_classes(self) -> int:
        return self.feature.shape[1]


def _softmax_rows(m: np.ndarray) -> np.ndarray:
    e = np.exp(m - m.max(axis=1, keepdims=True))
    return e / e.sum(axis=1, keepdims=True)


def train_forest_softmax(X: np.ndarray, y_ids: np.ndarray, n_classes: int,
                         config: GBTConfig) -> SoftmaxForest:
    """Multiclass boosting: each round trains one tree per class against the
    softmax gradients ``g_k = p_k - 1[y=k]``, ``h_k = p_k (1 - p_k)``; class
    margins start at the log-priors."""
    n, d = X.shape
    binned_host, edges = bin_features(X, config.max_bins)
    binned = jnp.asarray(binned_host)
    n_nodes_total = 2 ** (config.max_depth + 1) - 1
    T, K = config.num_trees, n_classes

    features = np.full((T, K, n_nodes_total), -1, np.int32)
    thresholds = np.zeros((T, K, n_nodes_total), np.int32)
    values = np.zeros((T, K, n_nodes_total), np.float32)

    priors = np.bincount(y_ids, minlength=K) / max(n, 1)
    base_scores = np.log(np.clip(priors, 1e-6, None))
    margins = np.tile(base_scores, (n, 1))
    onehot = (y_ids[:, None] == np.arange(K)[None, :]).astype(np.float64)

    for t in range(T):
        p = _softmax_rows(margins)
        for k in range(K):
            g = p[:, k] - onehot[:, k]
            h = np.maximum(p[:, k] * (1.0 - p[:, k]), 1e-12)
            (features[t, k], thresholds[t, k], values[t, k],
             tree_pred) = _train_one_tree(
                binned, jnp.asarray(g, jnp.float32),
                jnp.asarray(h, jnp.float32), d, config)
            margins[:, k] += config.learning_rate * np.asarray(tree_pred,
                                                               np.float64)

    return SoftmaxForest(features, thresholds, values, edges, base_scores,
                         config.learning_rate)


def predict_forest_softmax(X: np.ndarray, forest: SoftmaxForest) -> np.ndarray:
    """Per-class margins (n, K).  Rows zero-pad to the shared power-of-two
    bucket (``utils/padding.py``) so mixed batch sizes reuse one compiled
    tree-walk per bucket; routing is per-row, pad rows slice off."""
    from ...utils.padding import pad_rows_to_bucket

    binned = apply_bins(X, forest.bin_edges)
    (binned,), n = pad_rows_to_bucket((binned,))
    depth = int(np.log2(forest.feature.shape[2] + 1)) - 1
    margins = np.tile(forest.base_scores, (binned.shape[0], 1))
    binned_dev = jnp.asarray(binned)
    for t in range(forest.feature.shape[0]):
        for k in range(forest.n_classes):
            margins[:, k] += forest.learning_rate * np.asarray(
                _predict_tree_jit(binned_dev,
                                  jnp.asarray(forest.feature[t, k]),
                                  jnp.asarray(forest.threshold[t, k]),
                                  jnp.asarray(forest.value[t, k]), depth),
                np.float64)
    return margins[:n]


def _predict_tree(binned: np.ndarray, feature: np.ndarray,
                  threshold: np.ndarray, value: np.ndarray,
                  depth: int) -> np.ndarray:
    return np.asarray(_predict_tree_jit(
        jnp.asarray(binned), jnp.asarray(feature), jnp.asarray(threshold),
        jnp.asarray(value), depth))


@partial(aot_jit, static_argnames=("depth",))
def _predict_tree_jit(binned, feature, threshold, value, depth: int):
    n = binned.shape[0]
    node = jnp.zeros((n,), jnp.int32)       # global complete-tree index
    out = jnp.zeros((n,), jnp.float32)
    settled = jnp.zeros((n,), bool)
    for _ in range(depth + 1):
        feat = feature[node]
        is_leaf = feat < 0
        newly = is_leaf & ~settled
        out = jnp.where(newly, value[node], out)
        settled = settled | is_leaf
        row_bin = jnp.take_along_axis(binned, jnp.maximum(feat, 0)[:, None],
                                      1)[:, 0]
        child = 2 * node + 1 + (row_bin > threshold[node]).astype(jnp.int32)
        node = jnp.where(settled, node, jnp.minimum(child,
                                                    feature.shape[0] - 1))
    return out


def predict_forest(X: np.ndarray, forest: Forest) -> np.ndarray:
    """Sum of tree outputs, margin scale.  Rows zero-pad to the shared
    power-of-two bucket (``utils/padding.py``): one compiled tree-walk per
    bucket serves every batch size, pad rows slice off."""
    from ...utils.padding import pad_rows_to_bucket

    binned = apply_bins(X, forest.bin_edges)
    (binned,), n = pad_rows_to_bucket((binned,))
    depth = int(np.log2(forest.feature.shape[1] + 1)) - 1
    pred = np.full((binned.shape[0],), forest.base_score, np.float64)
    for t in range(forest.feature.shape[0]):
        pred += forest.learning_rate * _predict_tree(
            binned, forest.feature[t], forest.threshold[t],
            forest.value[t], depth)
    return pred[:n]


# ---------------------------------------------------------------------------
# kernel-registry entries: op ``gbt_level_histograms``.  segsum is what
# "auto" plans on every device.  The MXU form (PR 10: histogramming as
# one-hot systolic matmuls instead of segment_sum's per-element random
# accumulation — the decision-forest-literature TPU-histogram trick) was
# the TPU default until it first ran on a chip (PR 21): its contraction
# runs at default MXU precision, so every gradient/hessian is truncated to
# bf16 before it is summed, and it no longer matches segsum to f32
# summation order.  It stays registered for a forced lookup and
# ``HIST_IMPL = "mxu"``; whether a higher-precision contraction still
# beats segment_sum is ROADMAP S3.  Both feed the streamed histogram carry
# unchanged (accumulation over batches is a plain add either way).
# ---------------------------------------------------------------------------

def _register_gbt_kernels() -> None:
    from ...kernels.registry import register_kernel

    register_kernel(
        "gbt_level_histograms", "mxu", _level_histograms_mxu, priority=10,
        forced_only="bf16-truncated gradients: max |diff| 0.0079 from "
                    "segment_sum on sums of ~3 (TPU v5 lite, rtol 1e-4 "
                    "wanted)")
    register_kernel("gbt_level_histograms", "xla", _level_histograms_segsum)


_register_gbt_kernels()
