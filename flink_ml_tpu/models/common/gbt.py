"""Histogram-based gradient-boosted trees — the shared trainer.

Member of the later Flink ML 2.x library line (GBTClassifier/GBTRegressor).
CPU GBT implementations walk rows per node; the TPU-native formulation is
the histogram method with everything vectorized over rows:

- **Binning** (host, once a fit): per-feature quantile edges from a
  bounded sample of the rows, then ``bin = #edges strictly below x``
  (:func:`bin_features`; the rule is stated there).
- **Histograms** (device): per level, the (node, feature, bin) sums of
  (grad, hess) for ALL nodes and features at once — op
  ``gbt_level_histograms`` of the kernel registry: an exact one-hot
  contraction on the MXU on a TPU (``ops/gbt_hist_pallas.py``), one
  ``segment_sum`` over the flattened key elsewhere.
- **Split finding** (device): cumulative sums over bins give every
  candidate split's left/right (G, H); the XGBoost gain
  ``G_L^2/(H_L+l) + G_R^2/(H_R+l) - G^2/(H+l)`` is argmaxed per node.
- **Routing** (device): rows step to ``2*node (+1)`` by comparing their
  bin to the split threshold — no gather-scatter trees, just arrays.

On the device a table is FEATURE-MAJOR: ``d`` columns of bin ids, each
``(n,)`` int32, beside the rows' ``(n,)`` vectors (labels, margins,
gradients, node ids).  No array is ``(n, d)``: the chip tiles the last
dimension of an array to 128 lanes, so 13 features a row would take ten
times their bytes.  The additive pieces (:func:`_level_histograms`,
:func:`_level_splits`, :func:`_apply_split`) take the columns.

Trees are complete binary arrays (node i's children are 2i+1/2i+2).  The
binary in-core fit (:func:`train_forest`, the path ``GBTClassifier`` and
``GBTRegressor`` share) is ONE fused ``iterate`` program: a round is a
tree — the loss's gradient and hessian from the margins, ``max_depth``
levels unrolled (histogram, split, route), the leaves' Newton values and
the margin update — and the forest is fetched once, at the end.  The
multiclass (:func:`train_forest_softmax`) and streamed
(:func:`train_forest_outofcore`) trainers loop on the host around the
same pieces.
"""

from __future__ import annotations

import ctypes
import os

from dataclasses import dataclass
from functools import lru_cache, partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...iteration import IterationConfig, iterate
from ...iteration.body import with_program_key
from ...iteration.core import HandedOver
from ...kernels.aot import aot_jit
from ...obs.trace import tracer

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


#: the most rows the in-core fit reads to place its bin edges
EDGE_SAMPLE_ROWS = 1 << 18


def edge_sample(X: np.ndarray) -> np.ndarray:
    """The rows the in-core fit places its bin edges on: every row of a
    table of up to :data:`EDGE_SAMPLE_ROWS`, else every ``ceil(n /
    EDGE_SAMPLE_ROWS)``-th row from the first (a strided view, no copy).
    Spark's and Flink ML's GBT place theirs on a bounded sample too."""
    stride = -(-len(X) // EDGE_SAMPLE_ROWS)
    return X[::stride]


def quantile_edges(X: np.ndarray, max_bins: int) -> np.ndarray:
    """Per-feature quantile edges (d, bins-1) of the rows ``X``:
    ``np.quantile`` at ``linspace(0, 1, bins + 1)[1:-1]`` (linear
    interpolation, in float64)."""
    d = X.shape[1]
    edges = np.empty((d, max_bins - 1))
    qs = np.linspace(0, 1, max_bins + 1)[1:-1]
    for j in range(d):
        # duplicates collapse constant regions
        edges[j] = np.quantile(np.asarray(X[:, j], np.float64), qs)
    return edges


def bin_features(X: np.ndarray, max_bins: int) -> Tuple[np.ndarray, np.ndarray]:
    """The in-core fit's binning rule: ``(binned int32 (n, d), edges (d,
    bins-1))``.  The edges are :func:`quantile_edges` of
    :func:`edge_sample` (all the rows of a table of up to
    ``EDGE_SAMPLE_ROWS``); a value's bin is the number of edges strictly
    below it (``np.searchsorted(edges, x, side="left")``), a NaN's the
    number of edges that are not NaN.  The values are compared as given:
    a float32 table is binned as float32."""
    edges = quantile_edges(edge_sample(X), max_bins)
    return apply_bins(X, edges), edges


def apply_bins(X: np.ndarray, edges: np.ndarray) -> np.ndarray:
    binned = np.empty(X.shape, np.int32)
    for j in range(X.shape[1]):
        binned[:, j] = np.searchsorted(edges[j], X[:, j], side="left")
    return binned


@lru_cache(maxsize=None)
def _native_bins():
    """``native/gbt_bin.cpp`` built and loaded, or ``None`` on a machine
    with no ``make`` and no built library."""
    from ...utils.native_lib import load_native_lib

    lib = load_native_lib("gbt_bin")
    if lib is not None:
        lib.bin_columns.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int]
        lib.bin_columns.restype = ctypes.c_int
    return lib


def bin_columns(X: np.ndarray, edges: np.ndarray,
                rows: Optional[int] = None) -> np.ndarray:
    """``apply_bins(X, edges).T`` laid out for the device: ``(d, rows)``
    int32, row ``j`` the bin ids of feature ``j`` (C-contiguous, so each
    is one buffer to put), ``rows >= n`` with zeros after the ``n`` rows
    of ``X``.  A C-contiguous float32 ``X`` is binned by
    ``native/gbt_bin.cpp`` on every core (the same bins: see there);
    anything else, or no library, by NumPy."""
    n, d = X.shape
    rows = n if rows is None else rows
    out = np.empty((d, rows), np.int32)
    out[:, n:] = 0
    lib = _native_bins()
    if (lib is not None and X.dtype == np.float32
            and X.flags.c_contiguous and edges.shape[1] > 0):
        edges = np.ascontiguousarray(edges, np.float64)
        lib.bin_columns(X.ctypes.data, n, d, edges.ctypes.data,
                        edges.shape[1], out.ctypes.data, rows,
                        os.cpu_count() or 1)
        return out
    for j in range(d):
        out[j, :n] = np.searchsorted(edges[j], X[:, j], side="left")
    return out


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


#: histogram implementation: "auto" (the kernel registry picks: the
#: Pallas contraction on a TPU, segment_sum elsewhere; see the
#: registrations at the end of this module), "segsum" (force the XLA
#: scatter-adds) or "pallas" (force the kernel; interpreted off a TPU).
HIST_IMPL = "auto"


@partial(jax.jit, static_argnames=("n_nodes", "d", "bins"))
def _level_histograms_segsum(cols, node_ids, grad, hess, n_nodes: int,
                             d: int, bins: int):
    """segment_sum form: one scatter-add per (feature, row) key.  It
    makes three (d, n) temporaries: for tables a host holds, not for one
    that fills a chip."""
    live = node_ids >= 0
    safe_node = jnp.where(live, node_ids, 0)
    # (node, feature, bin) -> flat key; dead rows land in a scratch key 0
    # with zero weights
    keys = (safe_node[None, :] * (d * bins)
            + jnp.arange(d, dtype=jnp.int32)[:, None] * bins
            + jnp.stack(list(cols)))                            # (d, n)
    w = live.astype(grad.dtype)
    seg = n_nodes * d * bins
    flat = keys.reshape(-1)

    def summed(x):
        return jax.ops.segment_sum(
            jnp.broadcast_to(x * w, keys.shape).reshape(-1), flat, seg)

    # the key is feature-major: (feature, node, bin) in memory order
    return (summed(grad).reshape(n_nodes, d, bins),
            summed(hess).reshape(n_nodes, d, bins))


def _level_histograms_pallas(cols, node_ids, grad, hess, n_nodes: int,
                             d: int, bins: int):
    """The exact one-hot contraction of ``ops/gbt_hist_pallas.py`` (the
    interpreter runs it off a TPU)."""
    from ...ops.gbt_hist_pallas import level_histograms

    return level_histograms(tuple(cols), node_ids, grad, hess, n_nodes, d,
                            bins, interpret=jax.default_backend() != "tpu")


#: the dispatch table — unknown HIST_IMPL values raise KeyError instead
#: of silently running the wrong implementation
_HIST_IMPLS = {"segsum": _level_histograms_segsum,
               "pallas": _level_histograms_pallas}


def resolve_hist_impl(name: str = None, sig: tuple = ()) -> str:
    """Resolve a histogram impl name ("auto" -> the kernel registry's
    pick for this backend and the level ``sig = (d, bins, n_nodes)``: the
    Pallas kernel only where its VMEM holds the level; "segsum"/"pallas"
    force) to a concrete ``_HIST_IMPLS`` key.  Unknown names raise
    KeyError — never a silent fallback."""
    name = HIST_IMPL if name is None else name
    if name == "auto":
        from ...kernels.registry import lookup

        backend = lookup("gbt_level_histograms", sig).backend
        return {"xla": "segsum"}.get(backend, backend)
    if name not in _HIST_IMPLS:
        raise KeyError(name)
    return name


def _level_histograms(cols, node_ids, grad, hess, n_nodes: int,
                      d: int, bins: int):
    """Per-(node, feature, bin) grad/hess sums for one level, each
    ``(n_nodes, d, bins)`` — the ADDITIVE piece of split finding: the
    out-of-core trainer accumulates these over streamed batches and
    decides splits from the totals.  ``cols`` are the ``d`` bin-id
    columns.  Dispatches on :data:`HIST_IMPL` through
    :func:`resolve_hist_impl`."""
    impl = resolve_hist_impl(sig=(d, bins, n_nodes))
    return _HIST_IMPLS[impl](cols, node_ids, grad, hess, n_nodes, d, bins)


def _above(hist):
    """Per bin, the sum of the bins above it (0 above the last)."""
    suffix = jnp.flip(jnp.cumsum(jnp.flip(hist, -1), axis=-1), -1)
    return jnp.concatenate([suffix[..., 1:], jnp.zeros_like(hist[..., :1])],
                           axis=-1)


def _level_splits(g_hist, h_hist, reg_lambda: float,
                  min_child_weight: float):
    """Best (feature, bin, gain) per node from the level histograms.

    A candidate split at bin b sends bins <= b left: the left side's
    (G, H) are the cumulative sums up to b, the right side's the sums of
    the bins above b, and the node's a feature's whole sum.  The right
    side is NOT the node's total less the left: where the bins above b
    hold no row it must be exactly 0 (no viable split), and at 10^8 rows
    a float32 total less a float32 prefix leaves units of rounding there,
    enough for a split of a few rows' noise to outscore every real one."""
    n_nodes, d, bins = g_hist.shape
    g_left = jnp.cumsum(g_hist, axis=2)
    h_left = jnp.cumsum(h_hist, axis=2)
    g_right, h_right = _above(g_hist), _above(h_hist)

    def score(g, h):
        return g * g / (h + reg_lambda)

    gain = (score(g_left, h_left) + score(g_right, h_right)
            - score(g_left[:, :, -1:], h_left[:, :, -1:]))       # (nodes,d,bins)
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


def _newton(g, h, reg_lambda: float):
    """The Newton leaf value ``-G / (H + lambda)``; 0 for a node no row
    reaches (``H + lambda`` 0)."""
    denom = h + reg_lambda
    return jnp.where(denom > 0, -g / jnp.where(denom > 0, denom, 1.0), 0.0)


#: a table lookup by a row's node or feature is a chain of selects up to
#: this many entries (one pass over the rows, no gather), a gather above
_SELECT_MAX = 64


def _take(table, idx):
    """``table[idx]`` for a small ``table`` and a long ``idx`` in range."""
    if table.shape[0] > _SELECT_MAX:
        return table[idx]
    out = jnp.broadcast_to(table[0], idx.shape)
    for c in range(1, table.shape[0]):
        out = jnp.where(idx == c, table[c], out)
    return out


def _row_bins(cols, feature):
    """Each row's bin of the feature ``feature`` (one per row)."""
    if len(cols) > _SELECT_MAX:
        return jnp.take_along_axis(jnp.stack(list(cols)), feature[None],
                                   0)[0]
    out = cols[0]
    for f in range(1, len(cols)):
        out = jnp.where(feature == f, cols[f], out)
    return out


def _apply_split(cols, node_ids, best_feature, best_bin, best_gain):
    """Route live rows through the level's chosen splits: 2*node (+1 for
    right) in the next level's local numbering, -1 where the node did not
    split."""
    live = node_ids >= 0
    safe_node = jnp.where(live, node_ids, 0)
    row_bin = _row_bins(cols, _take(best_feature, safe_node))
    goes_right = row_bin > _take(best_bin, safe_node)
    node_split = _take(best_gain > 0, safe_node)
    return jnp.where(live & node_split,
                     2 * safe_node + goes_right.astype(jnp.int32), -1)


# ---------------------------------------------------------------------------
# the binary in-core fit: one fused program
# ---------------------------------------------------------------------------

def _logistic_grad_hess(y, margins):
    p = jax.nn.sigmoid(margins)
    return p - y, jnp.maximum(p * (1.0 - p), 1e-12)


def _squared_grad_hess(y, margins):
    return margins - y, jnp.ones_like(margins)


#: the losses of the fused fit: ``(y, margins) -> (grad, hess)`` in float32
GRAD_HESS = {"logistic": _logistic_grad_hess, "squared": _squared_grad_hess}


def _logistic_grad_hess_host(y, margins):
    p = 0.5 * (1.0 + np.tanh(0.5 * margins))
    return p - y, np.maximum(p * (1.0 - p), 1e-12)


def _squared_grad_hess_host(y, margins):
    return margins - y, np.ones_like(margins)


#: the same losses in NumPy for the streamed fit, whose margins stay on
#: the host (a float64 memmap) and whose batches reach the device as
#: gradients: one device call a batch for two vectors it sends anyway
#: would add nothing but a dispatch
HOST_GRAD_HESS = {"logistic": _logistic_grad_hess_host,
                  "squared": _squared_grad_hess_host}


def _node_sums(node_ids, grad, hess, n_nodes: int):
    """``(G, H)`` of each of ``n_nodes`` nodes: the sums over the rows it
    holds (a row of node -1 is in none).  Up to ``_SELECT_MAX`` nodes one
    pass of the rows, a select per node and row reduced (no scatter: the
    fused fit's leaves); a ``segment_sum`` above."""
    if n_nodes > _SELECT_MAX:
        live = node_ids >= 0
        safe = jnp.where(live, node_ids, 0)
        w = live.astype(grad.dtype)
        return (jax.ops.segment_sum(grad * w, safe, n_nodes),
                jax.ops.segment_sum(hess * w, safe, n_nodes))
    hit = node_ids[None, :] == jnp.arange(n_nodes, dtype=jnp.int32)[:, None]
    return (jnp.sum(jnp.where(hit, grad[None, :], 0.0), axis=1),
            jnp.sum(jnp.where(hit, hess[None, :], 0.0), axis=1))


def boost_round(n_rows: int, d: int, config: GBTConfig, loss: str,
                hist_impl: str):
    """The body of the fused fit: one tree a round (program key stated,
    ``iteration/body.py: with_program_key``: everything the trace reads).

    State ``(margins (n,), feature, threshold, value)``, the last three
    ``(trees, nodes)``; data ``(cols, y)``: the ``d`` bin-id columns and
    the labels, ``n`` rows of which the first ``n_rows`` are the table's
    (the rest pad the kernel's blocks and stay in no node).  A round:
    ``gbt.grad`` the loss's gradient and hessian from the margins;
    ``max_depth`` levels of ``gbt.hist`` (the level histograms),
    ``gbt.split`` (best split per node, the Newton values of the nodes
    that stop) and ``gbt.route`` (rows to children); then, under
    ``gbt.route``, the Newton values of the last level's leaves from the
    sums of their rows (a child's sums as its parent's total less its
    sibling's would cancel where a leaf is small), each row's value and
    the margin update."""
    depth, bins = config.max_depth, config.max_bins
    lam, mcw = float(config.reg_lambda), float(config.min_child_weight)
    lr = float(config.learning_rate)
    grad_hess, histograms = GRAD_HESS[loss], _HIST_IMPLS[hist_impl]

    def body(state, tree, data):
        margins, feature, threshold, value = state
        cols, y = data
        with jax.named_scope("gbt.grad"):
            g, h = grad_hess(y, margins)
        ids = jnp.where(jnp.arange(margins.shape[0]) < n_rows, 0,
                        -1).astype(jnp.int32)
        row_value = jnp.zeros_like(margins)
        f_rows, t_rows, v_rows = [], [], []
        for level in range(depth):
            n_nodes = 2 ** level
            with jax.named_scope("gbt.hist"):
                g_hist, h_hist = histograms(cols, ids, g, h, n_nodes, d,
                                            bins)
            with jax.named_scope("gbt.split"):
                f, b, gain = _level_splits(g_hist, h_hist, lam, mcw)
                split = gain > 0
                stops = _newton(jnp.sum(g_hist, axis=(1, 2)) / d,
                                jnp.sum(h_hist, axis=(1, 2)) / d, lam)
                f_rows.append(jnp.where(split, f, -1))
                t_rows.append(b)
                v_rows.append(jnp.where(split, 0.0, stops))
            with jax.named_scope("gbt.route"):
                live = ids >= 0
                safe = jnp.where(live, ids, 0)
                row_value = jnp.where(live & ~_take(split, safe),
                                      _take(stops, safe), row_value)
                ids = _apply_split(cols, ids, f, b, gain)
        with jax.named_scope("gbt.route"):
            leaves = _newton(*_node_sums(ids, g, h, 2 ** depth), lam)
            row_value = jnp.where(ids >= 0, _take(leaves, jnp.maximum(ids, 0)),
                                  row_value)
            margins = margins + lr * row_value
            leaf_level = jnp.full((2 ** depth,), -1, jnp.int32)
            feature = feature.at[tree].set(jnp.concatenate(f_rows
                                                           + [leaf_level]))
            threshold = threshold.at[tree].set(jnp.concatenate(
                t_rows + [jnp.zeros((2 ** depth,), jnp.int32)]))
            value = value.at[tree].set(jnp.concatenate(v_rows + [leaves]))
        return margins, feature, threshold, value

    return with_program_key(body, boost_round, n_rows, d, depth, bins, lam,
                            mcw, lr, loss, hist_impl)


def _start(rows: int, trees: int, nodes: int, base_score: float):
    return (jnp.full((rows,), base_score, jnp.float32),
            jnp.full((trees, nodes), -1, jnp.int32),
            jnp.zeros((trees, nodes), jnp.int32),
            jnp.zeros((trees, nodes), jnp.float32))


_start_jit = jax.jit(_start, static_argnums=(0, 1, 2))


def train_forest(X: np.ndarray, y: np.ndarray, loss: str,
                 base_score: float, config: GBTConfig) -> Tuple[Forest, str]:
    """Boost ``num_trees`` trees of the loss ``loss`` (a key of
    :data:`GRAD_HESS`) from the margin ``base_score``: ``(forest, the
    histogram backend the fit took)``.

    On the host, under the span ``fit.arrange`` (which notes
    ``hist_impl`` and ``trees``): the bin edges and, under
    ``fit.arrange.bin``, the feature-major bin ids of every row
    (:func:`bin_features`' rule, :func:`bin_columns`), rows padded to the
    histogram kernel's blocks where the Pallas backend runs.  Under
    ``fit.upload`` each column and the labels go to the device as arrays
    of their own, a round of at most ``parallel/mesh.py: PUT_BYTES`` in
    flight (``put_in_rounds``): the bins are never copied on the device.
    Then the fused program (:func:`boost_round`) and, under
    ``fit.fetch``, the forest."""
    from ...parallel.mesh import default_mesh, put_in_rounds

    n, d = X.shape
    T, depth, bins = config.num_trees, config.max_depth, config.max_bins
    nodes = 2 ** (depth + 1) - 1
    with tracer.span("fit.arrange", "fit") as arrange:
        # one backend for every level: the widest level's pick
        impl = resolve_hist_impl(sig=(d, bins, 2 ** max(depth - 1, 0)))
        arrange.note(hist_impl=impl, trees=T)
        if impl == "pallas":
            from ...ops.gbt_hist_pallas import padded_rows

            rows = padded_rows(n)
        else:
            rows = n
        with tracer.span("fit.arrange.bin", "fit"):
            edges = quantile_edges(edge_sample(X), bins)
            host_cols = bin_columns(X, edges, rows)
            labels = np.zeros((rows,), np.float32)
            labels[:n] = y
    device = default_mesh().devices.flat[0]
    with tracer.span("fit.upload", "fit") as upload:
        *cols, labels = put_in_rounds(list(host_cols) + [labels], device)
        upload.note(pieces=d + 1)
        with jax.default_device(device):
            start = _start_jit(rows, T, nodes, float(base_score))
    del host_cols
    result = iterate(boost_round(n, d, config, loss, impl), HandedOver(start),
                     (tuple(cols), labels), max_epochs=T,
                     config=IterationConfig(mode="fused"))
    with tracer.span("fit.fetch", "fit"):
        feature, threshold, value = (np.asarray(a) for a in
                                     jax.device_get(result.state[1:]))
    return Forest(feature, threshold, value, edges, float(base_score),
                  config.learning_rate), impl


# ---------------------------------------------------------------------------
# the hosted trainers' pieces (multiclass, streamed)
# ---------------------------------------------------------------------------

@partial(aot_jit, static_argnames=("n_nodes", "d", "bins", "reg_lambda",
                                   "min_child_weight", "hist_impl"))
def _build_level(cols, node_ids, grad, hess, n_nodes: int,
                 d: int, bins: int, reg_lambda: float,
                 min_child_weight: float, hist_impl: str = "segsum"):
    """One tree level for all ``n_nodes`` nodes at once
    (histograms -> splits -> routing).

    Returns (feature (n_nodes,), threshold (n_nodes,), gain (n_nodes,),
    new_node_ids (n,)).  ``node_ids`` are level-local in [0, n_nodes) with
    -1 marking rows already settled in a leaf.
    """
    g_hist, h_hist = _HIST_IMPLS[resolve_hist_impl(
        hist_impl, (d, bins, n_nodes))](cols, node_ids, grad, hess,
                                        n_nodes, d, bins)
    best_feature, best_bin, best_gain = _level_splits(
        g_hist, h_hist, reg_lambda, min_child_weight)
    new_ids = _apply_split(cols, node_ids, best_feature, best_bin,
                           best_gain)
    return best_feature, best_bin, best_gain, new_ids


@partial(aot_jit, static_argnames=("n_nodes", "reg_lambda"))
def _leaf_values(node_ids, grad, hess, n_nodes: int, reg_lambda: float):
    """Newton leaf weights -G/(H+lambda) for every level-local node."""
    return _newton(*_node_sums(node_ids, grad, hess, n_nodes), reg_lambda)


@jax.jit
def _columns(binned):
    """The feature-major columns of a row-major ``(n, d)`` bin table."""
    return tuple(binned[:, f] for f in range(binned.shape[1]))


def _train_one_tree(binned, cols, g, h, d: int, config: GBTConfig):
    """Grow one tree against device gradients/hessians; returns the host
    (feature, threshold, value) node rows plus the tree's DEVICE in-sample
    prediction (margin scale, before learning-rate shrinkage).
    ``binned`` is the ``(n, d)`` table, ``cols`` its columns."""
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
        # key (the registry pick can differ across processes)
        f, b, gain, node_ids = _build_level(
            cols, node_ids, g, h, n_nodes, d, bins,
            config.reg_lambda, config.min_child_weight,
            hist_impl=resolve_hist_impl(sig=(d, bins, n_nodes)))
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
        gh, hh = _HIST_IMPLS[resolve_hist_impl(hist_impl, (d, bins,
                                                           n_nodes))](
            tuple(b[:, f] for f in range(d)), ids, g, h, n_nodes, d, bins)
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
        return None, _node_sums(ids, g, h, n_nodes)

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
    cols = _columns(binned)
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
                binned, cols, jnp.asarray(g, jnp.float32),
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
# kernel-registry entries: op ``gbt_level_histograms``, signature ``(d,
# bins, n_nodes)`` of a level.  On a TPU "auto" plans the Pallas
# contraction (``ops/gbt_hist_pallas.py``) wherever its VMEM holds the
# level: exact float32 sums, no (rows x features) temporary, every level
# of a 115 M-row table in one pass of the rows.  Elsewhere, and for a
# level too wide or deep for it, segment_sum.  Both feed the streamed
# histogram carry unchanged (accumulation over batches is a plain add
# either way).
# ---------------------------------------------------------------------------

def _register_gbt_kernels() -> None:
    from ...kernels.registry import register_kernel, tpu_only
    from ...ops.gbt_hist_pallas import supported

    register_kernel("gbt_level_histograms", "pallas",
                    _level_histograms_pallas, priority=10,
                    supports=supported, available=tpu_only)
    register_kernel("gbt_level_histograms", "xla", _level_histograms_segsum)


_register_gbt_kernels()
