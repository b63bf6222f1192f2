"""Plain reference for binary logistic boosting of histogram trees.

What XGBoost's ``hist`` method (Chen and Guestrin, KDD 2016; its GPU form
in arXiv:1806.11248) and Flink ML's GBTClassifier describe, written out
in ``jax.numpy``, float32, every contraction at
``reference_params.matmul_precision`` (``highest``):

- bins: per feature, the edges are ``np.quantile`` (float64, linear) at
  ``linspace(0, 1, bins + 1)[1:-1]`` of every ``ceil(n / edge_sample_rows)``
  -th row; a value's bin is the number of edges strictly below it, a
  NaN's the number of edges that are not NaN (``models/common/gbt.py:
  bin_features`` states the same rule; nothing of it is imported).  On
  the device each edge becomes the largest float32 not above it, which
  orders every float32 value as the float64 edge does;
- margins start at ``log(p / (1 - p))`` of the label's mean ``p``; a
  round grows one tree against ``g = sigmoid(m) - y``, ``h = max(sigmoid
  (m) (1 - sigmoid(m)), 1e-12)``;
- a tree is complete, ``max_depth`` levels of splits, node ``i``'s
  children ``2i+1`` / ``2i+2``.  At a level, each node's histograms of
  (g, h) over (feature, bin); a split "bin <= b goes left" scores
  ``G_L^2/(H_L+l) + G_R^2/(H_R+l) - G^2/(H+l)`` (the left side the bins
  up to b, the right side the bins above it, each summed over its own
  bins) with both sides' hessian at least ``min_child_weight`` and ``b``
  below the last bin;
  the best score of the node, the first (feature, bin) in feature-major
  order among equal ones, splits it where it is above 0;
- a node that does not split, and every node of the last level, is a
  leaf worth ``-G/(H+l)`` of its rows (0 where it has none); ``m += lr *
  leaf`` of every row.

Departures from the published description, all shared with the program's
documented rule: the edges come from a strided sample, not XGBoost's
weighted quantile sketch; no row or feature subsampling, no missing-value
direction, no pruning by ``gamma``; the tie rule above is this
benchmark's.  The histograms are one-hot contractions over blocks of
``block_rows`` rows (``(bins, block)`` 0/1 against the block's (g, h) a
node), so that the reference holds no more than a block of temporaries
beside the table; ``G, H`` of a node are the sums of its histogram of
feature by feature, a leaf's the direct sum over its rows.

What is compared.  A boosted forest is a chain of greedy choices: where
two splits score within rounding of each other, float32 sums in another
order pick the other, and every tree after it fits other residuals (a
sound fit of 115 M rows and a free-running reference ended 4% apart in
their margins on one seed of seven, and a lower precision no further).
So the reference is run ALONG the answer's decisions (``boost(follow=)``):
each node splits as the answer's tree says, every leaf takes the
reference's own Newton value of the rows that structure puts there, and
at every node the reference's score of every candidate split is kept, so
that the answer's own split is scored on the reference's histograms.
Then:

- ``margin_err``: ``|m - m_ref| / |m_ref - m_start|`` of the training
  margins: the answer's forest walked over the reference's bins against
  the reference's margins along the same structure (values alone differ);
- ``logloss_gap``: ``|L - L_ref| / L_ref`` of the training log-loss;
- ``split_mismatch``: nodes, over every tree, whose choice scores short
  of the reference's best by more than rounding, at EVERY node: a split
  whose score on the reference's histograms falls more than ``DECIDED``
  (1e-3) of the scale ``|best| + 2 |own|`` (the size of the three terms
  the score adds, ``own`` the node's ``G^2/(H+l)``) under the best or
  under 0, or that no rule allows (a side under ``min_child_weight``, the
  last bin); a leaf where the best split scores more than that above 0.
  So a split that cuts the node's rows as the best one does (the next
  bin of an empty one: quantile edges of a column of few values repeat)
  passes, and any worse one fails, tied node or not;
- ``first_tree_mismatch``: the same in the first tree;
- ``split_shortfall``: the largest such shortfall over every node, as a
  share of its scale (reported, not held: what ``DECIDED`` has of room).

``control`` is the reference with every gradient and hessian rounded to
bfloat16 before anything sums them: what a one-hot contraction at the
MXU's default precision does to a histogram.  The faults: every second
row left out (``half_rows``), the start margins returned (``start``: no
split, no value), and the reference's own forest with every TIED node's
split (its best and next best within ``DECIDED``) moved to the node's
worst allowed split, its values kept (``tied_splits``): what a program
that gets only the nodes a tie-skipping check does not look at wrong
would return.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

FAULTS = ("half_rows", "start", "tied_splits")


def _params(config: dict) -> dict:
    ref = config["reference_params"]
    return {"depth": int(ref["max_depth"]), "bins": int(ref["max_bins"]),
            "trees": int(ref["trees"]), "lr": float(ref["learning_rate"]),
            "lam": float(ref["reg_lambda"]),
            "mcw": float(ref["min_child_weight"]),
            "sample": int(ref["edge_sample_rows"]),
            "block": int(ref["block_rows"]),
            "precision": ref["matmul_precision"]}


def edges_of(X: np.ndarray, bins: int, sample_rows: int) -> np.ndarray:
    sample = X[::-(-len(X) // sample_rows)]
    qs = np.linspace(0, 1, bins + 1)[1:-1]
    return np.stack([np.quantile(np.asarray(sample[:, j], np.float64), qs)
                     for j in range(X.shape[1])])


def _float32_floor(edges: np.ndarray) -> np.ndarray:
    t = edges.astype(np.float32)
    return np.where(t.astype(np.float64) > edges,
                    np.nextafter(t, np.float32(-np.inf)), t)


@jax.jit
def _bin_column(x, thresholds, not_nan):
    count = jnp.zeros(x.shape, jnp.int32)
    for k in range(thresholds.shape[0]):
        count = count + (x > thresholds[k]).astype(jnp.int32)
    return jnp.where(jnp.isnan(x), not_nan, count)


def bin_table(X: np.ndarray, edges: np.ndarray, rows: int) -> tuple:
    """The columns of bin ids on the device, each ``(rows,)`` (zeros past
    the table's rows)."""
    out = []
    for j in range(X.shape[1]):
        col = np.zeros(rows, np.float32)
        col[:len(X)] = X[:, j]
        t = _float32_floor(edges[j])
        out.append(_bin_column(jnp.asarray(col), jnp.asarray(t),
                               int(np.sum(~np.isnan(edges[j])))))
    return tuple(out)


def _pick(table, index):
    """``table[index]`` as a sum of selects."""
    out = jnp.zeros(index.shape, table.dtype)
    for c in range(table.shape[0]):
        out = jnp.where(index == c, table[c], out)
    return out


@functools.partial(jax.jit, static_argnames=("n_nodes", "bins", "block",
                                             "precision"))
def histograms(cols, node, g, h, n_nodes: int, bins: int, block: int,
               precision: str):
    """``(d, bins, n_nodes)`` sums of g and of h."""
    nodes = jnp.arange(n_nodes, dtype=jnp.int32)[:, None]
    ids = jnp.arange(bins, dtype=jnp.int32)[:, None]

    def add_block(i, acc):
        s = i * block
        nd = jax.lax.dynamic_slice(node, (s,), (block,))
        hit = nd[None, :] == nodes
        w = jnp.concatenate([
            jnp.where(hit, jax.lax.dynamic_slice(g, (s,), (block,)), 0.0),
            jnp.where(hit, jax.lax.dynamic_slice(h, (s,), (block,)), 0.0)])
        onehot = jnp.concatenate(
            [jax.lax.dynamic_slice(col, (s,), (block,))[None, :] == ids
             for col in cols]).astype(jnp.float32)     # (d * bins, block)
        return acc + jax.lax.dot_general(
            onehot, w, (((1,), (1,)), ((), ())), precision=precision)

    acc = jax.lax.fori_loop(
        0, node.shape[0] // block, add_block,
        jnp.zeros((len(cols) * bins, 2 * n_nodes), jnp.float32))
    acc = acc.reshape(len(cols), bins, 2 * n_nodes)
    return acc[:, :, :n_nodes], acc[:, :, n_nodes:]


#: a split scoring within this share of its node's scale of the best is
#: as good as the best (see ``split_mismatch``)
DECIDED = 1e-3


@functools.partial(jax.jit, static_argnames=("lam", "mcw"))
def split_scores(gh, hh, lam: float, mcw: float):
    """Per node: the score of every (feature, bin) split, feature-major,
    ``-inf`` where no rule allows it, and the node's own ``G^2/(H+l)``."""
    d, bins, n_nodes = gh.shape
    G = jnp.sum(gh, axis=1, keepdims=True)          # (d, 1, nodes)
    H = jnp.sum(hh, axis=1, keepdims=True)
    GL, HL = jnp.cumsum(gh, axis=1), jnp.cumsum(hh, axis=1)
    # the right side summed over the bins above, so that it is 0 where
    # they hold no row
    above = jnp.triu(jnp.ones((bins, bins), jnp.float32), k=1)
    GR = jnp.einsum("bc,dcn->dbn", above, gh, precision="highest")
    HR = jnp.einsum("bc,dcn->dbn", above, hh, precision="highest")
    score = (GL * GL / (HL + lam) + GR * GR / (HR + lam)
             - G * G / (H + lam))
    ok = ((HL >= mcw) & (HR >= mcw)
          & (jnp.arange(bins)[None, :, None] < bins - 1))
    score = jnp.where(ok, score, -jnp.inf).reshape(d * bins, n_nodes)
    return score.T, G[0, 0] * G[0, 0] / (H[0, 0] + lam)


@functools.partial(jax.jit, static_argnames=("n_nodes", "lam"))
def node_values(node, g, h, n_nodes: int, lam: float):
    nodes = jnp.arange(n_nodes, dtype=jnp.int32)[:, None]
    G = jnp.sum(jnp.where(node[None, :] == nodes, g[None, :], 0.0), axis=1)
    H = jnp.sum(jnp.where(node[None, :] == nodes, h[None, :], 0.0), axis=1)
    return jnp.where(H + lam > 0, -G / jnp.where(H + lam > 0, H + lam, 1.0),
                     0.0)


@jax.jit
def route(cols, node, feature, threshold, splits):
    live = node >= 0
    safe = jnp.where(live, node, 0)
    f = _pick(feature, safe)
    row_bin = jnp.zeros(node.shape, jnp.int32)
    for j, col in enumerate(cols):
        row_bin = jnp.where(f == j, col, row_bin)
    go = live & _pick(splits, safe)
    return jnp.where(go, 2 * safe + (row_bin > _pick(threshold, safe)), -1)


@jax.jit
def _gradients(y, m):
    p = 1.0 / (1.0 + jnp.exp(-m))
    return p - y, jnp.maximum(p * (1.0 - p), 1e-12)


def base_score(y: np.ndarray) -> float:
    p = float(np.clip(np.mean(y, dtype=np.float64), 1e-6, 1 - 1e-6))
    return float(np.log(p / (1.0 - p)))


@jax.jit
def _bf16(x):
    """``x`` rounded to the nearest bfloat16, kept float32.  Not as a cast
    there and back: a TPU program may fuse the two casts and keep the
    float32 value (the control then read exactly what the reference did
    at this cell's size, on the chip); ``reduce_precision`` is kept."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def boost(X: np.ndarray, y: np.ndarray, p: dict, bf16_addends=False,
          follow=None) -> dict:
    """The boosting loop, as an answer with the reference's own numbers
    beside it.  Without ``follow`` every node splits at its best score:
    the reference's forest.  With ``follow`` (an answer over the same
    rows) every node splits as that answer's tree says, and a leaf takes
    the reference's Newton value of the rows that structure puts there:
    the reference's arithmetic along the answer's decisions.  Either way
    every split's score at every node is recorded (``scores``, ``(trees,
    internal nodes, d * bins)``; ``score``: the node's own ``G^2/(H+l)``),
    and the margins, the bins and the labels are kept for the
    comparison.  ``bf16_addends`` rounds every gradient and hessian to
    bfloat16 before anything sums them."""
    n, d = X.shape
    depth, bins, lam, mcw = p["depth"], p["bins"], p["lam"], p["mcw"]
    rows = -(-n // p["block"]) * p["block"]
    edges = edges_of(X, bins, p["sample"])
    cols = bin_table(X, edges, rows)
    labels = np.zeros(rows, np.float32)
    labels[:n] = y
    labels = jnp.asarray(labels)
    start = base_score(y)
    m = jnp.full((rows,), start, jnp.float32)
    first = jnp.where(jnp.arange(rows) < n, 0, -1).astype(jnp.int32)
    shape = (p["trees"], 2 ** (depth + 1) - 1)
    out = {"feature": np.full(shape, -1, np.int32),
           "threshold": np.zeros(shape, np.int32),
           "value": np.zeros(shape, np.float32),
           "scores": np.full((shape[0], 2 ** depth - 1, d * bins), -np.inf,
                             np.float32),
           "score": np.zeros(shape, np.float32)}
    with jax.default_matmul_precision(p["precision"]):
        for t in range(p["trees"]):
            g, h = _gradients(labels, m)
            if bf16_addends:
                g, h = _bf16(g), _bf16(h)
            node, leaf = first, jnp.zeros((rows,), jnp.float32)
            for level in range(depth + 1):
                n_nodes, base = 2 ** level, 2 ** level - 1
                at = (t, slice(base, base + n_nodes))
                vals = node_values(node, g, h, n_nodes, lam)
                if level < depth:
                    gh, hh = histograms(cols, node, g, h, n_nodes, bins,
                                        p["block"], p["precision"])
                    scores, node_score = (
                        np.asarray(a) for a in split_scores(gh, hh, lam, mcw))
                    out["scores"][at] = scores
                    out["score"][at] = node_score
                    if follow is None:
                        best = np.argmax(scores, axis=1).astype(np.int32)
                        f, b = best // bins, best % bins
                        splits = scores.max(axis=1) > 0
                    else:
                        f = np.asarray(follow["feature"])[at]
                        b = np.asarray(follow["threshold"])[at]
                        splits = f >= 0
                else:
                    f = b = np.zeros((n_nodes,), np.int32)
                    splits = np.zeros((n_nodes,), bool)
                sp = jnp.asarray(splits)
                stops = (node >= 0) & ~_pick(sp, jnp.maximum(node, 0))
                leaf = jnp.where(stops, _pick(vals, jnp.maximum(node, 0)),
                                 leaf)
                out["feature"][at] = np.where(splits, f, -1)
                out["threshold"][at] = b
                out["value"][at] = np.where(splits, 0.0, np.asarray(vals))
                if level < depth:
                    node = route(cols, node, jnp.asarray(np.maximum(f, 0)),
                                 jnp.asarray(b), sp)
            m = m + p["lr"] * leaf
    out.update(binEdges=edges, baseScore=start, learningRate=p["lr"],
               margins=m, cols=cols, labels=labels,
               live=jnp.arange(rows) < n)
    return out


def shortfalls(answer: dict, ref: dict, tree: int, bins: int) -> np.ndarray:
    """Per internal node of tree ``tree``: how far the answer's choice
    scores under the best, on ``ref``'s histograms (a ``boost`` along the
    answer's structure), as a share of the node's scale (see
    ``split_mismatch``); ``inf`` for a split no rule allows."""
    scores = ref["scores"][tree]
    nodes = len(scores)
    own = ref["score"][tree][:nodes]
    f = np.asarray(answer["feature"][tree])[:nodes]
    t = np.asarray(answer["threshold"][tree])[:nodes]
    best = scores.max(axis=1)
    with np.errstate(invalid="ignore"):
        scale = np.where(np.isfinite(best), np.abs(best), 0.0) + 2 * np.abs(
            own)
        chosen = scores[np.arange(nodes), np.maximum(f, 0) * bins + t]
        short = np.where(f >= 0, np.maximum(best, 0.0) - chosen,
                         np.maximum(best, 0.0))
        return np.where(short > 0, short / np.where(scale > 0, scale, 1.0),
                        0.0)


def split_mismatch(answer: dict, ref: dict, tree: int, bins: int) -> int:
    """Nodes of tree ``tree`` whose choice scores short of the best by
    more than ``DECIDED`` of their scale: see the module's docstring."""
    return int(np.sum(shortfalls(answer, ref, tree, bins) > DECIDED))


@functools.partial(jax.jit, static_argnames=("depth",))
def _tree_values(cols, feature, threshold, value, depth: int):
    """Each row's leaf value of one complete tree."""
    rows = cols[0].shape[0]
    node = jnp.zeros((rows,), jnp.int32)          # level-local index
    out = jnp.zeros((rows,), jnp.float32)
    done = jnp.zeros((rows,), bool)
    for level in range(depth + 1):
        base, n_nodes = 2 ** level - 1, 2 ** level
        f = _pick(feature[base:base + n_nodes], node)
        leaf = ~done & (f < 0)
        out = jnp.where(leaf, _pick(value[base:base + n_nodes], node), out)
        done = done | leaf
        if level < depth:
            row_bin = jnp.zeros((rows,), jnp.int32)
            for j, col in enumerate(cols):
                row_bin = jnp.where(f == j, col, row_bin)
            right = row_bin > _pick(threshold[base:base + n_nodes], node)
            node = jnp.where(done, 0, 2 * node + right.astype(jnp.int32))
    return out


def margins(answer: dict, cols, depth: int):
    m = jnp.full(cols[0].shape, float(answer["baseScore"]), jnp.float32)
    lr = float(answer["learningRate"])
    for t in range(np.asarray(answer["feature"]).shape[0]):
        m = m + lr * _tree_values(
            cols, jnp.asarray(answer["feature"][t], jnp.int32),
            jnp.asarray(answer["threshold"][t], jnp.int32),
            jnp.asarray(answer["value"][t], jnp.float32), depth)
    return m


@jax.jit
def _logloss_sum(m, y, live):
    loss = jnp.maximum(m, 0.0) - m * y + jnp.log1p(jnp.exp(-jnp.abs(m)))
    return jnp.sum(jnp.where(live, loss, 0.0))


#: what an answer is: the forest and what walking it needs
ANSWER = ("feature", "threshold", "value", "binEdges", "baseScore",
          "learningRate")


def _answer(out: dict) -> dict:
    """``boost``'s forest alone, so that the table it held on the device
    is freed before a comparison bins the table again."""
    return {k: out[k] for k in ANSWER}


def reference(config: dict, data: dict, seed: int) -> dict:
    """The reference's own forest, as an answer."""
    return _answer(boost(data["features"], data["label"], _params(config)))


def compare(config: dict, data: dict, answer: dict, seed: int) -> dict:
    """The numbers compared, by name, for the answer a fit returned: the
    reference boosted along the answer's structure (``boost(follow=)``)."""
    p = _params(config)
    names = ("margin_err", "logloss_gap", "split_mismatch",
             "first_tree_mismatch", "split_shortfall", "logloss",
             "logloss_ref")
    trees = (p["trees"], 2 ** (p["depth"] + 1) - 1)
    if any(np.asarray(answer[k]).shape != trees
           for k in ("feature", "threshold", "value")):
        return dict.fromkeys(names, float("inf"))
    X, y = data["features"], data["label"]
    ref = boost(X, y, p, follow=answer)
    live, labels, m_ref = ref["live"], ref["labels"], ref["margins"]
    m = margins(answer, ref["cols"], p["depth"])
    gap = np.sqrt(float(jnp.sum(jnp.where(live, (m - m_ref) ** 2, 0.0))))
    moved = np.sqrt(float(jnp.sum(jnp.where(
        live, (m_ref - ref["baseScore"]) ** 2, 0.0))))
    n = len(y)
    loss = float(_logloss_sum(m, labels, live)) / n
    loss_ref = float(_logloss_sum(m_ref, labels, live)) / n
    mismatch = [split_mismatch(answer, ref, t, p["bins"])
                for t in range(p["trees"])]
    shortfall = max(float(shortfalls(answer, ref, t, p["bins"]).max())
                    for t in range(p["trees"]))
    with np.errstate(invalid="ignore", divide="ignore"):
        out = dict(zip(names, (gap / moved, abs(loss - loss_ref) / loss_ref,
                               float(sum(mismatch)), float(mismatch[0]),
                               shortfall, loss, loss_ref)))
    return {k: v if np.isfinite(v) else float("inf") for k, v in out.items()}


def control(config: dict, data: dict, seed: int, precision=None) -> dict:
    """The reference with every gradient and hessian rounded to bfloat16
    before it is summed."""
    return _answer(boost(data["features"], data["label"], _params(config),
                         bf16_addends=True))


def tied_nodes(ref: dict) -> np.ndarray:
    """``(trees, internal nodes)``: where the reference's own forest split
    a node whose best and next best scores lie within ``DECIDED`` of its
    scale."""
    top = -np.sort(-ref["scores"], axis=2)[:, :, :2]
    own = ref["score"][:, :top.shape[1]]
    with np.errstate(invalid="ignore"):
        scale = np.abs(top[:, :, 0]) + 2 * np.abs(own)
        near = top[:, :, 0] - top[:, :, 1] <= DECIDED * scale
    return near & (ref["feature"][:, :top.shape[1]] >= 0)


def fault(config: dict, data: dict, seed: int, kind: str) -> dict:
    """The reference with one fault planted, as an answer: every second
    row left out; the start margins returned (no split, no value); every
    tied node's split moved to the node's worst allowed one."""
    p = _params(config)
    if kind == "half_rows":
        return _answer(boost(data["features"][::2], data["label"][::2], p))
    if kind == "tied_splits":
        out = boost(data["features"], data["label"], p)
        tied = tied_nodes(out)
        worst = np.argmin(np.where(np.isfinite(out["scores"]),
                                   out["scores"], np.inf), axis=2)
        nodes = tied.shape[1]
        out["feature"][:, :nodes] = np.where(tied, worst // p["bins"],
                                             out["feature"][:, :nodes])
        out["threshold"][:, :nodes] = np.where(tied, worst % p["bins"],
                                               out["threshold"][:, :nodes])
        return dict(_answer(out), tied=int(tied.sum()))
    if kind != "start":
        raise ValueError(kind)
    shape = (p["trees"], 2 ** (p["depth"] + 1) - 1)
    return {"feature": np.full(shape, -1, np.int32),
            "threshold": np.zeros(shape, np.int32),
            "value": np.zeros(shape, np.float32),
            "binEdges": edges_of(data["features"], p["bins"], p["sample"]),
            "baseScore": base_score(data["label"]),
            "learningRate": p["lr"]}
