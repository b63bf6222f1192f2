#!/usr/bin/env python3
"""The quickest proof that flink_ml_tpu still starts on the chip.

One process drives the main path once through the entry points a user
calls, at the full width of the Criteo-shaped logistic regression
(2^20 hashed features, 13 dense + 26 categorical columns, batch 32768;
weights start at zero, data comes from a seed):

- ``fit``     LogisticRegression.fit on 2^20 rows, one-device mesh
- ``serve``   serve_model on that model; requests of 1, 37 and 256 rows
- ``stream``  fit_outofcore over a DataCacheWriter cache of 2^18 rows
- ``kmeans``  KMeans(k=256).fit on 2^20 x 64 points, then transform; the
              stats kernel tiled over k at 2^17 x 784, k 4096 against
              float64 sums under the first-index assignment
- ``als``     ALS(rank 32).fit on 2^20 skewed ratings, the grouped normal
              equations by blocks, against a float64 solve of a sample
- ``mesh4``   the fit leg on a four-device data mesh, and KMeans.fit with its
              rows divided over it, bit for bit the one-device fit's
              centroids (only with >= 4 chips)

Each leg checks what came out: finite values of the expected shape, the
Pallas plan the chip should get, and agreement with NumPy.  The linear
legs are held to a float64 NumPy SGD slot by slot over all 2^20 weights
(two full-batch steps for ``fit`` and ``mesh4``, the whole streamed epoch
for ``stream``), so an update the ELL kernels drop or misplace on any
slot fails the leg.  Every leg always runs (``mesh4`` whenever JAX
reports four devices); any failure is a non-zero exit.

    python chip_smoke.py

Without a TPU it exits 1 before importing the package and prints no
result.  ``--cpu-rehearsal`` is for debugging this script in a sandbox:
reduced row counts, no Pallas-plan assertions, every line marked, no
result line, exit code 2 even when every leg passed.

On success the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The per-leg report also goes to ``chiprun_out/chip_smoke.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
NUM_FEATURES = 1 << 20
N_DENSE, N_CAT = 13, 26
NATIVE_LIBS = ("ell_layout", "datacache", "criteo", "als_plan")

# (rows, batch) per leg — rows only for kmeans: the chip run, and the
# --cpu-rehearsal cut
FULL = {"fit": (1 << 20, 1 << 15), "stream": (1 << 18, 1 << 13),
        "kmeans": 1 << 20, "als": 1 << 20}
REHEARSAL = {"fit": (1 << 14, 1 << 12), "stream": (1 << 13, 1 << 10),
             "kmeans": 1 << 16, "als": 1 << 14}

# One representative signature per registry op for the op -> backend
# table: the shapes this script runs where it runs the op, the Criteo
# shape elsewhere.  An op missing here is looked up with no signature.
OP_SIGNATURES = {
    "ell_margin": [("", (NUM_FEATURES // 128,))],
    "ell_scatter_apply": [("", (NUM_FEATURES // 128,))],
    "kmeans_update_stats": [("", (1 << 20, 64, 256, "euclidean")),
                            ("mnist8m", (2_025_000, 784, 4096, "euclidean"))],
    "kmeans_workset_update": [("", (1 << 20, 64, 256, "euclidean", 1))],
    "routed_table_grad": [("", ("gather", 13, 8192 * 26))],
    # (rank, groups): a users' block of the benchmark's ALS cell, and a
    # split group's one system a step
    "als_cholesky_solve": [("block", (100, 30_020)), ("part", (100, 1))],
    # (rows, width) at Criteo's cardinalities; width 0: the wide table
    "routed_adam_update": [("emb", (33_762_577, 16)),
                           ("wide", (33_762_577, 0))],
    # (nprobe, k, dim, m, ksub, nlist, block)
    "retrieve": [("flat", (16, 10, 128, 0, 0, 1024, 1024)),
                 ("pq", (16, 10, 128, 16, 16, 1024, 1024))],
}

_PREFIX = [""]


def say(text: str = "") -> None:
    for line in text.splitlines() or [""]:
        print(_PREFIX[0] + line, flush=True)


class SmokeFailure(AssertionError):
    """A leg's check did not hold."""


def check(condition, message: str) -> None:
    if not condition:
        raise SmokeFailure(message)


def criteo_rows(rows: int, seed: int):
    """Seeded Criteo-shaped rows (the examples/criteo_mixed_lr_example.py
    recipe): hashed indices start at 32, above the dense block's weight
    slots, and field 0 encodes the class in slots 16/17."""
    import numpy as np

    rng = np.random.default_rng(seed)
    dense = rng.normal(size=(rows, N_DENSE)).astype(np.float32)
    cat = rng.integers(32, NUM_FEATURES, size=(rows, N_CAT), dtype=np.int32)
    label = rng.integers(0, 2, size=rows).astype(np.float64)
    cat[:, 0] = np.where(label == 1, 16, 17)
    return dense, cat, label


def reference_margins(model, dense, cat):
    """NumPy float64 margins of a fitted linear model on mixed rows."""
    import numpy as np

    (data,) = model.get_model_data()
    w = np.asarray(data["coefficients"][0], np.float64)
    b = float(data["intercept"][0])
    return dense.astype(np.float64) @ w[:N_DENSE] + w[cat].sum(axis=1) + b


def check_linear_model(model, dense, cat, label, what: str) -> dict:
    """The fitted weights separate a sample, by an independent NumPy
    score: the class rides slots 16/17, so a working fit is near-perfect."""
    import numpy as np

    (data,) = model.get_model_data()
    w = np.asarray(data["coefficients"][0])
    check(w.shape == (NUM_FEATURES,), f"{what}: coefficients {w.shape}")
    check(np.isfinite(w).all(), f"{what}: non-finite coefficients")
    margins = reference_margins(model, dense, cat)
    accuracy = float(np.mean((margins > 0) == (label == 1)))
    signed = np.where(label == 1, margins, -margins)
    loss = float(np.mean(np.logaddexp(0.0, -signed)))
    check(accuracy >= 0.99, f"{what}: reference accuracy {accuracy}")
    check(loss < np.log(2.0), f"{what}: reference loss {loss} >= ln 2")
    return {"reference_accuracy": round(accuracy, 4),
            "reference_loss": round(loss, 4)}


def reference_sgd(batches, lr: float):
    """float64 NumPy minibatch SGD of logistic regression on mixed rows:
    LogisticRegression's algorithm at its defaults (mean log-loss of the
    batch, no regularisation, an intercept), from zero weights, one step
    per ``(dense, cat, label)`` batch in the order given.  Returns the
    weights with the intercept appended and, slot for slot, the total
    |update| each received: what the error a bf16 MXU pass may leave
    there scales with."""
    import numpy as np

    w, b, b_moved = np.zeros(NUM_FEATURES), 0.0, 0.0
    moved = np.zeros(NUM_FEATURES)
    for dense, cat, label in batches:
        dense = dense.astype(np.float64)
        margin = dense @ w[:N_DENSE] + w[cat].sum(axis=1) + b
        r = (1.0 / (1.0 + np.exp(-margin)) - label) / len(label)
        slots = cat.ravel()
        w -= lr * np.bincount(slots, np.repeat(r, N_CAT), NUM_FEATURES)
        moved += lr * np.bincount(slots, np.repeat(np.abs(r), N_CAT),
                                  NUM_FEATURES)
        w[:N_DENSE] -= lr * (r @ dense)
        moved[:N_DENSE] += lr * (np.abs(r) @ np.abs(dense))
        b -= lr * r.sum()
        b_moved += lr * np.abs(r).sum()
    return np.append(w, b), np.append(moved, b_moved)


def check_against_reference(model, batches, what: str) -> dict:
    """Every fitted weight against :func:`reference_sgd` over the same
    batches.  The ELL kernels gather residuals and weights through one
    bf16 MXU pass ("default" ``ell_precision``), which costs a slot up to
    2^-7 of each update it receives; 2^-6 of the slot's total |update| is
    allowed (on the chip the worst slot used 0.11 of that after two steps
    and 0.23 after the streamed epoch — PR 21).  A slot is touched a
    handful of times at most (two steps: once or twice; the streamed
    epoch: about six), so one update dropped from it or added to it is
    15-100% of that total, and a slot no row touches must still be zero."""
    import numpy as np

    from flink_ml_tpu.models.classification import LogisticRegression

    defaults = LogisticRegression()     # every fit here runs at these
    check(defaults.get_reg() == 0.0, f"{what}: the reference has no reg")
    w_ref, moved = reference_sgd(batches, defaults.get_learning_rate())
    (data,) = model.get_model_data()
    w = np.append(np.asarray(data["coefficients"][0], np.float64),
                  float(data["intercept"][0]))
    check(w.shape == w_ref.shape, f"{what}: coefficients {w.shape}")
    err, allowed = np.abs(w - w_ref), 2.0 ** -6 * moved + 1e-12
    worst = int(np.argmax(err - allowed))
    check(bool((err <= allowed).all()),
          f"{what}: weight {worst} is {float(w[worst])!r}, the NumPy "
          f"reference {float(w_ref[worst])!r} (allowed error "
          f"{allowed[worst]:.3g}); "
          f"{int((err > allowed).sum())} slots out of bound")
    touched = moved > 0
    return {"slots_touched": int(touched.sum()),
            "worst_error_over_allowed": round(float(
                (err[touched] / allowed[touched]).max()), 4)}


def fit_lr(table, batch: int, mesh):
    from flink_ml_tpu.models.classification import LogisticRegression
    from flink_ml_tpu.parallel.mesh import use_mesh

    with use_mesh(mesh):
        return (LogisticRegression().set_num_features(NUM_FEATURES)
                .set_global_batch_size(batch).set_max_iter(2).fit(table))


def check_two_steps(ctx, mesh, what: str) -> dict:
    """``LogisticRegression.fit`` on ``mesh`` over ONE batch of the leg's
    rows for two epochs — two full-batch steps, so the shuffle cannot
    matter; the second starts from non-zero weights, so the margin kernel
    counts too — against the NumPy reference, slot by slot."""
    from flink_ml_tpu import Table

    batch = ctx["batch"]
    dense, cat, label = (a[:batch] for a in ctx["rows"])
    model = fit_lr(Table({"features_dense": dense, "features_indices": cat,
                          "label": label}), batch, mesh)
    if ctx["chip"]:
        check(model.planned_impl == "ell",
              f"{what}: planned_impl={model.planned_impl!r}")
    return check_against_reference(model, [(dense, cat, label)] * 2, what)


def check_lr_fit(model, sample, chip: bool, what: str) -> dict:
    import numpy as np

    log = [float(v) for v in model.loss_log]
    check(len(log) == 2 and np.isfinite(log).all(), f"{what}: loss log {log}")
    check(log[1] < log[0], f"{what}: loss did not fall: {log}")
    if chip:
        check(model.planned_impl == "ell",
              f"{what}: planned_impl={model.planned_impl!r}, expected 'ell'")
    out = {"planned_impl": model.planned_impl,
           "loss_log": [round(v, 5) for v in log]}
    out.update(check_linear_model(model, *sample, what))
    return out


def leg_fit(ctx) -> dict:
    from flink_ml_tpu import Table
    from flink_ml_tpu.kernels.registry import lookup

    rows, batch = ctx["sizes"]["fit"]
    ctx["rows"] = dense, cat, label = criteo_rows(rows, seed=0)
    ctx["table"] = Table({"features_dense": dense, "features_indices": cat,
                          "label": label})
    ctx["sample"] = (dense[:4096], cat[:4096], label[:4096])
    ctx["batch"] = batch
    model = ctx["model"] = fit_lr(ctx["table"], batch, ctx["mesh1"])
    out = check_lr_fit(model, ctx["sample"], ctx["chip"], "fit")
    out["two_steps_vs_numpy"] = check_two_steps(ctx, ctx["mesh1"],
                                                "fit, two steps")
    for op in ("ell_margin", "ell_scatter_apply"):
        backend = lookup(op, sig=(NUM_FEATURES // 128,)).backend
        out[op] = backend
        if ctx["chip"]:
            check(backend.startswith("pallas"),
                  f"fit: registry resolved {op} to {backend!r}")
    return out


def leg_serve(ctx) -> dict:
    import numpy as np

    from flink_ml_tpu import Table
    from flink_ml_tpu.serving import serve_model
    from flink_ml_tpu.utils.backend import count_compiles

    check("model" in ctx, "serve needs the fit leg's model")
    model = ctx["model"]
    dense, cat, _ = ctx["sample"]
    requests = [Table({"features_dense": dense[:n],
                       "features_indices": cat[:n]}) for n in (1, 37, 256)]
    endpoint = serve_model(model, requests[0])     # deploys and warms up
    try:
        with count_compiles() as compiles:
            served = [endpoint.predict(r) for r in requests]
            after_warm_up = compiles()
    finally:
        endpoint.close()
    check(after_warm_up == 0,
          f"serve: {after_warm_up} compilations after warm-up "
          "(process-wide count)")
    for request, answer in zip(requests, served):
        n = request.num_rows
        offline = model.transform(request)[0]
        for col in ("prediction", "rawPrediction"):
            check(np.array_equal(answer[col], offline[col]),
                  f"serve: {col} of a {n}-row request != model.transform")
        margins = reference_margins(model, dense[:n], cat[:n])
        sure = np.abs(margins) > 1e-2
        check(np.array_equal(np.asarray(answer["prediction"])[sure],
                             (margins > 0).astype(np.int64)[sure]),
              f"serve: predictions of a {n}-row request != NumPy reference")
        check(np.allclose(answer["rawPrediction"],
                          1.0 / (1.0 + np.exp(-margins)), atol=1e-2),
              f"serve: probabilities of a {n}-row request != reference")
    return {"requests": [r.num_rows for r in requests],
            "compiles_after_warm_up": after_warm_up}


def leg_stream(ctx) -> dict:
    import numpy as np

    from flink_ml_tpu.data import DataCacheReader, DataCacheWriter
    from flink_ml_tpu.models.classification import LogisticRegression

    rows, batch = ctx["sizes"]["stream"]
    dense, cat, label = criteo_rows(rows, seed=1)
    info: dict = {}
    with tempfile.TemporaryDirectory(prefix="chip_smoke_cache_") as cache:
        writer = DataCacheWriter(cache, segment_rows=1 << 16)
        for start in range(0, rows, 1 << 16):
            stop = start + (1 << 16)
            writer.append({"features_dense": dense[start:stop],
                           "features_indices": cat[start:stop],
                           "label": label[start:stop]})
        writer.finish()
        model = (LogisticRegression().set_max_iter(1).fit_outofcore(
            lambda: DataCacheReader(cache, batch_rows=batch),
            num_features=NUM_FEATURES, mixed=True, mesh=ctx["mesh1"],
            stream_info=info))
    log = [float(v) for v in model.loss_log]
    check(len(log) == 1 and np.isfinite(log).all(),
          f"stream: loss log {log}")
    check(log[0] < np.log(2.0), f"stream: epoch loss {log[0]} >= ln 2")
    if ctx["chip"]:
        check(model.planned_impl == "ell-stream",
              f"stream: planned_impl={model.planned_impl!r}")
    out = {"planned_impl": model.planned_impl, "batches": rows // batch,
           "loss_log": [round(v, 5) for v in log]}
    out.update(check_linear_model(model, dense[:4096], cat[:4096],
                                  label[:4096], "stream"))
    # the reader owns the order: batch i is rows [i * batch, (i + 1) * batch)
    out["epoch_vs_numpy"] = check_against_reference(
        model, [(dense[i:i + batch], cat[i:i + batch], label[i:i + batch])
                for i in range(0, rows, batch)], "stream")
    return out


def leg_kmeans(ctx) -> dict:
    import numpy as np

    from flink_ml_tpu import Table
    from flink_ml_tpu.kernels.registry import lookup
    from flink_ml_tpu.models.clustering import KMeans
    from flink_ml_tpu.parallel.mesh import use_mesh

    rows = ctx["sizes"]["kmeans"]
    k, dim = 256, 64
    rng = np.random.default_rng(2)
    centers = (4.0 * rng.normal(size=(k, dim))).astype(np.float32)
    points = (centers[rng.integers(0, k, size=rows)]
              + rng.normal(size=(rows, dim)).astype(np.float32))
    backend = lookup("kmeans_update_stats",
                     sig=(rows, dim, k, "euclidean")).backend
    if ctx["chip"]:
        check(backend == "pallas",
              f"kmeans: the fit plans {backend!r}, expected 'pallas'")
    with use_mesh(ctx["mesh1"]):
        model = (KMeans().set_k(k).set_max_iter(5).set_seed(0)
                 .fit(Table({"features": points})))
        sample = points[:4096]
        assigned = np.asarray(
            model.transform(Table({"features": sample}))[0]["prediction"])
    (data,) = model.get_model_data()
    fitted = np.asarray(data["centroids"][0], np.float64)
    check(fitted.shape == (k, dim) and np.isfinite(fitted).all(),
          f"kmeans: centroids {fitted.shape}, finite="
          f"{bool(np.isfinite(fitted).all())}")
    sample64 = sample.astype(np.float64)
    p2 = (sample64 ** 2).sum(1)

    def sq_dists(centers):
        return (p2[:, None] - 2.0 * sample64 @ centers.T
                + (centers ** 2).sum(1)[None])

    c2 = (fitted ** 2).sum(1)
    d2 = sq_dists(fitted)
    check(assigned.shape == (len(sample),) and assigned.min() >= 0
          and assigned.max() < k, "kmeans: predictions out of range")
    agreement = float(np.mean(assigned == d2.argmin(1)))
    # the assign kernel scores with a default-precision MXU pass (operands
    # truncated to bf16), so a squared distance carries ~2^-8 (|p|^2+|c|^2)
    # of error and two centroids sharing one true cluster are a near-tie:
    # most points must get THE nearest centroid, every point one that is
    # nearest to within that error (a wrong cluster is ~2000 away)
    check(agreement >= 0.95,
          f"kmeans: transform agrees with NumPy on {agreement} of a sample")
    excess = d2[np.arange(len(sample)), assigned] - d2.min(1)
    slack = 2.0 ** -6 * (p2 + c2[assigned])
    check(bool((excess <= slack).all()),
          f"kmeans: an assigned centroid is {excess.max():.1f} farther than "
          f"the nearest (allowed {slack.max():.1f})")
    # Lloyd's must have moved toward the data: cheaper than k raw points
    # taken as centroids, which is what any take-k start costs
    cost = float(np.mean(d2.min(1)))
    start_cost = float(np.mean(
        sq_dists(points[-k:].astype(np.float64)).min(1)))
    check(cost < 0.75 * start_cost,
          f"kmeans: mean squared distance {cost} vs {start_cost} for k raw "
          "points")
    return {"fit_plan": backend, "reference_agreement": round(agreement, 4),
            "mean_sq_distance": round(cost, 2),
            "take_k_mean_sq_distance": round(start_cost, 2),
            "tiled_over_k": kmeans_tiled_over_k(ctx)}


def kmeans_tiled_over_k(ctx) -> dict:
    """The stats kernel tiled over k at ``kmeans_mnist8m``'s width (2^17 x
    784 whole grey levels, k 4096) against float64 sums of the
    bfloat16-rounded points under the first-index assignment, and the plan
    the fit would take there.  The sums of whole grey levels are exact;
    only the clusters that a row within float32's reach of a tie could
    touch are let off."""
    import jax.numpy as jnp
    import numpy as np

    from flink_ml_tpu.kernels.registry import lookup
    from flink_ml_tpu.ops.kmeans_pallas import (kmeans_update_stats,
                                                stats_tiles)

    n, d, k = ((1 << 17, 784, 4096) if ctx["chip"] else (1 << 10, 784, 64))
    rng = np.random.default_rng(3)
    # more shapes than centroids, or every row lies between near-twins
    protos = ((rng.random((2 * k, d)) < 0.19)
              * rng.integers(60, 256, size=(2 * k, d))).astype(np.float32)
    points = protos[rng.integers(0, 2 * k, size=n)]
    points = np.clip(np.rint(
        points * rng.uniform(0.6, 1.0, size=(n, 1))
        + (points > 0) * rng.integers(-16, 17, size=points.shape)),
        0, 255).astype(np.float32)
    cents = points[rng.permutation(n)[:k]].copy()
    cents[k - 2] = cents[1]           # an exact tie, a whole tile apart
    if ctx["chip"]:
        tiles = stats_tiles(d, k)
        backend = lookup("kmeans_update_stats",
                         sig=(2_025_000, d, k, "euclidean")).backend
        check(backend == "pallas" and tiles == (512, 512),
              f"kmeans: at 2,025,000 x {d}, k {k} the fit plans {backend!r} "
              f"at tiles {tiles}, expected 'pallas' at (512, 512)")
    else:
        tiles = (128, 16)
    sums, counts = kmeans_update_stats(
        jnp.asarray(points), jnp.asarray(cents), block_n=tiles[0],
        k_tile=tiles[1], tie_policy="first", interpret=not ctx["chip"])
    sums, counts = np.asarray(sums, np.float64), np.asarray(counts)
    rounded = np.asarray(jnp.asarray(cents).astype(jnp.bfloat16)
                         .astype(jnp.float32), np.float64)
    c2 = (cents.astype(np.float64) ** 2).sum(1)
    assign = np.empty(n, np.int64)
    loose = np.zeros(k, bool)
    for lo in range(0, n, 4096):
        sc = c2[None] - 2.0 * points[lo:lo + 4096].astype(
            np.float64) @ rounded.T
        assign[lo:lo + 4096] = sc.argmin(1)
        two = np.argpartition(sc, 1, axis=1)[:, :2]
        best, second = (sc[np.arange(len(sc)), two[:, i]] for i in (0, 1))
        near = (np.abs(best - second) < 8.0) & (best != second)
        loose[two[near].reshape(-1)] = True
    want_counts = np.bincount(assign, minlength=k)
    want_sums = np.zeros((k, d))
    np.add.at(want_sums, assign, points.astype(np.float64))
    check(loose.mean() < 0.2,
          f"kmeans tiled over k: {loose.mean():.3f} of the clusters lie "
          "within reach of a near-tie; the check would see too little")
    check(counts.sum() == n and counts[k - 2] == 0,
          f"kmeans tiled over k: {counts.sum()} rows counted of {n}, "
          f"{counts[k - 2]} on the second of two equal centroids")
    check(np.array_equal(counts[~loose], want_counts[~loose])
          and np.array_equal(sums[~loose], want_sums[~loose]),
          "kmeans tiled over k: counts or sums differ from the float64 "
          "sums under the first-index assignment")
    check(np.array_equal(sums.sum(0), points.sum(0, dtype=np.float64)),
          "kmeans tiled over k: the sums do not add up to the points")
    return {"tiles": list(tiles), "rows": n, "k": k,
            "clusters_near_a_tie": int(loose.sum())}


def leg_als(ctx) -> dict:
    """Explicit ALS-WR through ``ALS.fit`` with the grouped normal
    equations (``normalEquationsImpl='sorted'``): skewed users and items,
    one iteration from the seed's start, then the users of a sample
    (heaviest, lightest, a spread between) re-solved in float64 NumPy
    against the START's item factors, which is what the first half-epoch
    saw.  Then the solve by itself, op ``als_cholesky_solve`` as the
    registry picks it here (on the chip the kernel that keeps a tile of
    groups in VMEM), at the benchmark's rank 100 and this leg's 32 with a
    ragged last tile, against a float64 solve and its XLA twin."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flink_ml_tpu import Table
    from flink_ml_tpu.kernels.registry import lookup
    from flink_ml_tpu.models.recommendation.als import ALS
    from flink_ml_tpu.parallel.mesh import use_mesh

    rows = ctx["sizes"]["als"]
    users, items, rank, reg, seed = rows // 32, rows // 256, 32, 0.05, 3
    rng = np.random.default_rng(4)
    # Zipf-like popularity on both sides, distinct pairs
    u = np.minimum((users * rng.random(rows) ** 2.5).astype(np.int64),
                   users - 1)
    i = np.minimum((items * rng.random(rows) ** 2.5).astype(np.int64),
                   items - 1)
    pairs = np.unique(u * items + i)
    u, i = pairs // items, pairs % items
    r = rng.integers(1, 6, size=len(pairs)).astype(np.float32)
    with use_mesh(ctx["mesh1"]):
        model = (ALS().set_rank(rank).set_reg_param(reg).set_max_iter(1)
                 .set_seed(seed).set(ALS.NEQ_IMPL, "sorted")
                 .fit(Table({"user": u, "item": i, "rating": r})))
    check(model.neq_plan == "grouped",
          f"als: the fit planned {model.neq_plan!r}, expected 'grouped'")
    solve_plan = "vmem" if ctx["chip"] else "xla"
    check(model.solve_plan == solve_plan,
          f"als: the fit's blocks were solved by {model.solve_plan!r}, "
          f"expected {solve_plan!r}")
    (data,) = model.get_model_data()
    user_ids = np.asarray(data["userIds"][0])
    item_ids = np.asarray(data["itemIds"][0])
    fitted = np.asarray(data["userFactors"][0], np.float64)
    check(fitted.shape == (len(user_ids), rank)
          and np.isfinite(fitted).all()
          and np.isfinite(np.asarray(data["itemFactors"][0])).all(),
          f"als: factors {fitted.shape}, not all finite")
    # the start, by the estimator's documented rule
    start = np.random.default_rng(seed)
    start.normal(size=(len(user_ids), rank))
    v0 = (start.normal(size=(len(item_ids), rank)) / np.sqrt(rank)).astype(
        np.float32).astype(np.float64)
    u_pos, i_pos = np.searchsorted(user_ids, u), np.searchsorted(item_ids, i)
    counts = np.bincount(u_pos, minlength=len(user_ids))
    # the heaviest user, the lightest, and a spread between
    sample = np.unique(np.concatenate([
        [counts.argmax(), counts.argmin()],
        np.argsort(counts)[::max(1, len(counts) // 64)]]))
    worst = 0.0
    for g in sample:
        mine = u_pos == g
        y = v0[i_pos[mine]]
        a = y.T @ y + reg * max(mine.sum(), 1) * np.eye(rank)
        x = np.linalg.solve(a, y.T @ r[mine].astype(np.float64))
        worst = max(worst, float(np.abs(fitted[g] - x).max()
                                 / max(np.abs(x).max(), 1e-12)))
    check(worst < 1e-3,
          f"als: a user's factors are {worst:.2e} off a float64 solve")
    solve_gap = 0.0
    for solve_rank, groups in (((100, 1100), (32, 4001)) if ctx["chip"]
                               else ((100, 200), (32, 200))):
        y = rng.normal(size=(groups, solve_rank + 3, solve_rank)).astype(
            np.float32)
        a = np.einsum("gls,glt->gst", y, y) + np.float32(0.5) * np.eye(
            solve_rank, dtype=np.float32)
        b = rng.normal(size=(groups, solve_rank)).astype(np.float32)
        entry = lookup("als_cholesky_solve", sig=(solve_rank, groups))
        check(entry.backend == ("pallas" if ctx["chip"] else "xla"),
              f"als: the solve of {groups} groups resolved to "
              f"{entry.backend!r}")
        at, bt = jnp.transpose(jnp.asarray(a), (2, 1, 0)), jnp.asarray(b).T
        got = np.asarray(jax.jit(entry.fn)(at, bt)).T
        twin = np.asarray(jax.jit(
            lookup("als_cholesky_solve", backend="xla").fn)(at, bt)).T
        exact = np.linalg.solve(a.astype(np.float64),
                                b.astype(np.float64)[..., None])[..., 0]
        scale = np.abs(exact).max(axis=1, keepdims=True)
        gaps = (float(np.max(np.abs(got - exact) / scale)),
                float(np.max(np.abs(got - twin) / scale)))
        check(max(gaps) < 2e-5,
              f"als: the solve at rank {solve_rank} is {gaps[0]:.2e} off a "
              f"float64 solve and {gaps[1]:.2e} off its XLA twin")
        solve_gap = max(solve_gap, *gaps)
    return {"neq_plan": model.neq_plan, "solve_plan": model.solve_plan,
            "solve_gap": float(f"{solve_gap:.3g}"),
            "ratings": int(len(pairs)),
            "users": int(len(user_ids)), "items": int(len(item_ids)),
            "heaviest_user": int(counts.max()),
            "worst_relative_gap": float(f"{worst:.3g}")}


def leg_mesh4(ctx) -> dict:
    import jax
    import numpy as np

    from flink_ml_tpu.parallel.mesh import device_mesh

    check("model" in ctx, "mesh4 needs the fit leg's model")
    devices = jax.devices()[:4]
    for d in devices:
        d.memory_stats()        # fail here, not after the fit, if absent
    mesh = device_mesh(devices=devices)
    model = fit_lr(ctx["table"], ctx["batch"], mesh)
    out = check_lr_fit(model, ctx["sample"], ctx["chip"], "mesh4")
    # every device of the mesh held its own layout stack (12 bytes per
    # weight slot per step), so the epoch tensors' shards sat on four
    # distinct devices
    steps = ctx["table"].num_rows // ctx["batch"]
    shard_bytes = steps * NUM_FEATURES * 12
    peaks = [int(d.memory_stats()["peak_bytes_in_use"]) for d in devices]
    check(len({d.id for d in devices}) == 4 and min(peaks) >= shard_bytes,
          f"mesh4: per-device peak bytes {peaks}, expected >= "
          f"{shard_bytes} on each of four devices")
    (one,), (four,) = ctx["model"].get_model_data(), model.get_model_data()
    diff = float(np.max(np.abs(np.asarray(one["coefficients"])
                               - np.asarray(four["coefficients"]))))
    check(diff <= 1e-4, f"mesh4: weights differ from the one-device fit "
                        f"by {diff}")
    out.update({"max_weight_diff_vs_one_device": diff,
                "peak_bytes_per_device": peaks,
                "two_steps_vs_numpy": check_two_steps(
                    ctx, mesh, "mesh4, two steps"),
                "kmeans": kmeans_mesh4(ctx, mesh)})
    return out


def kmeans_mesh4(ctx, mesh) -> dict:
    """``KMeans.fit`` with the rows divided over the four-device ``data``
    mesh (every device its run of them flat, laid out and padded on the
    device; the kernel a shard, one all-reduce a step) against the fit on
    one device: whole-numbered points, so the shards' partial sums are
    exact in any order and the centroids must agree bit for bit."""
    import numpy as np

    from flink_ml_tpu import Table
    from flink_ml_tpu.models.clustering import KMeans
    from flink_ml_tpu.obs.trace import tracer
    from flink_ml_tpu.parallel.mesh import use_mesh

    rows, k, dim = ctx["sizes"]["kmeans"] + 5, 256, 64
    rng = np.random.default_rng(4)
    centers = 4.0 * rng.normal(size=(k, dim))
    table = Table({"features": np.rint(8.0 * (
        centers[rng.integers(0, k, size=rows)]
        + rng.normal(size=(rows, dim)))).astype(np.float32)})

    def fit(on):
        tracer.enable()
        try:
            with use_mesh(on):
                model = (KMeans().set_k(k).set_max_iter(5).set_seed(0)
                         .fit(table))
            (arrange,) = [s for s in tracer.find("fit.arrange")
                          if "shards" in s.ids]
        finally:
            tracer.disable()
            tracer.clear()
        (data,) = model.get_model_data()
        return np.asarray(data["centroids"][0]), arrange.ids

    one, _ = fit(ctx["mesh1"])
    four, notes = fit(mesh)
    check(notes["shards"] == 4, f"mesh4 kmeans: the fit noted {notes}")
    if ctx["chip"]:
        check(notes["stats_plan"] != "xla",
              f"mesh4 kmeans: the sharded fit planned {notes['stats_plan']}")
    check(np.isfinite(four).all() and four.tobytes() == one.tobytes(),
          "mesh4 kmeans: centroids differ from the one-device fit by "
          f"{float(np.max(np.abs(four - one)))}")
    return {"shards": notes["shards"], "stats_plan": notes["stats_plan"],
            "bit_for_bit_vs_one_device": True}


def rebuild_native() -> dict:
    """Delete native/build and load the libraries again: what loads is
    built from the committed .cpp files by this run, or the smoke fails."""
    from flink_ml_tpu.utils.native_lib import NATIVE_DIR, load_native_lib

    shutil.rmtree(os.path.join(NATIVE_DIR, "build"), ignore_errors=True)
    loaded = {}
    for name in NATIVE_LIBS:
        lib = load_native_lib(name)
        check(lib is not None, f"native library {name!r} did not load")
        loaded[name] = os.path.relpath(lib._name, HERE)
    return loaded


def backend_table() -> list:
    """op -> backend the registry chooses on this device, and why each
    other registered backend was not chosen (a forced-lookup-only entry
    with the reason its registration gives)."""
    from flink_ml_tpu.kernels import registry

    rows = []
    for op in registry.ops():
        for label, sig in OP_SIGNATURES.get(op, [("", ())]):
            chosen = registry.lookup(op, sig).backend
            others = []
            for backend in registry.backends(op):
                if backend == chosen:
                    continue
                entry = registry.lookup(op, backend=backend)
                if entry.forced_only is not None:
                    why = f"forced lookup only ({entry.forced_only})"
                elif not entry.supports_sig(sig):
                    why = "not for this signature"
                elif not entry.is_available():
                    why = "not on this platform"
                else:
                    why = "lower priority"
                others.append(f"{backend}: {why}")
            rows.append((f"{op}[{label}]" if label else op, chosen,
                         "; ".join(others)))
    return rows


LEGS = {"fit": leg_fit, "serve": leg_serve, "stream": leg_stream,
        "kmeans": leg_kmeans, "als": leg_als, "mesh4": leg_mesh4}


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def main(argv) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--cpu-rehearsal", action="store_true",
                        help="debug this script without a chip: reduced "
                             "rows, no result line, exit code 2")
    args = parser.parse_args(argv)
    if args.cpu_rehearsal:
        _PREFIX[0] = "[CPU REHEARSAL - not a chip run] "

    import jax
    import jax.monitoring

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    say(f"jax {jax.__version__}  platform={device['platform']}  "
        f"device_kind={device['kind']}  devices={device['count']}")
    chip = device["platform"] == "tpu"
    if not chip and not args.cpu_rehearsal:
        say(f"chip_smoke: JAX found platform={device['platform']!r}, not a "
            "TPU.  Nothing was run.")
        return 1

    sys.path.insert(0, HERE)
    from flink_ml_tpu.parallel.mesh import device_mesh
    from flink_ml_tpu.utils.backend import compile_count, enable_compile_cache

    cache_dir = enable_compile_cache()
    cold_entries = cache_entries(cache_dir)
    cache_events = {"/jax/compilation_cache/cache_hits": 0,
                    "/jax/compilation_cache/cache_misses": 0}

    def on_event(event: str, **_) -> None:
        if event in cache_events:
            cache_events[event] += 1

    jax.monitoring.register_event_listener(on_event)
    compile_count()                                  # start the counter

    t0 = time.perf_counter()
    native = rebuild_native()
    say(f"native libraries rebuilt in {time.perf_counter() - t0:.1f}s: "
        + ", ".join(f"{k} ({v})" for k, v in native.items()))

    ctx = {"chip": chip, "sizes": FULL if chip else REHEARSAL,
           "mesh1": device_mesh(devices=devices[:1])}
    report, failed = {}, []
    for name, run_leg in LEGS.items():
        if name == "mesh4" and len(devices) < 4:
            report[name] = {"status": "not run",
                            "why": f"{len(devices)} device(s), needs 4"}
            say(f"leg {name}: not run ({report[name]['why']})")
            continue
        t0 = time.perf_counter()
        try:
            result = run_leg(ctx)
            status = "passed"
        except Exception as exc:   # noqa: BLE001 — a leg boundary: the
            # other legs still run, the exit code reports the failure
            import traceback

            traceback.print_exc()
            result, status = {"error": repr(exc)[:500]}, "FAILED"
            failed.append(name)
        seconds = round(time.perf_counter() - t0, 1)
        report[name] = {"status": status, "wall_s": seconds, **result}
        say(f"leg {name}: {status} in {seconds}s  {json.dumps(result)}")

    say()
    say("op -> backend chosen on this device")
    table = backend_table()
    for op, chosen, others in table:
        say(f"  {op:24s} {chosen:7s} {others}")
    warm_entries = cache_entries(cache_dir)
    compile_report = {
        "dir": cache_dir, "entries_at_start": cold_entries,
        "entries_at_end": warm_entries,
        "new_entries": warm_entries - cold_entries,
        "backend_compiles": compile_count(),
        "persistent_cache_hits":
            cache_events["/jax/compilation_cache/cache_hits"],
        "persistent_cache_misses":
            cache_events["/jax/compilation_cache/cache_misses"]}
    say(f"compile cache: {json.dumps(compile_report)}")
    say("wall seconds per leg (set-up information, not a metric): "
        + ", ".join(f"{k}={v.get('wall_s', '-')}" for k, v in report.items()))

    out_dir = os.path.join(HERE, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "chip_smoke.json"), "w") as f:
        json.dump({"device": device, "chip_run": chip, "legs": report,
                   "native": native, "compile_cache": compile_report,
                   "op_backends": [list(r) for r in table]}, f, indent=1)

    if failed:
        say(f"chip_smoke: FAILED legs: {', '.join(failed)}")
        return 1
    if not chip:
        say("every leg passed, on the CPU: this proves nothing about the "
            "chip.  No result.")
        return 2
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
