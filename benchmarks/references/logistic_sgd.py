"""Plain reference for the mixed dense + categorical logistic regression.

``reference_sgd`` is chip_smoke.py's float64 NumPy minibatch SGD (copied,
not imported, so a later PR to the program cannot move the yardstick),
extended to an ordered multi-step epoch and to the epoch's loss: mean
log-loss of each batch at the weights it meets, no regularisation, an
intercept, zero start, one step per batch, the same epoch order replayed
every epoch.  Nothing here imports the program or takes anything it made.

The epoch order is the estimator's documented shuffle: rows permuted once
by ``numpy.random.default_rng(shuffle_seed).permutation(rows)`` and cut
into consecutive batches.  It is data the algorithm is defined over, not
arithmetic; the reference draws it itself.

What is compared (every weight the fit returned, at the timed sizes):

- ``weight_err``: over all weights and the intercept, the worst
  ``|w - w_ref| / moved`` where ``moved`` is the total |update| the slot
  received in the reference.  A slot no row touches has ``moved`` 0 and
  must be exactly 0.  One update dropped from, or added to, a slot that few
  rows touch is tens of percent of ``moved``.
- ``loss_gap``: the worst epoch's ``|loss - loss_ref| / loss_ref``.

``control`` is the same SGD with its state (weights and intercept) kept in
a lower precision, rounded after every step: the nearest precision below
the configuration's float32 state, put in the program's place.
"""

from __future__ import annotations

import numpy as np

TINY = 1e-12


def epoch_batches(data: dict, batch: int, shuffle_seed: int, epochs: int):
    """The ordered batches of ``epochs`` epochs; rows beyond the last whole
    batch are not expected (the configurations size rows to whole
    batches)."""
    dense, cat, label = (data["features_dense"], data["features_indices"],
                         data["label"])
    rows = len(label)
    if rows % batch:
        raise ValueError(f"{rows} rows are not whole batches of {batch}")
    perm = np.random.default_rng(shuffle_seed).permutation(rows)
    for _ in range(epochs):
        for start in range(0, rows, batch):
            take = perm[start:start + batch]
            yield dense[take], cat[take], label[take]


def _rounder(state_dtype):
    if state_dtype is None:
        return lambda a: a
    if state_dtype == "bfloat16":
        import ml_dtypes

        return lambda a: np.asarray(a).astype(ml_dtypes.bfloat16).astype(
            np.float64)
    raise ValueError(f"unknown control state dtype {state_dtype!r}")


def reference_sgd(batches, lr: float, num_features: int, n_dense: int,
                  steps_per_epoch: int, state_dtype=None):
    """Returns the weights with the intercept appended, slot for slot the
    total |update| each received, and the loss of every epoch."""
    w, b, b_moved = np.zeros(num_features), 0.0, 0.0
    moved = np.zeros(num_features)
    keep = _rounder(state_dtype)
    losses, epoch_losses = [], []
    for dense, cat, label in batches:
        n_cat = cat.shape[1]
        dense = dense.astype(np.float64)
        margin = dense @ w[:n_dense] + w[cat].sum(axis=1) + b
        signed = np.where(label == 1, margin, -margin)
        losses.append(float(np.mean(np.logaddexp(0.0, -signed))))
        r = (1.0 / (1.0 + np.exp(-margin)) - label) / len(label)
        slots = cat.ravel()
        w -= lr * np.bincount(slots, np.repeat(r, n_cat), num_features)
        moved += lr * np.bincount(slots, np.repeat(np.abs(r), n_cat),
                                  num_features)
        w[:n_dense] -= lr * (r @ dense)
        moved[:n_dense] += lr * (np.abs(r) @ np.abs(dense))
        b -= lr * r.sum()
        b_moved += lr * np.abs(r).sum()
        w, b = keep(w), float(keep(b))
        if len(losses) == steps_per_epoch:
            epoch_losses.append(float(np.mean(losses)))
            losses = []
    return np.append(w, b), np.append(moved, b_moved), epoch_losses


def _run(config: dict, data: dict, state_dtype=None, half_batch=False):
    ref = config["reference_params"]
    batch = int(ref["batch"])
    epochs = int(ref["epochs"])
    batches = epoch_batches(data, batch, int(ref["shuffle_seed"]), epochs)
    if half_batch:
        batches = (tuple(a[:batch // 2] for a in b) for b in batches)
    return reference_sgd(
        batches, float(ref["learning_rate"]), int(ref["num_features"]),
        int(ref["n_dense"]), len(data["label"]) // batch, state_dtype)


def compare(config: dict, data: dict, answer: dict, seed: int) -> dict:
    """The numbers compared, by name, for the answer a fit returned."""
    w_ref, moved, loss_ref = _run(config, data)
    w = np.append(np.asarray(answer["coefficients"], np.float64).ravel(),
                  float(np.asarray(answer["intercept"]).ravel()[0]))
    if w.shape != w_ref.shape:
        raise ValueError(f"the fit returned {w.shape} weights, the "
                         f"reference {w_ref.shape}")
    loss = np.asarray(answer["loss_log"], np.float64)
    if loss.shape != (len(loss_ref),):
        raise ValueError(f"the fit logged {loss.shape} epochs, the "
                         f"reference {len(loss_ref)}")
    with np.errstate(invalid="ignore"):
        weight_err = float(np.max(np.abs(w - w_ref) / (moved + TINY)))
        loss_gap = float(np.max(np.abs(loss - loss_ref) / np.abs(loss_ref)))
    # a NaN anywhere must fail, not vanish in a max
    if not (np.isfinite(w).all() and np.isfinite(loss).all()):
        weight_err = loss_gap = float("inf")
    return {"weight_err": weight_err, "loss_gap": loss_gap}


def control(config: dict, data: dict, seed: int) -> dict:
    """The reference in the control's precision, as an answer."""
    ref = config["reference_params"]
    w, _, losses = _run(config, data, ref["control_state_dtype"])
    return {"coefficients": w[:-1], "intercept": w[-1:],
            "loss_log": np.asarray(losses)}


FAULTS = ("unchanged", "half_batch", "altered")


def fault(config: dict, data: dict, seed: int, kind: str) -> dict:
    """The reference with one fault planted, as an answer: the state
    returned unchanged; half of every batch left out, the mean taken over
    the rest; one weight altered by a tenth of the updates it received."""
    if kind == "half_batch":
        w, _, losses = _run(config, data, half_batch=True)
    else:
        w, moved, losses = _run(config, data)
        if kind == "unchanged":
            w = np.zeros_like(w)
        elif kind == "altered":
            slot = int(np.argsort(moved)[len(moved) // 2 + seed % 1000])
            w[slot] += 0.1 * moved[slot] + 1e-6
        else:
            raise ValueError(kind)
    return {"coefficients": w[:-1], "intercept": w[-1:],
            "loss_log": np.asarray(losses)}
