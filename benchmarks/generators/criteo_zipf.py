"""Criteo-shaped rows with skewed keys, from a seed.

One row is ``n_dense`` float32 (standard normal), one hashed slot for each
categorical field, and a {0, 1} label.  Each field draws a VALUE by rank
from a bounded Zipf law over the field's published cardinality and hashes
``(field, value)`` into ``[hash_floor, num_features)``.

The Zipf draw is an inverse CDF: ranks up to ``HEAD`` come from an exact
table of ``r ** -s``; the tail beyond it from the integral of ``x ** -s``
over ``[r - 0.5, r + 0.5)``, inverted in closed form.  That costs a few
vector operations per field instead of a search in a table of up to 10^7
entries (see PERF.md, section 4, for what it cost on the chip's host).

The label: ``p = sigmoid(bias + dense @ a + sum_f c_f * sign_f(rank))`` over the
fields in ``label_fields``, where ``sign_f`` is +1 for an even rank and -1
for an odd one, and the label is a Bernoulli draw of ``p``.  Rank 0, the
heaviest key of such a field, therefore carries signal, and so does the
dense block: the loss falls under both the heavy path and the ELL grid.

Every field and the dense block draw from their own child of
``SeedSequence(seed)``, so the rows do not depend on how many threads ran.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

HEAD = 1024
THREADS = min(12, os.cpu_count() or 1)
_MULT = np.uint64(0x9E3779B97F4A7C15)
_FIELD_MULT = np.uint64(0xBF58476D1CE4E5B9)


def zipf_ranks(rng: np.random.Generator, rows: int, cardinality: int,
               exponent: float) -> np.ndarray:
    """``rows`` zero-based ranks in ``[0, cardinality)``, P(rank r) roughly
    proportional to ``(r + 1) ** -exponent``."""
    s = float(exponent)
    head = min(HEAD, cardinality)
    head_mass = np.cumsum(np.arange(1, head + 1, dtype=np.float64) ** -s)
    lo, hi = head + 0.5, cardinality + 0.5

    def integral(x):
        return (x ** (1.0 - s) - 1.0) / (1.0 - s)

    tail_mass = integral(hi) - integral(lo) if cardinality > head else 0.0
    u = rng.random(rows) * (head_mass[-1] + tail_mass)
    ranks = np.searchsorted(head_mass, u, side="right").astype(np.int64)
    in_tail = u >= head_mass[-1]
    if tail_mass > 0.0 and in_tail.any():
        t = integral(lo) + (u[in_tail] - head_mass[-1])
        x = (1.0 + (1.0 - s) * t) ** (1.0 / (1.0 - s))
        ranks[in_tail] = np.floor(x + 0.5).astype(np.int64) - 1
    return np.minimum(ranks, cardinality - 1)


def hash_slots(field: int, values: np.ndarray, num_features: int,
               hash_floor: int) -> np.ndarray:
    """``(field, value)`` into ``[hash_floor, num_features)``: a 64-bit
    multiplicative hash, its high bits folded into the range."""
    with np.errstate(over="ignore"):
        mixed = (values.astype(np.uint64) + np.uint64(1)) * _MULT \
            + np.uint64(field + 1) * _FIELD_MULT
    span = np.uint64(num_features - hash_floor)
    return ((mixed >> np.uint64(24)) % span).astype(np.int32) + hash_floor


def generate(params: dict, seed: int) -> dict:
    rows = int(params["rows"])
    n_dense = int(params["n_dense"])
    cards = [int(c) for c in params["field_cardinalities"]]
    num_features = int(params["num_features"])
    hash_floor = int(params["hash_floor"])
    exponent = float(params["zipf_exponent"])
    label_fields = {int(f): float(c) for f, c in params["label_fields"]}
    children = np.random.SeedSequence(int(seed)).spawn(len(cards) + 2)

    by_field = np.empty((len(cards), rows), np.int32)
    logit_parts = {}

    def draw_field(f: int) -> None:
        rng = np.random.default_rng(children[f])
        ranks = zipf_ranks(rng, rows, cards[f], exponent)
        by_field[f] = hash_slots(f, ranks, num_features, hash_floor)
        if f in label_fields:
            logit_parts[f] = label_fields[f] * (1.0 - 2.0 * (ranks & 1))

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(draw_field, range(len(cards))))

    rng = np.random.default_rng(children[-2])
    dense = rng.standard_normal((rows, n_dense), dtype=np.float32)
    a = np.asarray(params["label_dense_coefficients"], np.float32)
    logit = (dense @ a).astype(np.float64) + float(params["label_bias"])
    for f in sorted(logit_parts):
        logit += logit_parts[f]
    p = 1.0 / (1.0 + np.exp(-logit))
    label = (np.random.default_rng(children[-1]).random(rows) < p).astype(
        np.float64)
    return {"features_dense": dense,
            "features_indices": np.ascontiguousarray(by_field.T),
            "label": label}
