"""Criteo-shaped rows with every categorical field at its own cardinality.

The draws of ``criteo_zipf`` (a bounded Zipf law by rank within each
field, standard-normal dense columns, the same label model), but the
categorical column holds the per-field RANKS themselves, ``(rows, n_cat)``
int32 with field ``f`` in ``[0, vocab_sizes[f])``: nothing is hashed into
a shared range.  Columns are named as ``WideDeep`` reads them by default:
``denseFeatures``, ``catFeatures``, ``label``.

Every field and the dense block draw from their own child of
``SeedSequence(seed)``, so the rows do not depend on how many threads ran.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from generators.criteo_zipf import THREADS, zipf_ranks


def generate(params: dict, seed: int) -> dict:
    rows = int(params["rows"])
    n_dense = int(params["n_dense"])
    cards = [int(c) for c in params["vocab_sizes"]]
    exponent = float(params["zipf_exponent"])
    label_fields = {int(f): float(c) for f, c in params["label_fields"]}
    children = np.random.SeedSequence(int(seed)).spawn(len(cards) + 2)

    cat = np.empty((rows, len(cards)), np.int32)
    logit_parts = {}

    def draw_field(f: int) -> None:
        rng = np.random.default_rng(children[f])
        ranks = zipf_ranks(rng, rows, cards[f], exponent)
        cat[:, f] = ranks
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
        np.float32)
    return {"denseFeatures": dense, "catFeatures": cat, "label": label}
