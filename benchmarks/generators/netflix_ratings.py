"""Netflix-Prize-shaped explicit ratings: ``(user, item, rating)`` rows.

What is taken from the source (the Prize's training set as cuMF's data set
table has it) are its SHAPES: integer ratings 1-5, a heavy-tailed number
of ratings a user (log-normal fitted to the median and mean the
configuration gives, clipped to ``[user_degree_min, user_degree_max]`` and
to the number of items), a heavier-tailed popularity of the items
(log-normal weights fitted the same way; an item's count follows from the
users' draws), every ``(user, item)`` pair distinct, exactly ``rows``
pairs.  What a rating is drawn from is this benchmark's: a planted
low-rank model (``planted_rank`` factors a side, a user and an item
offset) plus Gaussian noise, rounded and clipped to 1-5, so that ALS has
something to fit.

User and item ids are arbitrary distinct int64 labels, not ``0..n-1``
(the Prize's are customer and movie numbers with gaps), and the rows come
in a random order.  Columns as ``ALS`` reads them by default: ``user``,
``item`` (int64), ``rating`` (float32).

Every stage draws from its own child of ``SeedSequence(seed)``, and the
two long stages (the light users' items, the ratings) from one child for
each of ``PARTS`` fixed parts, so the rows do not depend on how many
threads ran.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

THREADS = min(12, os.cpu_count() or 1)
PARTS = 24

#: a user who rates more than this share of the catalogue draws their
#: items exactly (the smallest keys of one exponential race over all the
#: items); everyone else draws with replacement and redraws collisions
EXACT_SHARE = 0.1


def _lognormal(rng, size: int, median: float, mean: float) -> np.ndarray:
    sigma = np.sqrt(2.0 * np.log(mean / median))
    return rng.lognormal(np.log(median), sigma, size)


def user_degrees(rng, params: dict) -> np.ndarray:
    """Ratings a user: a clipped log-normal whose sum is exactly ``rows``
    (the rounding's remainder is spread one rating at a time over users
    drawn at random, inside the clip)."""
    users, rows = int(params["users"]), int(params["rows"])
    lo = int(params["user_degree_min"])
    hi = min(int(params["user_degree_max"]), int(params["items"]))
    raw = _lognormal(rng, users, float(params["user_degree_median"]),
                     float(params["user_degree_mean"]))
    raw *= rows / raw.sum()
    deg = np.clip(np.rint(raw), lo, hi).astype(np.int64)
    while (gap := rows - int(deg.sum())) != 0:
        step = 1 if gap > 0 else -1
        room = np.flatnonzero(deg < hi if gap > 0 else deg > lo)
        if room.size == 0:
            raise ValueError(f"{rows} ratings do not fit {users} users "
                             f"of {lo} to {hi} ratings")
        pick = rng.choice(room, size=min(abs(gap), room.size), replace=False)
        deg[pick] += step
    return deg


def _exact_items(rng, weights: np.ndarray, degrees: np.ndarray) -> np.ndarray:
    """For each heavy user the ``degree`` items with the smallest keys
    ``Exp(1) / weight``: a weighted draw without replacement.  Returns the
    items of all of them, user after user."""
    out = []
    for start in range(0, len(degrees), 256):
        deg = degrees[start:start + 256]
        keys = rng.standard_exponential(
            (len(deg), len(weights)), dtype=np.float32) / weights[None, :]
        ranked = np.argsort(keys, axis=1)
        out.extend(ranked[u, :d] for u, d in enumerate(deg))
    return (np.concatenate(out) if out else np.empty(0, np.int64)).astype(
        np.int64)


def _light_items(rng, cdf: np.ndarray, users: np.ndarray,
                 degrees: np.ndarray, n_items: int) -> tuple:
    """``(user, item)`` for the light users: rounds of draws with
    replacement by the items' popularity, a collision with a pair already
    held redrawn in the next round, until every user holds ``degree``
    distinct items.  Only the users still short take part in a round."""
    done = []                                # keys user * n_items + item
    held = np.empty(0, np.int64)             # those of the users still short
    need = degrees.copy()
    while need.sum():
        # half more than needed, so that most users finish in a round
        ask = need + (need + 1) // 2 + 2 * (need > 0)
        who = np.repeat(np.arange(len(users)), ask)
        item = np.searchsorted(cdf, rng.random(len(who)), side="right")
        keys = np.concatenate(
            [held, who * n_items + np.minimum(item, n_items - 1)])
        # one stable sort: a draw is new if no equal key stands before it,
        # and the held keys stand first
        order = np.argsort(keys, kind="stable")
        ranked = keys[order]
        fresh = np.ones(len(keys), bool)
        fresh[order[1:]] = ranked[1:] != ranked[:-1]
        fresh[:len(held)] = False
        keys = keys[fresh]                   # still in draw order
        who = keys // n_items                # ascending: drawn user by user
        starts = np.searchsorted(who, np.arange(len(users)))
        take = np.arange(len(who)) - starts[who] < need[who]
        need = need - np.bincount(who[take], minlength=len(users))
        held = np.concatenate([held, keys[take]])
        short = need[held // n_items] > 0
        done.append(held[~short])
        held = held[short]
    keys = np.sort(np.concatenate(done))
    return users[keys // n_items], keys % n_items


def generate(params: dict, seed: int) -> dict:
    users, items = int(params["users"]), int(params["items"])
    rows = int(params["rows"])
    if rows > users * items:
        raise ValueError("more ratings than (user, item) pairs")
    seeds = np.random.SeedSequence(int(seed)).spawn(8)
    rng_deg, rng_pop, rng_exact, rng_model, rng_ids, rng_order = (
        np.random.default_rng(s) for s in seeds[:6])
    light_seeds, rating_seeds = seeds[6].spawn(PARTS), seeds[7].spawn(PARTS)

    degrees = user_degrees(rng_deg, params)
    weights = np.clip(
        _lognormal(rng_pop, items, float(params["item_count_median"]),
                   float(params["item_count_mean"])),
        float(params["item_count_min"]), float(params["item_count_max"]))
    weights = (weights / weights.sum()).astype(np.float32)
    heavy = degrees > EXACT_SHARE * items
    heavy_users = np.flatnonzero(heavy)
    u_heavy = np.repeat(heavy_users, degrees[heavy_users])
    i_heavy = _exact_items(rng_exact, weights, degrees[heavy_users])
    cdf = np.cumsum(weights, dtype=np.float64)
    with ThreadPoolExecutor(THREADS) as pool:
        light = list(pool.map(
            lambda part, s: _light_items(np.random.default_rng(s), cdf, part,
                                         degrees[part], items),
            np.array_split(np.flatnonzero(~heavy), PARTS), light_seeds))
    u = np.concatenate([u_heavy] + [part[0] for part in light])
    i = np.concatenate([i_heavy] + [part[1] for part in light])
    del u_heavy, i_heavy, light

    # the planted model, a part of the ratings at a time
    k = int(params["planted_rank"])
    p = rng_model.standard_normal((users, k), dtype=np.float32)
    q = rng_model.standard_normal((items, k), dtype=np.float32)
    user_offset = rng_model.standard_normal(users, dtype=np.float32)
    item_offset = rng_model.standard_normal(items, dtype=np.float32)
    signal = float(params["planted_scale"]) / np.sqrt(k)
    offset_scale = float(params["offset_scale"])
    rating = np.empty(rows, np.float32)

    def rate(part: np.ndarray, s) -> None:
        rng = np.random.default_rng(s)
        for at in np.array_split(part, max(1, len(part) >> 20)):
            uu, ii = u[at], i[at]
            value = (float(params["rating_mean"])
                     + signal * np.einsum("nk,nk->n", p[uu], q[ii])
                     + offset_scale * (user_offset[uu] + item_offset[ii])
                     + float(params["rating_noise"])
                     * rng.standard_normal(len(at), dtype=np.float32))
            rating[at] = np.clip(np.rint(value), 1.0, 5.0)

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(rate, np.array_split(np.arange(rows), PARTS),
                      rating_seeds))

    # arbitrary labels, and the rows in a random order
    def labels(n: int) -> np.ndarray:
        drawn = np.unique(rng_ids.integers(1, 1 << 40, size=n + n // 8 + 16))
        return rng_ids.permutation(drawn)[:n].astype(np.int64)

    user_labels, item_labels = labels(users), labels(items)
    order = rng_order.permutation(rows)
    return {"user": user_labels[u[order]], "item": item_labels[i[order]],
            "rating": rating[order]}
