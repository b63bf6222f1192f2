"""Plain reference for explicit-feedback ALS with weighted-lambda
regularisation (ALS-WR).

Zhou, Wilkinson, Schreiber, Pan 2008 ("Large-scale Parallel Collaborative
Filtering for the Netflix Prize"), as cuMF_als (arXiv:1603.03820) runs it:
with the items' factors ``Y`` fixed, every user ``u`` with ``n_u`` ratings
``r_ui`` takes

    x_u = (sum_i y_i y_i^T + lambda * n_u * I)^-1  sum_i r_ui y_i

over the items they rated; then the items the same against the new users'
factors; that is one iteration.  A group with no rating keeps its factors.

Written to share no idea with a sparse implementation: the normal
equations are the DENSE masked ones, a block of groups at a time.  A
block's ratings are scattered into a dense mask ``M`` and rating matrix
``R`` of shape ``(block, n_other)``; ``A = M @ (Y (x) Y)`` is one matrix
product against the rows' outer products, the other side taken
``block_other`` rows at a time so that ``Y (x) Y`` fits; ``b = R @ Y``;
``jnp.linalg.solve`` (LU).  Plain ``jax.numpy`` in float32, contractions
at ``reference_params.matmul_precision`` (``highest``: what the
configuration states).  Nothing is imported from the program and nothing
it made is taken.

Data the algorithm is defined over, which the reference draws itself by
the estimator's documented rules: the groups are the sorted distinct user
and item labels; the start is ``default_rng(seed)``: ``U0 =
normal(size=(users, rank)) / sqrt(rank)`` as float32, then ``V0`` the
same.

What is compared (every value the fit returned):

- ``factor_err``: the worse side's ``|X - X_ref|_F / |X_ref - X_start|_F``,
  the gap over the distance the fit covered (1 for a start left
  unchanged);
- ``rmse_gap``: ``|rmse - rmse_ref| / rmse_ref`` of the training RMSE of
  the returned factors, computed here for both.

``control`` is the same reference with its contractions at one bf16 pass
of the MXU (``control_matmul_precision``: the nearest precision below the
configuration's).  On a CPU every precision is float32, so there the
control rounds the operands of the contractions to bfloat16 itself, which
is what that pass does.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

FAULTS = ("unchanged", "half_ratings", "plain_lambda")


def index(data: dict) -> tuple:
    """``(user_ids, item_ids, u, i, r)``: the sorted distinct labels and
    every rating's position among them."""
    user_ids, u = np.unique(np.asarray(data["user"]), return_inverse=True)
    item_ids, i = np.unique(np.asarray(data["item"]), return_inverse=True)
    return (user_ids, item_ids, u.astype(np.int32), i.astype(np.int32),
            np.asarray(data["rating"], np.float32))


def start(seed: int, users: int, items: int, rank: int) -> tuple:
    rng = np.random.default_rng(int(seed))
    scale = 1.0 / np.sqrt(rank)
    u0 = (rng.normal(size=(users, rank)) * scale).astype(np.float32)
    v0 = (rng.normal(size=(items, rank)) * scale).astype(np.float32)
    return u0, v0


def _blocks(group: np.ndarray, other: np.ndarray, rating: np.ndarray,
            groups: int, block: int) -> tuple:
    """The ratings sorted by group and cut into blocks of ``block``
    groups, every block filled to the longest with entries that fall
    outside the dense matrices: ``(local group, other, rating)`` as
    ``(blocks, longest)`` arrays."""
    order = np.argsort(group, kind="stable")
    group, other, rating = group[order], other[order], rating[order]
    n_blocks = -(-groups // block)
    edges = np.searchsorted(group, np.arange(n_blocks + 1) * block)
    longest = max(1, int(np.diff(edges).max()))
    local = np.full((n_blocks, longest), block, np.int32)    # out of range
    oth = np.zeros((n_blocks, longest), np.int32)
    rat = np.zeros((n_blocks, longest), np.float32)
    for k in range(n_blocks):
        lo, hi = edges[k], edges[k + 1]
        local[k, :hi - lo] = group[lo:hi] - k * block
        oth[k, :hi - lo] = other[lo:hi]
        rat[k, :hi - lo] = rating[lo:hi]
    return local, oth, rat


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8, 9))
def _solve_block(prev, factors, local, other, rating, block: int,
                 block_other: int, reg: float, precision: str,
                 count_weighted: bool):
    """The factors of one block of groups against the fixed ``factors``
    of the other side (its rows filled with zeros to a multiple of
    ``block_other``)."""
    n_other, rank = factors.shape
    mask = jnp.zeros((block, n_other), jnp.float32).at[local, other].add(
        1.0, mode="drop")
    dense = jnp.zeros((block, n_other), jnp.float32).at[local, other].add(
        rating, mode="drop")
    if precision == "bfloat16_operands":
        def operand(x):
            return x.astype(jnp.bfloat16).astype(jnp.float32)
        precision = "highest"
    else:
        def operand(x):
            return x

    def chunk(k, acc):
        y = jax.lax.dynamic_slice_in_dim(factors, k * block_other,
                                         block_other)
        m = jax.lax.dynamic_slice_in_dim(mask, k * block_other, block_other,
                                         axis=1)
        outer = (y[:, :, None] * y[:, None, :]).reshape(block_other,
                                                        rank * rank)
        return acc + jnp.dot(operand(m), operand(outer), precision=precision)

    a = jax.lax.fori_loop(0, n_other // block_other, chunk,
                          jnp.zeros((block, rank * rank), jnp.float32))
    b = jnp.dot(operand(dense), operand(factors), precision=precision)
    count = jnp.sum(mask, axis=1)
    weight = jnp.maximum(count, 1.0) if count_weighted else jnp.ones_like(
        count)
    a = a.reshape(block, rank, rank) + (
        reg * weight)[:, None, None] * jnp.eye(rank, dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        solved = jnp.linalg.solve(a, b[:, :, None])[:, :, 0]
    return jnp.where((count > 0)[:, None], solved, prev)


def _side(prev, factors, blocks, block: int, block_other: int, reg: float,
          precision: str, count_weighted: bool):
    groups = prev.shape[0]
    fill = (-factors.shape[0]) % block_other
    factors = jnp.pad(factors, ((0, fill), (0, 0)))
    prev = jnp.pad(prev, ((0, (-groups) % block), (0, 0)))
    out = [_solve_block(prev[k * block:(k + 1) * block], factors, local,
                        other, rating, block, block_other, reg, precision,
                        count_weighted)
           for k, (local, other, rating) in enumerate(zip(*blocks))]
    return jnp.concatenate(out)[:groups]


def run(config: dict, data: dict, seed: int, precision: str = None,
        half_ratings: bool = False, count_weighted: bool = True) -> dict:
    """The whole fit, as an answer: ids and factors on the host."""
    ref = config["reference_params"]
    precision = precision or ref["matmul_precision"]
    if precision == "default" and jax.default_backend() == "cpu":
        precision = "bfloat16_operands"
    rank, reg = int(ref["rank"]), float(ref["reg_param"])
    block, block_other = int(ref["block_groups"]), int(ref["block_other"])
    user_ids, item_ids, u, i, r = index(data)
    u0, v0 = start(seed, len(user_ids), len(item_ids), rank)
    if half_ratings:
        u, i, r = u[::2], i[::2], r[::2]
    by_user = tuple(jnp.asarray(a) for a in _blocks(
        u, i, r, len(user_ids), block))
    by_item = tuple(jnp.asarray(a) for a in _blocks(
        i, u, r, len(item_ids), block))
    users, items = jnp.asarray(u0), jnp.asarray(v0)
    for _ in range(int(ref["epochs"])):
        users = _side(users, items, by_user, block, block_other, reg,
                      precision, count_weighted)
        items = _side(items, users, by_item, block, block_other, reg,
                      precision, count_weighted)
    return {"userIds": user_ids, "itemIds": item_ids,
            "userFactors": np.asarray(jax.device_get(users)),
            "itemFactors": np.asarray(jax.device_get(items))}


@jax.jit
def _squared_error(users, items, u, i, r):
    def part(carry, xs):
        uu, ii, rr = xs
        gap = jnp.sum(users[uu] * items[ii], axis=1) - rr
        return carry + jnp.sum(jnp.where(rr != 0, gap * gap, 0.0)), None

    return jax.lax.scan(part, jnp.zeros((), jnp.float32), (u, i, r))[0]


def train_rmse(answer: dict, u, i, r) -> float:
    """The training RMSE of ``answer``'s factors, a part of the ratings at
    a time (a rating of 0 fills the last part: the generator's are 1-5)."""
    part = 1 << 18
    fill = (-len(r)) % part
    u, i, r = (jnp.asarray(np.concatenate([a, np.zeros(fill, a.dtype)])
                           .reshape(-1, part)) for a in (u, i, r))
    total = _squared_error(jnp.asarray(answer["userFactors"], jnp.float32),
                           jnp.asarray(answer["itemFactors"], jnp.float32),
                           u, i, r)
    return float(np.sqrt(float(total) / (u.size - fill)))


def numbers(answer: dict, ref: dict, begin: tuple, u, i, r) -> dict:
    """The numbers compared: ``answer`` against the reference's answer
    ``ref``, given the start ``begin`` both left from."""
    names = ("factor_err", "user_factor_err", "item_factor_err", "rmse_gap",
             "train_rmse", "train_rmse_ref")
    if not all(np.array_equal(np.asarray(answer[ids]), ref[ids])
               for ids in ("userIds", "itemIds")):
        # other ids than the sorted distinct labels of the data: nothing
        # of such an answer can be held against the reference's
        return dict.fromkeys(names, float("inf"))
    with np.errstate(invalid="ignore", divide="ignore"):
        errs = [float(np.linalg.norm(np.asarray(answer[k], np.float64)
                                     - ref[k])
                      / np.linalg.norm(ref[k].astype(np.float64) - x0))
                for k, x0 in zip(("userFactors", "itemFactors"), begin)]
        rmse_ref = train_rmse(ref, u, i, r)
        rmse = train_rmse(answer, u, i, r)
        out = dict(zip(names, (max(errs), errs[0], errs[1],
                               abs(rmse - rmse_ref) / rmse_ref, rmse,
                               rmse_ref)))
    return {k: v if np.isfinite(v) else float("inf") for k, v in out.items()}


_LAST = {}


def reference(config: dict, data: dict, seed: int) -> dict:
    """The reference's answer; the last one is kept, by the seed, the
    rows and the parameters, so that a control and the faults of one seed
    are compared with one run of it."""
    key = (int(seed), len(data["rating"]),
           repr(sorted(config["reference_params"].items())))
    if _LAST.get("key") != key:
        _LAST.clear()
        _LAST.update(key=key, answer=run(config, data, seed))
    return _LAST["answer"]


def compare(config: dict, data: dict, answer: dict, seed: int) -> dict:
    """The numbers compared, by name, for the answer a fit returned."""
    ref = reference(config, data, seed)
    _, _, u, i, r = index(data)
    begin = start(seed, len(ref["userIds"]), len(ref["itemIds"]),
                  int(config["reference_params"]["rank"]))
    return numbers(answer, ref, begin, u, i, r)


def control(config: dict, data: dict, seed: int, precision=None) -> dict:
    """The reference in the control's precision, as an answer."""
    return run(config, data, seed, precision=precision or config[
        "reference_params"]["control_matmul_precision"])


def fault(config: dict, data: dict, seed: int, kind: str) -> dict:
    """The reference with one fault planted, as an answer: the start
    returned unchanged; every second rating left out; ``lambda * I`` in
    place of ``lambda * n_g * I`` (ALS without the weighting)."""
    if kind == "unchanged":
        ref = reference(config, data, seed)
        u0, v0 = start(seed, len(ref["userIds"]), len(ref["itemIds"]),
                       int(config["reference_params"]["rank"]))
        return {**ref, "userFactors": u0, "itemFactors": v0}
    if kind == "half_ratings":
        return run(config, data, seed, half_ratings=True)
    if kind != "plain_lambda":
        raise ValueError(kind)
    return run(config, data, seed, count_weighted=False)
