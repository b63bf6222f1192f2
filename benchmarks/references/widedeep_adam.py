"""Plain reference for the Wide&Deep fit under dense Adam.

Wide & Deep Learning for Recommender Systems (Cheng et al. 2016,
arXiv:1606.07792), as the configuration states it: a wide linear part over
the dense features and the categorical ids (one scalar weight per id), a
deep part of one embedding per id concatenated with the dense features
into ReLU layers and one linear output, the two logits added, trained
jointly on the mask-weighted mean logistic loss with Adam (Kingma & Ba
2015: b1 0.9, b2 0.999, eps 1e-8, bias-corrected) over EVERY parameter,
the tables included.  Plain ``jax.numpy`` in float32: the gradients are
``jax.grad`` through a plain ``table[ids]`` gather (XLA's scatter-add),
Adam is written out, nothing is imported from the program and nothing it
made is taken.  Contractions run at ``reference_params.matmul_precision``
(``default``: what the configuration states, one bf16 pass on a TPU's MXU).

Two things are data the algorithm is defined over, not arithmetic, and
the reference draws both itself by the estimator's documented rules:

- the epoch order: rows permuted once by
  ``numpy.random.default_rng(seed).permutation(rows)``, cut into
  consecutive batches, the last one filled with rows of weight 0 (id 0,
  zeros), the same order replayed every epoch;
- the start (``WideDeep``'s ``init_params``): from
  ``default_rng(seed + 1)``, layer by layer ``w = normal(size=(fan_in, h))
  * sqrt(2 / fan_in)`` as float32 and ``b`` zeros, the wide part zeros,
  then one word ``integers(0, 2**32)`` that keys the embedding table:
  ``bits = jax.random.bits(jax.random.key(word), (rows, width), uint32)``,
  ``emb = ((bits >> 8) - 2**23) * (a / 2**23)``, ``a = float32(0.05 *
  sqrt(3))``: exact products, the same bits on every backend.

What is compared (every value the fit returned, at the timed sizes).  A
fit is 160 Adam steps through ReLU layers, and Adam divides each gradient
by the root of its second moment: an element whose gradient is near zero
still moves by about the learning rate, in the direction of a sign that a
last bit decides.  Two sound implementations therefore part by a few
percent of the distance the training covered (PERF.md section 2 has the
readings), so every gap is taken over that distance, which reads 1 for a
state left unchanged:

- ``table_err``: ``|x - x_ref| / |x_ref - x_start|`` over both tables
  together (Frobenius).  A row no batch touched (``moved`` 0, where
  ``moved`` is the sum over the steps of the norm of the row's change in
  the reference) must equal its start bit for bit: dense Adam leaves a
  row with a zero gradient where it is, and one such row that differs
  reads as infinity;
- ``tower_err``: the worst layer's ``|w - w_ref| / |w_ref - w_start|``
  (weights and bias of a layer together; the wide part's dense weights
  and bias count as one more layer);
- ``first_loss_gap`` and ``loss_gap``: the first and the worst epoch's
  ``|loss - loss_ref| / loss_ref``;
- ``table_row_err``: the worst touched row's ``|x - x_ref| / moved``, for
  the record: a largest value over 2.6 million rows, it swings too widely
  to carry a limit.

``control`` is the same fit with its state (parameters and both Adam
moments) rounded to a lower precision after every step: the nearest
precision below the configuration's float32 state, put in the program's
place.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

TINY = 1e-30
B1, B2, EPS = 0.9, 0.999, 1e-8
TABLES = ("emb", "wide_cat")


def initial_params(seed: int, n_dense: int, vocab_sizes, emb_dim: int,
                   hidden) -> dict:
    rng = np.random.default_rng(int(seed) + 1)
    total = int(np.sum(vocab_sizes))
    layers, fan_in = [], n_dense + len(vocab_sizes) * emb_dim
    for h in list(hidden) + [1]:
        w = (rng.normal(size=(fan_in, h)) * np.sqrt(2.0 / fan_in)).astype(
            np.float32)
        layers.append({"w": jnp.asarray(w), "b": jnp.zeros((h,), jnp.float32)})
        fan_in = h
    word = int(rng.integers(0, 1 << 32))
    return {"emb": _initial_table(word, total, emb_dim),
            "wide_cat": jnp.zeros((total,), jnp.float32),
            "wide_dense": jnp.zeros((n_dense,), jnp.float32),
            "wide_b": jnp.zeros((), jnp.float32),
            "mlp": layers}


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _initial_table(word: int, rows: int, width: int):
    bits = jax.random.bits(jax.random.key(word), (rows, width), jnp.uint32)
    steps = (bits >> 8).astype(jnp.int32) - (1 << 23)
    half_width = np.float32(0.05 * np.sqrt(3.0))
    return steps.astype(jnp.float32) * np.float32(half_width / (1 << 23))


def epoch_tensors(data: dict, vocab_sizes, batch: int, seed: int,
                  half_batch: bool = False) -> tuple:
    """``(dense, ids, label, weight)`` as ``(steps, batch, ...)`` arrays in
    the estimator's epoch order, the ids offset into the stacked tables."""
    dense = np.asarray(data["denseFeatures"], np.float32)
    offsets = np.concatenate([[0], np.cumsum(vocab_sizes)[:-1]])
    ids = (np.asarray(data["catFeatures"], np.int64)
           + offsets[None, :]).astype(np.int32)
    label = np.asarray(data["label"], np.float32)
    rows = len(label)
    steps = max(1, -(-rows // batch))
    perm = np.random.default_rng(int(seed)).permutation(rows)

    def laid_out(a):
        a = a[perm]
        fill = np.zeros((steps * batch - rows,) + a.shape[1:], a.dtype)
        a = np.concatenate([a, fill]).reshape((steps, batch) + a.shape[1:])
        return a[:, :batch // 2] if half_batch else a

    return tuple(map(laid_out, (dense, ids, label,
                                np.ones((rows,), np.float32))))


def logits(params: dict, dense, ids):
    wide = (dense @ params["wide_dense"] + params["wide_cat"][ids].sum(axis=1)
            + params["wide_b"])
    deep = jnp.concatenate(
        [dense, params["emb"][ids].reshape(ids.shape[0], -1)], axis=1)
    for i, layer in enumerate(params["mlp"]):
        deep = deep @ layer["w"] + layer["b"]
        if i + 1 < len(params["mlp"]):
            deep = jnp.maximum(deep, 0.0)
    return wide + deep[:, 0]


def loss_fn(params: dict, dense, ids, label, weight):
    signed = (2.0 * label - 1.0) * logits(params, dense, ids)
    return (jnp.sum(jnp.logaddexp(0.0, -signed) * weight)
            / jnp.maximum(jnp.sum(weight), 1e-12))


def _row_norm(a):
    return jnp.abs(a) if a.ndim == 1 else jnp.sqrt(jnp.sum(a * a, axis=1))


def _step(lr: float, precision: str, state_dtype, state, i, tensors):
    """One Adam step on batch ``i``; ``moved`` adds up, row by row, the
    norm of each table row's change."""
    params, m, v, t, moved = state
    dense, ids, label, weight = (a[i] for a in tensors)
    with jax.default_matmul_precision(precision):
        loss, g = jax.value_and_grad(loss_fn)(params, dense, ids, label,
                                              weight)
    t = t + 1
    tree = jax.tree_util.tree_map
    m = tree(lambda m, g: B1 * m + (1.0 - B1) * g, m, g)
    v = tree(lambda v, g: B2 * v + (1.0 - B2) * g * g, v, g)
    tf = t.astype(jnp.float32)
    c1, c2 = 1.0 - B1 ** tf, 1.0 - B2 ** tf
    new = tree(lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + EPS),
               params, m, v)
    if state_dtype is not None:
        # reduce_precision, not a cast there and back: XLA may drop such a
        # pair of converts (it did on the chip, PR 29: the "control" read
        # like a sound run), the rounding it may not
        kept = jnp.finfo(state_dtype)
        new, m, v = (tree(lambda a: jax.lax.reduce_precision(
            a, exponent_bits=kept.nexp, mantissa_bits=kept.nmant), x)
            for x in (new, m, v))
    moved = {k: moved[k] + _row_norm(new[k] - params[k]) for k in moved}
    return (new, m, v, t, moved), loss


def _config_start(config: dict, seed: int) -> dict:
    return initial_params(seed, int(config["n_dense"]),
                          [int(v) for v in config["vocab_sizes"]],
                          int(config["embedding_dim"]),
                          config["hidden_units"])


def run(config: dict, data: dict, seed: int, state_dtype=None,
        half_batch: bool = False) -> tuple:
    """The whole fit: ``(params, moved, losses)``, device arrays and one
    loss per epoch."""
    ref = config["reference_params"]
    vocab_sizes = [int(v) for v in config["vocab_sizes"]]
    tensors = tuple(jnp.asarray(a) for a in epoch_tensors(
        data, vocab_sizes, int(ref["batch"]), seed, half_batch))
    params = _config_start(config, seed)
    zeros = functools.partial(jax.tree_util.tree_map, jnp.zeros_like)
    state = (params, zeros(params), zeros(params), jnp.zeros((), jnp.int32),
             {k: jnp.zeros(params[k].shape[:1], jnp.float32)
              for k in TABLES})
    del params
    step = jax.jit(
        functools.partial(_step, float(ref["learning_rate"]),
                          ref["matmul_precision"],
                          None if state_dtype is None
                          else jnp.dtype(state_dtype)),
        donate_argnums=(0,))
    steps, losses = tensors[0].shape[0], []
    for _ in range(int(ref["epochs"])):
        epoch = []
        for i in range(steps):
            state, loss = step(state, jnp.int32(i), tensors)
            epoch.append(loss)
        losses.append(float(np.mean(np.asarray(jax.device_get(epoch),
                                               np.float64))))
    return state[0], state[4], losses


def _layers(answer: dict) -> list:
    """The towers as flat vectors, a layer each, the wide part's dense
    weights and bias first."""
    out = [np.concatenate([np.ravel(answer["wide_dense"]),
                           np.ravel(answer["wide_b"])])]
    i = 0
    while f"mlp_{i}_w" in answer:
        out.append(np.concatenate([np.ravel(answer[f"mlp_{i}_w"]),
                                   np.ravel(answer[f"mlp_{i}_b"])]))
        i += 1
    return [np.asarray(a, np.float64) for a in out]


def _flat(params: dict, losses) -> dict:
    """A parameter tree under the names of the answer a fit returns, its
    arrays left where they are."""
    out = {k: params[k] for k in TABLES + ("wide_dense", "wide_b")}
    for i, layer in enumerate(params["mlp"]):
        out[f"mlp_{i}_w"], out[f"mlp_{i}_b"] = layer["w"], layer["b"]
    out["loss_log"] = np.asarray(losses, np.float64)
    return out


def as_answer(params: dict, losses) -> dict:
    """A parameter tree in the shape of the answer a fit returns: host
    arrays."""
    return {k: np.asarray(jax.device_get(v))
            for k, v in _flat(params, losses).items()}


@jax.jit
def _table_gaps(x, x_ref, x_start, moved) -> tuple:
    """Of one table: the worst row's ``|x - x_ref| / moved``, ``|x -
    x_ref|^2``, ``|x_ref - x_start|^2`` and how many rows that no batch
    touched (``moved`` 0) differ from the reference's, which still holds
    their start."""
    def rows(a):
        return jnp.abs(a) if a.ndim == 1 else jnp.sqrt(jnp.sum(a * a, axis=1))

    gap = rows(x - x_ref)
    idle = moved == 0
    err = jnp.where(idle, 0.0, gap / jnp.where(idle, 1.0, moved))
    # a NaN anywhere must fail, not vanish in a max
    worst = jnp.max(jnp.where(jnp.isfinite(err), err, jnp.inf))
    return (worst, jnp.sum(gap * gap), jnp.sum(rows(x_ref - x_start) ** 2),
            jnp.sum(idle & ~(gap == 0)))


def numbers(answer: dict, ref: dict, start: dict, moved: dict) -> dict:
    """The numbers compared: ``answer`` against the reference's answer
    ``ref``, given the start both began from and ``moved``; arrays on
    the host or on the device."""
    loss = np.asarray(answer["loss_log"], np.float64)
    if loss.shape != ref["loss_log"].shape:
        raise ValueError(f"the fit logged {loss.shape} epochs, the "
                         f"reference {ref['loss_log'].shape}")
    for k in TABLES:
        if np.shape(answer[k]) != np.shape(ref[k]):
            raise ValueError(f"the fit returned {k} of shape "
                             f"{np.shape(answer[k])}, the reference "
                             f"{np.shape(ref[k])}")
    with np.errstate(invalid="ignore", divide="ignore"):
        gaps = np.abs(loss - ref["loss_log"]) / np.abs(ref["loss_log"])
        rows = [[float(v) for v in _table_gaps(
            jnp.asarray(answer[k], jnp.float32), jnp.asarray(ref[k]),
            jnp.asarray(start[k]), jnp.asarray(moved[k]))] for k in TABLES]
        table_err = float(np.sqrt(sum(r[1] for r in rows)
                                  / sum(r[2] for r in rows)))
        if any(r[3] for r in rows):
            table_err = float("inf")
        tower_err = max(
            float(np.linalg.norm(a - b) / np.linalg.norm(b - c))
            for a, b, c in zip(_layers(answer), _layers(ref), _layers(start),
                               strict=True))
    out = {"loss_gap": float(np.max(gaps)), "first_loss_gap": float(gaps[0]),
           "table_row_err": max(r[0] for r in rows),
           "table_err": table_err, "tower_err": tower_err}
    return {k: v if np.isfinite(v) else float("inf") for k, v in out.items()}


def compare(config: dict, data: dict, answer: dict, seed: int) -> dict:
    """The numbers compared, by name, for the answer a fit returned."""
    params, moved, losses = run(config, data, seed)
    # the reference's tables stay on the device, where the gaps are taken
    return numbers(answer, _flat(params, losses),
                   _flat(_config_start(config, seed), []), moved)


def control(config: dict, data: dict, seed: int, state_dtype=None) -> dict:
    """The reference in the control's precision, as an answer."""
    state_dtype = state_dtype or config["reference_params"][
        "control_state_dtype"]
    params, _, losses = run(config, data, seed, state_dtype=state_dtype)
    return as_answer(params, losses)


FAULTS = ("unchanged", "half_batch", "altered")


def fault(config: dict, data: dict, seed: int, kind: str) -> dict:
    """The reference with one fault planted, as an answer: the state
    returned unchanged (the start comes back); half of every batch left
    out, the mean taken over the rest; one row of the embedding table
    that no batch touched altered in its last bits (an update that
    reached the wrong row)."""
    if kind == "unchanged":
        _, _, losses = run(config, data, seed)
        return as_answer(_config_start(config, seed), losses)
    if kind == "half_batch":
        params, _, losses = run(config, data, seed, half_batch=True)
        return as_answer(params, losses)
    if kind != "altered":
        raise ValueError(kind)
    params, moved, losses = run(config, data, seed)
    return altered(as_answer(params, losses),
                   np.asarray(jax.device_get(moved["emb"])), seed)


def altered(answer: dict, moved: np.ndarray, seed: int) -> dict:
    idle = np.flatnonzero(moved == 0)
    row = int(idle[seed % len(idle)])
    emb = np.array(answer["emb"])
    emb[row, 0] *= np.float32(1.0 + 1e-6)
    return {**answer, "emb": emb}
