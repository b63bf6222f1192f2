"""Operations and bytes of one ``als_netflix`` step (one ALS iteration:
the users' side, then the items'), from shapes alone: the least any
implementation of the configuration does, so a share of these over a
measured time cannot pass 100%.

``nnz`` ratings, ``m`` users, ``n`` items, rank ``f``.  Every rating is
used once a side."""

FLOAT = 4.0


def _shapes(config: dict) -> tuple:
    return (float(config["rows"]), float(config["users"]),
            float(config["items"]), float(config["rank"]))


def gather_counts(config: dict) -> dict:
    """One row of the other side's factors read for every rating, once a
    side: 2 nnz rows of ``f`` floats (HBM-bound).  Reading a row once for
    all the ratings that share it is what a cache would do and no
    implementation is owed; the ratings' own ids are counted with the
    step."""
    nnz, _, _, f = _shapes(config)
    return {"flops": 0.0, "bytes": 2.0 * nnz * f * FLOAT}


def normal_eq_counts(config: dict) -> dict:
    """``A_g = sum y y^T`` over a group's ratings, the symmetric half
    (``f (f + 1) / 2`` products a rating, a multiply and an add each), and
    ``b_g = sum r y`` (``2 f`` a rating), once a side: ``2 nnz f (f + 1) +
    4 nnz f`` FLOP, on the MXU; the gathered rows arrive from the gather
    and are not counted again."""
    nnz, _, _, f = _shapes(config)
    return {"flops": 2.0 * nnz * f * (f + 1.0) + 4.0 * nnz * f, "bytes": 0.0}


def solve_counts(config: dict) -> dict:
    """A Cholesky factorisation (``f^3 / 3``) and two triangular solves
    (``f^2`` each) for every user and every item: ``(m + n) (f^3 / 3 +
    2 f^2)`` FLOP.  The least bytes are one write and one read of each
    group's ``A`` (its symmetric half, ``f (f + 1) / 2`` floats) and of its
    right-hand side and solution."""
    _, m, n, f = _shapes(config)
    return {"flops": (m + n) * (f ** 3 / 3.0 + 2.0 * f ** 2),
            "bytes": (m + n) * FLOAT * (f * (f + 1.0) + 2.0 * f)}


def step_counts(config: dict) -> dict:
    """One iteration: each side's ratings read once (the other side's
    index and the rating, 8 bytes a rating a side), the gathers, the
    normal equations, the solves, and both factor matrices written once.
    5.6e11 FLOP (2.8 ms at the bf16 peak) against 21 GB (26 ms at 819
    GB/s): HBM-bound by the gathers as counted here."""
    nnz, m, n, f = _shapes(config)
    parts = [gather_counts(config), normal_eq_counts(config),
             solve_counts(config)]
    return {"flops": sum(p["flops"] for p in parts),
            "bytes": (2.0 * nnz * 8.0 + (m + n) * f * FLOAT
                      + sum(p["bytes"] for p in parts))}


def kernel_counts(config: dict) -> dict:
    """The step makes no Pallas call: the gathers, the batched
    contractions and the Cholesky solve are XLA's, so there is nothing to
    count and ``kernel_roofline_pct`` finds no kernel event."""
    return {"flops": 0.0, "bytes": 0.0}
