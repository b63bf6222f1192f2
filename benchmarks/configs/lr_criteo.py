"""Operations and bytes of one ``lr_criteo`` step, from shapes alone."""


def step_counts(config: dict) -> dict:
    """What any implementation has to do for one minibatch step of batch
    ``B`` over ``F`` weights: 4 FLOP per feature of a row (margin and
    gradient, multiply and add), the batch read once (13 float32, 26
    int32, a label and a row weight), the weights read and written once.
    HBM-bound: 13.8 MB against 5 MFLOP at the shapes of the cell."""
    b, f = int(config["global_batch_size"]), int(config["num_features"])
    per_row = int(config["n_dense"]) + int(config["n_cat"])
    return {"flops": 4.0 * per_row * b,
            "bytes": b * (4.0 * per_row + 4 + 4) + 2 * 4.0 * f}


def kernel_counts(config: dict) -> dict:
    """The two Pallas calls of a step by their operands and results
    (``ops/ell_scatter.py``): ``ell_margin`` reads the weights and the
    layout's ``src``, ``pos`` and ``mask`` grids (4 x 4F bytes) and writes
    B margins; ``ell_scatter_apply`` reads the same four and B residuals
    and writes the weights (5 x 4F).  Their one-hot contractions are MXU
    work the algorithm does not need, so only the bytes count: HBM-bound."""
    b, f = int(config["global_batch_size"]), int(config["num_features"])
    return {"flops": 0.0, "bytes": 9 * 4.0 * f + 2 * 4.0 * b}
