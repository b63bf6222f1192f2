"""Operations and bytes of one ``gbt_airline`` step (one tree of depth
``max_depth`` over ``rows`` rows of ``features`` int32 bin ids), from
shapes alone: the least any implementation of the configuration does, so
a share of these over a measured time cannot pass 100%.

Every level of a tree has to read each live row's bin of every feature
and its gradient, hessian and node id to sum its histograms; every row's
gradient comes from its margin and label; every level routes a row by one
bin, and the tree's value is added to every margin.  The histogram's
additions are the step's only arithmetic of note, and it is bound by
HBM: a level of 115 M rows reads 7.4 GB (9 ms at 819 GB/s) to add
3.0 G values."""

INT = FLOAT = 4.0


def _shapes(config: dict) -> tuple:
    return (float(config["rows"]), float(config["features"]),
            float(config["max_depth"]))


def hist_counts(config: dict) -> dict:
    """A tree's level histograms: at each of ``depth`` levels, every row's
    ``d`` bins, gradient, hessian and node id read once (``4 d + 12``
    bytes), a gradient and a hessian added into a bin of each feature
    (``2 d`` FLOP)."""
    n, d, depth = _shapes(config)
    return {"flops": 2.0 * n * d * depth,
            "bytes": depth * n * (INT * d + 3.0 * FLOAT)}


def step_counts(config: dict) -> dict:
    """One tree: the histograms; the gradient and hessian from the margin
    and the label (two reads, two writes a row); at each level a row's
    node id read and written and one of its bins read (12 bytes); the
    margin read and written once (8 bytes)."""
    n, _, depth = _shapes(config)
    hist = hist_counts(config)
    return {"flops": hist["flops"],
            "bytes": hist["bytes"] + n * (4.0 * FLOAT + depth * 12.0 + 8.0)}


def kernel_counts(config: dict) -> dict:
    """The step's Pallas calls are the level histograms
    (``gbt_level_histograms``): the same counts as ``hist_counts``."""
    return hist_counts(config)
