"""Operations and bytes of one ``widedeep_criteo`` step, from shapes
alone: what any implementation of the configuration has to do, so a share
of these over a measured time cannot pass 100%."""

FLOAT = 4.0


def _shapes(config: dict) -> tuple:
    rows = float(sum(int(v) for v in config["vocab_sizes"]))
    width = int(config["embedding_dim"])
    batch = int(config["global_batch_size"])
    return rows, width, batch, batch * int(config["n_cat"])


def tower_weights(config: dict) -> int:
    """Weights of the deep tower's matrices: 1,094,912 at the cell's sizes
    (429 x 1024 + 1024 x 512 + 512 x 256 + 256 x 1)."""
    fan_in = int(config["n_dense"]) + int(config["n_cat"]) * int(
        config["embedding_dim"])
    total = 0
    for h in list(config["hidden_units"]) + [1]:
        total += fan_in * int(h)
        fan_in = int(h)
    return total


def towers_counts(config: dict) -> dict:
    """Forward and backward of the deep tower on one batch: 2 FLOP a
    weight a row forward, twice that backward (MXU-bound; the activations
    are a few hundred MB and not counted)."""
    _, _, batch, _ = _shapes(config)
    return {"flops": 6.0 * tower_weights(config) * batch, "bytes": 0.0}


def lookup_counts(config: dict) -> dict:
    """One embedding row and one wide weight read for every slot."""
    _, width, _, slots = _shapes(config)
    return {"flops": 0.0, "bytes": FLOAT * slots * (width + 1)}


def table_grad_counts(config: dict) -> dict:
    """The gradient of both tables from the per-slot rows: each slot's row
    read once, the sum of each touched table row written once
    (``unique_rows_per_step``, a floor read from the data).  Forming a
    table-shaped gradient is one implementation and not counted."""
    _, width, _, slots = _shapes(config)
    unique = float(config["unique_rows_per_step"])
    return {"flops": 0.0, "bytes": FLOAT * (slots + unique) * (width + 1)}


def optimizer_counts(config: dict) -> dict:
    """Dense Adam as the configuration states it: every row of both tables
    has its parameter and both moments read and written every step, six
    streams over 33.76 M x 17 floats (13.8 GB).  A gradient stream over
    the whole table is what this program also reads, but an update from
    the touched rows alone would not, so it is left out; the towers'
    4 MB are noise beside it."""
    rows, width, _, _ = _shapes(config)
    return {"flops": 0.0, "bytes": 6.0 * FLOAT * rows * (width + 1)}


def step_counts(config: dict) -> dict:
    """One minibatch step: the batch read once (13 float32, 26 int32, a
    label and a row weight), the lookups, the towers, the table gradient
    and Adam.  HBM-bound: 13.9 GB (17 ms at 819 GB/s) against 2.15e11
    FLOP (1.1 ms at 197 TFLOP/s)."""
    _, _, batch, _ = _shapes(config)
    per_row = int(config["n_dense"]) + int(config["n_cat"]) + 2
    parts = [lookup_counts(config), towers_counts(config),
             table_grad_counts(config), optimizer_counts(config)]
    return {"flops": sum(p["flops"] for p in parts),
            "bytes": FLOAT * batch * per_row + sum(p["bytes"] for p in parts)}


def kernel_counts(config: dict) -> dict:
    """The step makes no Pallas call: ``routed_table_grad`` resolves to
    its XLA stages (the Pallas fold is parked, ``ops/emb_grad_pallas.py``),
    so there is nothing to count and ``kernel_roofline_pct`` finds no
    kernel event to divide by."""
    return {"flops": 0.0, "bytes": 0.0}
