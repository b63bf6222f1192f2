"""The ELL scatter kernel against its roofline: the least time of one
call of the Pallas kernel ``ell_scatter_apply`` from the bytes of its
operands and its result, over the device time of that kernel's events a
step (``ell_margin_roofline_pct.kernel_share``).

Bytes (``scatter_bytes``), HBM-bound: the weights and the layout's
``src``, ``pos`` and ``mask`` grids in (4 bytes a slot each, one slot a
weight) and the extended residuals (the batch and a nonempty pad to whole
256-lane rows), the weights out."""

from metrics.ell_margin_roofline_pct import extended_len, kernel_share


def scatter_bytes(config: dict) -> float:
    f = int(config["num_features"])
    return 4.0 * (5 * f + extended_len(int(config["global_batch_size"])))


def read(ctx):
    return kernel_share(ctx, "ell_scatter_apply", scatter_bytes)
