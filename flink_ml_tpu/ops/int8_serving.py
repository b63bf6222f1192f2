"""Int8 serving backends for the hot scoring ops (ISSUE 18).

Each op here is the "int8" registry backend of a stage-convention
serving kernel whose "xla" backend lives next to its model
(``linear_margins`` / ``kmeans_assign`` / ``widedeep_scores``).  The
contract is weight-only quantization with the f32 expression kept
bit-for-bit: params arrive as the ``{"q": int8, "s": f32}`` pytrees
produced by :func:`flink_ml_tpu.kernels.quantize.quantize_stage_params`,
dequantize in-program (one exact cast + one f32 multiply), then run the
SAME margin/assign/score expression as the f32 kernel — so the only
divergence from f32 is the quantization error the parity matrix's
accuracy-envelope harnesses gate (rank/decision agreement, not bitwise).

Tables gather-then-dequantize (codes gathered as int8, each row scaled
by its own per-row scale), never dequantize-then-gather: the f32 table
must not materialize, on-chip residency being the entire point — the
same order the ``EmbeddingRowCache`` int8 pools use, so cached and
uncached serving produce identical bits from identical codes.

These entries register with an ``available`` gate that always says no:
auto-pick must NEVER select them, because they require the quantized
param pytree only ``make_servable(..., precision="int8")`` builds.  A
forced ``lookup(op, backend="int8")`` — which bypasses availability by
contract — is the one route in, and the servable bind path is the one
caller.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["int8_linear_margins", "int8_kmeans_assign",
           "int8_widedeep_scores"]


def int8_linear_margins(static, params, cols):
    """``linear_margins`` on dequantized weights — expression-identical
    to ``_linear_chain_kernel`` after the one multiply that rebuilds
    ``w`` (per-tensor scale for vector ``w``, per-class for multiclass);
    ``b`` is f32 passthrough (intercepts never quantize)."""
    from ..api.chain import as_matrix
    from ..kernels.quantize import dequantize
    from ..models.common.linear import _stable_margins

    (fcol, mcol) = static
    X = as_matrix(cols[fcol])
    qw = params["w"]
    w = dequantize(qw["q"], qw["s"],
                   None if qw["q"].ndim == 1 else 1)
    return {mcol: _stable_margins(X.astype(jnp.float32), w, params["b"])}


def int8_kmeans_assign(static, params, cols):
    """``kmeans_assign`` on dequantized centroids (per-centroid-row
    scales) — same pairwise/argmin expression as
    ``_kmeans_chain_kernel``; the measure singleton rides the
    plan-static tuple exactly as in the f32 plan."""
    from ..api.chain import as_matrix
    from ..kernels.quantize import dequantize

    (fcol, acol, measure) = static
    pts = as_matrix(cols[fcol])
    centroids = dequantize(params["centroids"]["q"],
                           params["centroids"]["s"], 0)
    dists = measure.pairwise(pts.astype(jnp.float32), centroids)
    return {acol: jnp.argmin(dists, axis=1)}


def int8_widedeep_scores(static, params, cols):
    """``widedeep_scores`` with int8 tables and mlp matrices.  The
    ``wide_cat``/``emb`` gathers run on the int8 codes and dequantize
    the GATHERED rows only; the dense tower dequantizes its (small)
    matrices in-program.  Biases, ``wide_b`` and the id ``offsets``
    are exact passthrough."""
    from ..kernels.quantize import (
        dequantize,
        dequantize_rows,
        dequantize_widedeep_rest,
    )
    from ..models.recommendation.widedeep import forward_from_rows

    (dcol, ccol, scol) = static
    qnet = params["net"]
    dense = cols[dcol].astype(jnp.float32)
    cat = cols[ccol] + params["offsets"][None, :]
    wide_rows = dequantize(qnet["wide_cat"]["q"][cat],
                           qnet["wide_cat"]["s"])
    emb_rows = dequantize_rows(qnet["emb"]["q"][cat],
                               qnet["emb"]["s"][cat])
    scores = jax.nn.sigmoid(forward_from_rows(
        dequantize_widedeep_rest(qnet), dense, wide_rows, emb_rows))
    return {scol: scores}


def _register_int8_kernels() -> None:
    from ..kernels.registry import register_kernel

    # int8 entries consume the quantized param pytree only the servable
    # bind path builds, so auto-pick (which would hand them the f32
    # params) must never see them; the bind path's forced
    # ``lookup(op, backend="int8")`` IS the admission path
    quantized = "takes the quantized params only the servable bind builds"
    for op, fn in (("linear_margins", int8_linear_margins),
                   ("kmeans_assign", int8_kmeans_assign),
                   ("widedeep_scores", int8_widedeep_scores)):
        register_kernel(op, "int8", fn, convention="stage",
                        forced_only=quantized)


_register_int8_kernels()
