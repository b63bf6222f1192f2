"""Shared Estimator/Model bases for the linear family (LogisticRegression,
LinearRegression, LinearSVC) — one SGD skeleton, per-model loss + link."""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...api.stage import Estimator, Model
from ...data.table import Table
from ...linalg import SparseVector, stack_sparse_vectors, stack_vectors
from ...obs.trace import tracer
from ...params.shared import (
    HasElasticNet,
    HasFeaturesCol,
    HasGlobalBatchSize,
    HasLabelCol,
    HasLearningRate,
    HasMaxIter,
    HasNumFeatures,
    HasPredictionCol,
    HasRawPredictionCol,
    HasRegParam,
    HasSeed,
    HasTol,
    HasWeightCol,
)
from ...utils import persist
from ...utils.padding import pad_rows_to_bucket
from .losses import LOSSES
from .sgd import (
    LinearState,
    SGDConfig,
    sgd_fit,
    sgd_fit_mixed,
    sgd_fit_outofcore,
    sgd_fit_sparse,
)

__all__ = ["LinearEstimatorParams", "LinearModelBase", "LinearEstimatorBase",
           "resolve_features", "check_sparse_indices"]


def check_sparse_indices(idx: np.ndarray, num_features: int) -> None:
    """Range-check hashed indices against the weight size.  A jitted gather
    silently CLAMPS out-of-range indices (piling every stray feature onto
    the last weight), so a hasher/model numFeatures mismatch would produce
    garbage scores with no diagnostic — the same trap ``_validate_cat_ids``
    guards in WideDeep."""
    if idx.size and (int(idx.min()) < 0 or int(idx.max()) >= num_features):
        raise ValueError(
            f"hashed index out of range for numFeatures={num_features} "
            f"(got index {int(idx.max()) if int(idx.min()) >= 0 else int(idx.min())}); "
            "the hasher and the model disagree on the hash-space size")


def _stable_margins(X, w, b):
    """``X @ w + b`` with a context-stable contraction for vector ``w``.

    An ``(n, d) @ (d,)`` matvec (and a k=1 GEMM) lowers to a LOOP FUSION
    whose accumulation order depends on whether the lhs is a program
    parameter or a fused producer — so the same values score to
    different last-ulp margins standalone vs inside a fused chain
    segment (``api/chain.py``).  A k>=2 GEMM materializes its operands
    and accumulates identically in every context (verified across
    d 8..512 / n 8..1024), so the binary case pads ``w`` with one zero
    column and takes column 0: bit-identical margins whether the
    features are a parameter (stagewise/serving) or produced mid-segment
    (fused chain).  Matrix ``w`` (multiclass) is already a k>=2 GEMM."""
    if w.ndim == 1:
        w2 = jnp.stack([w, jnp.zeros_like(w)], axis=-1)
        return (X @ w2)[:, 0] + b
    return X @ w + b


@jax.jit
def _jit_margins(X, w, b):
    """Module-level jit: repeated transform() calls are cache hits."""
    return _stable_margins(X, w, b)


def _linear_chain_kernel(static, params, cols):
    """Chain-terminal margins — expression-identical to ``_jit_margins``
    (the shared predict entry point), staged under a private column the
    host ``post`` maps to prediction/raw columns."""
    import jax.numpy as jnp

    from ...api.chain import as_matrix

    (fcol, mcol) = static
    X = as_matrix(cols[fcol])
    return {mcol: _stable_margins(X.astype(jnp.float32),
                                  params["w"], params["b"])}


@jax.jit
def _jit_sparse_margins(idx, vals, w, b):
    """Sparse score: one gather + row reduce (no dense matrix ever built)."""
    return jnp.sum(vals * w[idx], axis=-1) + b


@jax.jit
def _jit_mixed_margins(dense, cat, w, b):
    """Mixed score: matvec over the leading dense slots + gather over the
    hashed categorical slots (implicit value 1.0)."""
    return dense @ w[: dense.shape[-1]] + jnp.sum(w[cat], axis=-1) + b


def resolve_features(table: Table, col: str):
    """Resolve a features column into the device-facing form.

    Sparse/hashed features appear in a Table either as a column of
    :class:`SparseVector` objects, or as the hashed PAIR convention two
    columns ``{col}_indices (n, nnz) int`` + ``{col}_values (n, nnz)
    float`` (what ``FeatureHasher.set_sparse_output(True)`` emits), or as
    the MIXED Criteo-native convention ``{col}_dense (n, nd) float`` +
    ``{col}_indices (n, nc) int`` (dense block occupying weight slots
    ``[0, nd)`` plus hashed categorical with implicit value 1.0 — the
    fastest LR layout on TPU, see ``sgd.sgd_fit_mixed``).

    Returns ``("dense", X)``, ``("sparse", (indices, values, dim))``, or
    ``("mixed", (dense, cat))``; ``dim`` is the feature dimension if
    derivable from the data (SparseVector carries it) else 0 (pair/mixed
    columns: the caller must know numFeatures)."""
    if col not in table:
        idx_col, val_col = f"{col}_indices", f"{col}_values"
        dense_col = f"{col}_dense"
        if dense_col in table and idx_col in table:
            if val_col in table:
                raise ValueError(
                    f"ambiguous feature schema: {dense_col!r}, {idx_col!r} "
                    f"AND {val_col!r} all present — the mixed layout "
                    "carries implicit value 1.0, so it cannot coexist with "
                    "a values column; drop one of them")
            return "mixed", (np.asarray(table[dense_col], np.float32),
                             np.asarray(table[idx_col], np.int32))
        if idx_col in table and val_col in table:
            return "sparse", (np.asarray(table[idx_col], np.int32),
                              np.asarray(table[val_col], np.float32), 0)
        raise KeyError(
            f"No column {col!r} (nor {idx_col!r}/{val_col!r}, nor "
            f"{dense_col!r}/{idx_col!r}); available: "
            f"{table.column_names}")
    column = table[col]
    if column.dtype == object and len(column) \
            and isinstance(column[0], SparseVector):
        return "sparse", stack_sparse_vectors(column)
    return "dense", stack_vectors(column)


class LinearModelParams(HasFeaturesCol, HasPredictionCol, HasRawPredictionCol):
    pass


class LinearEstimatorParams(LinearModelParams, HasLabelCol, HasWeightCol,
                            HasMaxIter, HasLearningRate, HasRegParam,
                            HasElasticNet, HasGlobalBatchSize, HasTol,
                            HasSeed, HasNumFeatures):
    pass


class LinearModelBase(LinearModelParams, Model):
    """Holds (coefficients, intercept); subclasses map margins to the
    prediction / raw-prediction columns."""

    loss_name: str = "squared"

    def __init__(self):
        super().__init__()
        self._state: Optional[LinearState] = None

    # -- model data ---------------------------------------------------------
    def set_model_data(self, *inputs) -> "LinearModelBase":
        (table,) = inputs
        self._state = LinearState(
            coefficients=np.asarray(table["coefficients"][0], np.float64),
            intercept=float(table["intercept"][0]))
        return self

    def get_model_data(self) -> List[Table]:
        self._require_model()
        return [Table({
            "coefficients": self._state.coefficients[None, :],
            "intercept": np.array([self._state.intercept]),
        })]

    def _require_model(self):
        if self._state is None:
            raise RuntimeError(
                f"{type(self).__name__} has no model data; fit the estimator "
                "or call set_model_data first")

    @property
    def loss_log(self) -> list:
        """Per-epoch training loss recorded by fit (empty when the model
        was built from set_model_data/load rather than trained)."""
        return list(getattr(self, "_loss_log", []) or [])

    @property
    def planned_impl(self) -> Optional[str]:
        """Which update implementation the fit planned ("ell" / "xla" /
        "sharded" / "dense" / "*-stream"), surfaced on the product path.
        None when the model was loaded rather than trained."""
        return self._state.planned_impl if self._state is not None else None

    # -- inference ----------------------------------------------------------
    def _margins(self, table: Table) -> np.ndarray:
        """Margins at BUCKETED batch shapes: rows zero-pad to the shared
        power-of-two bucket (``utils/padding.py``) before the jitted score,
        so mixed batch sizes — offline transforms and the online serving
        micro-batches alike — hit a bounded set of compiled programs
        instead of retracing per shape.  Pad rows are sliced off; margins
        are row-independent, so real rows are bit-identical."""
        self._require_model()
        kind, feats = resolve_features(table, self.get_features_col())
        w = jnp.asarray(self._state.coefficients, jnp.float32)
        b = jnp.asarray(self._state.intercept, jnp.float32)
        if kind == "sparse":
            idx, vals, _ = feats
            check_sparse_indices(idx, self._state.coefficients.shape[0])
            (idx, vals), n = pad_rows_to_bucket((idx, vals))
            return np.asarray(_jit_sparse_margins(idx, vals, w, b),
                              np.float64)[:n]
        if kind == "mixed":
            dense, cat = feats
            check_sparse_indices(cat, self._state.coefficients.shape[0])
            (dense, cat), n = pad_rows_to_bucket((dense, cat))
            return np.asarray(_jit_mixed_margins(dense, cat, w, b),
                              np.float64)[:n]
        (X,), n = pad_rows_to_bucket((feats.astype(np.float32),))
        return np.asarray(_jit_margins(X, w, b), np.float64)[:n]

    def _decision(self, margins: np.ndarray) -> np.ndarray:
        raise NotImplementedError

    def _raw(self, margins: np.ndarray) -> np.ndarray:
        return margins

    def transform_kernel(self, schema):
        """Chain TERMINAL for dense features: the in-segment kernel is
        expression-identical to the shared ``_margins`` predict entry
        point (one f32 matmul at the same padded bucket), and the host
        ``post`` applies the same f64 ``_decision``/``_raw`` mapping —
        fused output is bit-exact with stagewise ``transform``.  Sparse
        pair/mixed feature conventions stay on their own entry points
        (the chain substrate is dense column dicts)."""
        from ...api.chain import StageKernel, numeric_entry

        self._require_model()
        fcol = self.get_features_col()
        if numeric_entry(schema, fcol) is None:
            return None
        pred_col = self.get_prediction_col()
        raw_col = self.get_raw_prediction_col()
        margin_col = f"__chain_margins__{pred_col}"

        def post(host):
            m = host[margin_col].astype(np.float64)
            out = {pred_col: self._decision(m)}
            if raw_col:
                out[raw_col] = self._raw(m)
            return out

        return StageKernel(
            fn=_linear_chain_kernel, static=(fcol, margin_col),
            params={"w": np.asarray(self._state.coefficients, np.float32),
                    "b": np.float32(self._state.intercept)},
            consumes=(fcol,), produces=(margin_col,), post=post)

    def transform(self, *inputs) -> List[Table]:
        (table,) = inputs
        self._require_model()
        # dense features score through the kernel registry's shared
        # dispatch surface — the SAME (fn, static) plan the chain
        # terminal and the serving executor run, so offline transform,
        # fused pipelines, and serving share one compiled executable per
        # (schema, bucket).  Sparse/mixed layouts (and f32-unsafe int
        # batches) keep their own entry points below.
        from ...api.chain import apply_kernel_or_none

        kernel = self.transform_kernel(table.schema())
        cols = apply_kernel_or_none(kernel, table)
        if cols is not None:
            out = table
            for name in (n for n in cols if n not in kernel.produces):
                out = out.with_column(name, cols[name])
            return [out]
        m = self._margins(table)
        out = table.with_column(self.get_prediction_col(), self._decision(m))
        raw_col = self.get_raw_prediction_col()
        if raw_col:
            out = out.with_column(raw_col, self._raw(m))
        return [out]

    # -- persistence --------------------------------------------------------
    def save(self, path: str) -> None:
        self._require_model()
        persist.save_metadata(self, path)
        persist.save_model_arrays(path, "model", {
            "coefficients": self._state.coefficients,
            "intercept": np.array([self._state.intercept]),
        })

    @classmethod
    def load(cls, path: str):
        model = persist.load_stage_param(path)
        data = persist.load_model_arrays(path, "model")
        model._state = LinearState(
            coefficients=data["coefficients"].astype(np.float64),
            intercept=float(data["intercept"][0]))
        return model


class LinearEstimatorBase(LinearEstimatorParams, Estimator):
    """fit(): extract (X, y, weight), run the fused SGD loop, wrap the fitted
    state in the concrete model class."""

    loss_name: str = "squared"
    model_cls = None  # set by subclasses

    def _labels(self, table: Table) -> np.ndarray:
        return np.asarray(table[self.get_label_col()], np.float64)

    def fit(self, *inputs):
        (table,) = inputs
        with tracer.fit_span(type(self).__name__):
            return self._fit(table)

    def _fit(self, table: Table):
        """``fit`` under its root span; the phase spans are in the
        trainers (``sgd_fit_mixed`` has all of them)."""
        with tracer.span("fit.gather", "fit"):
            kind, feats = resolve_features(table, self.get_features_col())
            y = self._labels(table)
            weight_col = self.get_weight_col()
            weights = (np.asarray(table[weight_col], np.float64)
                       if weight_col else None)

        if kind == "sparse":
            idx, vals, dim = feats
            num_features = self.get_num_features() or dim
            if not num_features:
                raise ValueError(
                    "hashed pair-column input needs numFeatures (the hash-"
                    "space size); call set_num_features")
            check_sparse_indices(idx, num_features)
            state, loss_log = sgd_fit_sparse(
                LOSSES[self.loss_name], idx, vals, y, weights,
                num_features, self._sgd_config())
        elif kind == "mixed":
            dense, cat = feats
            num_features = self.get_num_features()
            if not num_features:
                raise ValueError(
                    "mixed dense+hashed input needs numFeatures (the hash-"
                    "space size); call set_num_features")
            state, loss_log = sgd_fit_mixed(
                LOSSES[self.loss_name], dense, cat, y, weights,
                num_features, self._sgd_config())
        else:
            state, loss_log = sgd_fit(
                LOSSES[self.loss_name], feats, y, weights,
                self._sgd_config())

        model = self.model_cls()
        model.copy_params_from(self)
        model._state = state
        model._loss_log = loss_log
        return model

    def _sgd_config(self) -> SGDConfig:
        return SGDConfig(
            learning_rate=self.get_learning_rate(),
            reg=self.get_reg(),
            elastic_net=self.get_elastic_net(),
            global_batch_size=self.get_global_batch_size(),
            max_epochs=self.get_max_iter(),
            tol=self.get_tol(),
            seed=self.get_seed(),
        )

    def fit_outofcore(self, make_reader, *, num_features: int, mesh=None,
                      sparse: bool = False, mixed: bool = False,
                      checkpoint=None,
                      checkpoint_every_steps: int = 0, resume: bool = False,
                      **stream_kwargs):
        """Out-of-core ``fit``: the dataset streams from ``make_reader()``
        (a fresh per-epoch iterator of host batch dicts, e.g. a re-seeked
        ``DataCacheReader``) instead of living in RAM/HBM — the
        Criteo-scale input path (BASELINE.md north star).  Column names
        follow this estimator's params (featuresCol/labelCol/weightCol);
        with ``sparse=True`` the reader must carry the hashed pair columns
        ``{featuresCol}_indices`` / ``{featuresCol}_values`` instead, and
        with ``mixed=True`` the Criteo-native ``{featuresCol}_dense`` +
        ``{featuresCol}_indices`` pair (implicit categorical value 1.0).
        globalBatchSize and seed are inert here: the reader owns batch size
        and ordering (shuffle when writing the cache or vary segment order
        per epoch).  Extra keyword arguments (``cache_decoded``,
        ``decoded_ram_budget``, ``stream_info``, ``prefetch_*``,
        ``steps_per_dispatch``, ``ell_*``) forward to
        :func:`sgd_fit_outofcore` — in particular
        ``cache_decoded=False`` opts out of the decoded replay cache for
        readers that intentionally vary their stream per epoch, and
        ``steps_per_dispatch`` (default 8) sizes the chunked-scan
        dispatch (W batches per jitted dispatch, bit-exact at any W)."""
        feat = self.get_features_col()
        state, loss_log = sgd_fit_outofcore(
            LOSSES[self.loss_name], make_reader,
            num_features=num_features, config=self._sgd_config(), mesh=mesh,
            features_key=feat,
            label_key=self.get_label_col(),
            weight_key=self.get_weight_col() or None,
            indices_key=f"{feat}_indices" if (sparse or mixed) else None,
            values_key=f"{feat}_values" if sparse else None,
            dense_key=f"{feat}_dense" if mixed else None,
            checkpoint=checkpoint,
            checkpoint_every_steps=checkpoint_every_steps, resume=resume,
            **stream_kwargs)
        model = self.model_cls()
        model.copy_params_from(self)
        model._state = state
        model._loss_log = loss_log
        return model

    def save(self, path: str) -> None:
        persist.save_metadata(self, path)

    @classmethod
    def load(cls, path: str):
        return persist.load_stage_param(path)


# ---------------------------------------------------------------------------
# kernel-registry entry: op ``linear_margins`` (stage convention).  The
# chain-terminal kernel fn IS the registered implementation — offline
# transform, fused pipelines, and the serving executor all dispatch this
# one (fn, static) plan through the registry's shared jit, so any
# consumer's warm-up is a compile-cache hit for the others.
# ---------------------------------------------------------------------------

def _register_linear_kernels() -> None:
    from ...kernels.registry import register_kernel

    register_kernel("linear_margins", "xla", _linear_chain_kernel,
                    convention="stage")


_register_linear_kernels()
