"""Compiled executors: ``ServableModel`` wraps a fitted Model for serving.

The adapter's contract:

- **Bucketed shapes.**  Every predict pads its rows to a power-of-two
  bucket (``utils/padding.py``), so the full space of request/batch sizes
  in ``[1, max_batch_rows]`` maps onto ``log2`` many compiled programs.
- **Eager warm-up.**  ``warm_up()`` runs one predict per bucket BEFORE the
  endpoint reports ready, so steady-state traffic of mixed sizes triggers
  zero new XLA compiles (asserted in ``tests/test_serving.py`` with a JAX
  lowering counter).
- **Bit-exact with offline ``transform()``.**  The served computation is
  either literally ``model.transform`` (the generic adapter — same jit
  cache, same host post-processing) or an expression-identical jitted
  score function for the specialized families; pad rows are inert in
  every row-independent predict, so serving a request returns exactly the
  rows offline ``transform`` would.
- **One compiled surface.**  The specialized executors dispatch their
  model's chain-kernel ``(fn, static)`` plan through the kernel
  registry's shared plan-static jit (``kernels/registry.py``) — the
  same executable the fused pipelines and the models' own ``transform``
  entry points run, so warm-up anywhere is a compile-cache hit
  everywhere, and the registry's compile/cache-hit gauges account it.
  On TPU the shared jit donates the padded column dict (the per-request
  transfer buffer is dead after the call — donation lets XLA reuse the
  HBM allocation instead of holding both); donation is skipped on
  backends that ignore it (CPU) to avoid spurious warnings.
"""

from __future__ import annotations

import copy

from typing import Any, Optional, Sequence

import jax
import numpy as np

from ..data.table import Table
from ..robustness.faults import fault_point
from ..utils.padding import (
    DEFAULT_BUCKET_CAP,
    DEFAULT_MIN_BUCKET,
    bucket_rows,
    bucket_sizes,
)

__all__ = ["ServableModel", "make_servable"]


# The per-family serving jits collapsed into the kernel registry's ONE
# dispatch surface (kernels/registry.py, PR 10): the specialized
# executors below run their model's chain-kernel (fn, static) plan
# through the same plan-static jit the fused pipelines and the models'
# own predict entry points use, so a shape warmed by ANY consumer is a
# compile-cache hit for serving (and vice versa).  Donation of the
# per-request transfer buffer on TPU moved into the shared jit.


class ServableModel:
    """A fitted Model adapted for online serving: schema-checked,
    bucket-padded, warm-compiled predict.

    ``example`` is a small Table carrying the REQUEST schema (the columns
    clients send — typically one row of the training table minus the
    label); warm-up tiles it to every bucket size.  The generic adapter
    serves ANY stage whose ``transform`` is row-independent; the
    specialized subclasses below add donated-input jitted score paths for
    the families the serving layer optimizes.
    """

    #: precisions this executor family can serve at.  "int8" means the
    #: bind path quantizes the published params (per-channel max-abs,
    #: ``kernels/quantize.py``) and scores through the op's "int8"
    #: registry backend; only the registry-dispatched families support
    #: it — the generic ``model.transform`` adapter and the fused
    #: pipeline plan have no quantized param seam, so they refuse at
    #: construction rather than silently serving f32.
    supported_precisions = ("f32",)

    def __init__(self, model, example: Table, *,
                 max_batch_rows: int = 256,
                 min_bucket: int = DEFAULT_MIN_BUCKET,
                 output_cols: Optional[Sequence[str]] = None,
                 precision: str = "f32"):
        if precision not in self.supported_precisions:
            raise TypeError(
                f"{type(self).__name__} cannot serve at precision "
                f"{precision!r} (supports {self.supported_precisions}); "
                "int8 covers the registry-dispatched families only")
        self.precision = precision
        if not hasattr(model, "transform"):
            raise TypeError(
                f"{type(model).__name__} has no transform(); only fitted "
                "Models/Transformers are servable")
        if example.num_rows == 0:
            raise ValueError("example must carry at least one row")
        if max_batch_rows > DEFAULT_BUCKET_CAP:
            raise ValueError(
                f"max_batch_rows={max_batch_rows} exceeds the bucket cap "
                f"({DEFAULT_BUCKET_CAP}) above which predict paths keep "
                "exact shapes — the zero-retrace warm-up cannot cover it")
        self.model = model
        self.example = example
        self.min_bucket = min_bucket
        self.max_batch_rows = max_batch_rows
        self.buckets = bucket_sizes(max_batch_rows, min_bucket)
        self.output_cols = tuple(output_cols) if output_cols else None
        self._schema = set(example.column_names)
        self._ready = False
        #: readiness accounting (ISSUE 12): wall time to ready and the
        #: per-bucket executable source — populated by :meth:`warm_up`
        self.warmup_report: Optional[dict] = None

    #: True for executor families whose compiled score programs take the
    #: params as RUNTIME arguments (the module-global serving jit cache):
    #: a same-shape new generation can :meth:`rebind` without warm-up —
    #: the continuous-learning delta-publish fast path.  The generic
    #: adapter serves through ``model.transform``, whose jit caches may
    #: bake params in as constants, so it stays False.
    rebind_safe = False

    def rebind(self, model) -> "ServableModel":
        """A ready clone of this servable scoring with ``model`` (same
        example/buckets/output schema).  Only meaningful when
        ``rebind_safe``: the clone inherits readiness WITHOUT a warm-up
        because every compiled program it can reach is already compiled
        (params are runtime args) — publish becomes a buffer swap.
        Callers own the same-shape contract; a shape change must go
        through the full deploy path instead."""
        if not self.rebind_safe:
            raise TypeError(
                f"{type(self).__name__} is not rebind-safe: its transform "
                "path may bake params into compiled programs — deploy the "
                "new version through the registry (load->warm->swap)")
        clone = copy.copy(self)
        clone.model = model
        return clone

    # -- predict ------------------------------------------------------------
    def check_schema(self, table: Table) -> None:
        names = set(table.column_names)
        if names != self._schema:
            raise ValueError(
                f"request schema {sorted(names)} does not match the "
                f"endpoint's example schema {sorted(self._schema)}")

    def bucket_for(self, rows: int) -> int:
        return bucket_rows(rows, min_bucket=self.min_bucket)

    def predict(self, table: Table) -> Table:
        """Serve one (micro-)batch: returns the transform output for
        exactly ``table``'s rows, computed at the padded bucket shape."""
        fault_point("serving.predict")
        out = self._run(table)
        if self.output_cols:
            out = out.select(*self.output_cols)
        return out

    def _run(self, table: Table) -> Table:
        # generic adapter: the model's own transform IS the compiled
        # executor — its predict entry points bucket-pad internally
        # (utils/padding.py), so this path shares the offline jit cache
        # and is bit-exact with offline transform by construction
        return self.model.transform(table)[0]

    # -- warm-up ------------------------------------------------------------
    def _tiled_example(self, rows: int) -> Table:
        reps = -(-rows // self.example.num_rows)
        return Table({
            name: np.concatenate([col] * reps, axis=0)[:rows]
            for name, col in self.example.to_dict().items()})

    def warm_up(self) -> "ServableModel":
        """Compile every bucket eagerly (one predict per ladder rung) so
        the endpoint only reports ready once steady state is retrace-free.
        Runs on the deploying thread — OFF the serving path, so a hot-swap
        warms the incoming version while the old one keeps serving.

        Populates :attr:`warmup_report`: total wall to ready plus, per
        bucket, whether readiness cost a live XLA **compile**, a
        persistent-cache **aot** load (``kernels/aot.py``), or rode an
        in-process **cache** hit — diffed from the registry's
        THIS-THREAD counters (``kernel_stats.thread_counts``), so
        cold-start composition is attributed, not guessed, and a
        hot-swap warming on the deploy thread is never mislabeled by
        the old generation's concurrent serving dispatches.  (Servables
        whose predict path does not go through the registry dispatch —
        the generic ``model.transform`` adapter — report
        ``untracked``.)"""
        import time as _time

        from ..kernels.registry import kernel_stats

        fault_point("serving.warm_up")
        report: dict = {"wall_s": None, "precision": self.precision,
                        "buckets": {}}
        t_start = _time.perf_counter()
        for bucket in self.buckets:
            compiles0, aot0, hits0 = kernel_stats.thread_counts()
            t0 = _time.perf_counter()
            self._run(self._tiled_example(bucket))
            ms = (_time.perf_counter() - t0) * 1e3
            compiles1, aot1, hits1 = kernel_stats.thread_counts()
            if compiles1 > compiles0:
                source = "compile"
            elif aot1 > aot0:
                source = "aot"
            elif hits1 > hits0:
                source = "cache"
            else:
                source = "untracked"
            report["buckets"][bucket] = {"source": source,
                                         "ms": round(ms, 3),
                                         "precision": self.precision}
        report["wall_s"] = round(_time.perf_counter() - t_start, 4)
        sources = [b["source"] for b in report["buckets"].values()]
        report["compiled"] = sources.count("compile")
        report["aot_loaded"] = sources.count("aot")
        report["cache_hits"] = sources.count("cache")
        self.warmup_report = report
        self._ready = True
        return self

    @property
    def ready(self) -> bool:
        return self._ready


# -- specialized executors ---------------------------------------------------

class _KernelServable(ServableModel):
    """Families whose model exposes a chain ``transform_kernel``: serving
    runs that kernel's ``(fn, static)`` plan through the kernel
    registry's shared dispatch surface (``api/chain.py::run_kernel``).

    The plan is built once per generation from the EXAMPLE schema and
    its params are device-put once, so steady-state requests pay one
    dispatch with zero host->device param traffic — and because the
    compiled program identity is the same (fn, static) pair the fused
    pipelines and the model's own ``transform`` dispatch, a bucket
    warmed by any consumer is a compile-cache hit here (and a serving
    warm-up pre-compiles the offline paths).  ``rebind`` (the
    continuous-learning delta-publish fast path) rebuilds only the
    cached params — same plan, same shapes, zero new lowerings."""

    rebind_safe = True
    op_label: Optional[str] = None
    supported_precisions = ("f32", "int8")

    def __init__(self, model, example: Table, **kwargs: Any):
        super().__init__(model, example, **kwargs)
        self._build_kernel()

    def _build_kernel(self) -> None:
        # transform_kernel's "unported config" signal is returning None
        # (all three families); a RAISE here is a genuine defect (e.g.
        # an unfitted model) and must surface at construction, not
        # silently degrade every request to the generic transform path
        kernel = self.model.transform_kernel(self.example.schema())
        if kernel is None and self.precision == "int8":
            # no chain plan for this config (e.g. sparse linear layouts)
            # means no quantized path either; silently serving f32 under
            # an int8 contract would lie to the capacity planner
            raise TypeError(
                f"{type(self.model).__name__} has no chain kernel for "
                "this example schema — precision='int8' requires the "
                "registry-dispatched plan; serve this config at f32")
        if kernel is not None and self.precision == "int8":
            # THE calibration capture point: quantize this generation's
            # params and swap the plan's fn for the op's "int8" registry
            # backend.  rebind() re-runs this bind on the clone, so a
            # delta publish re-derives scales from the NEW params before
            # the swap — stale scales never serve (ARCHITECTURE.md
            # "Int8 serving").  Same (fn, static) plan identity across
            # generations => rebind stays zero-new-lowerings.
            import dataclasses

            from ..kernels.quantize import quantize_stage_params
            from ..kernels.registry import lookup

            entry = lookup(self.op_label, backend="int8")
            kernel = dataclasses.replace(
                kernel, fn=entry.fn,
                params=quantize_stage_params(self.op_label,
                                             kernel.params))
        self._kernel = kernel
        self._kernel_params = (jax.device_put(kernel.params)
                               if kernel is not None else None)

    def rebind(self, model) -> "ServableModel":
        clone = super().rebind(model)
        clone._build_kernel()
        return clone

    def _run(self, table: Table) -> Table:
        from ..api.chain import UnsafeColumnValues, run_kernel

        kernel = self._kernel
        if kernel is None:
            return self.model.transform(table)[0]
        # kernel admissibility was decided on the EXAMPLE schema; a
        # request re-spelling a consumed column as object dtype (e.g. a
        # SparseVector features column under the same name) must route
        # to the model's own transform, exactly like the pre-registry
        # per-request resolve_features fallback did
        if any(np.asarray(table[n]).dtype.kind not in "fiub"
               for n in kernel.consumes):
            return self.model.transform(table)[0]
        try:
            cols = run_kernel(kernel, table, params=self._kernel_params,
                              min_bucket=self.min_bucket, op=self.op_label)
        except (UnsafeColumnValues, KeyError):
            # f32-unsafe int batch, or a request schema the kernel's
            # columns don't cover — the model's own transform owns those
            return self.model.transform(table)[0]
        out = table
        for name in (n for n in cols if n not in kernel.produces):
            out = out.with_column(name, cols[name])
        return out


class _LinearServable(_KernelServable):
    """Linear family (LogisticRegression / LinearRegression / LinearSVC):
    dense features score through the registry-dispatched margin kernel;
    sparse and mixed layouts fall back to the model's own (bucket-routed)
    transform (their ``transform_kernel`` is None)."""

    op_label = "linear_margins"


class _KMeansServable(_KernelServable):
    """KMeansModel: registry-dispatched nearest-centroid assign."""

    op_label = "kmeans_assign"


class _WideDeepServable(_KernelServable):
    """WideDeepModel: registry-dispatched sigmoid(forward) (the id range
    check runs as the kernel's host ``pre``, the in-kernel offset is an
    exact int add)."""

    op_label = "widedeep_scores"


class _RetrieveServable(_KernelServable):
    """IVFIndex — the first NON-model servable: the fused IVF / IVF-PQ
    scan+top-k plan serves through exactly the kernel seams the model
    families do (same plan identity as the index's own ``transform``, so
    warmed buckets are compile-cache hits; rebind swaps posting-list
    params with zero new lowerings).  No "int8" registry backend — PQ
    codes ARE the compressed representation, carried by the f32 plan."""

    op_label = "retrieve"
    supported_precisions = ("f32",)


class _PipelineServable(ServableModel):
    """PipelineModel: the whole chain (preprocess + score) compiles into
    fused segments (``api/chain.py``) at deploy time — a fully-chainable
    pipeline serves every micro-batch in ONE jitted dispatch.  ``warm_up``
    (inherited) tiles the example through every bucket, so each segment
    compiles per bucket OFF the serving path; plans with the same stage
    types share compiled executables across hot-swapped generations via
    the plan-static segment jit."""

    def __init__(self, model, example: Table, **kwargs: Any):
        super().__init__(model, example, **kwargs)
        from ..api.chain import compile_pipeline, raw_schema

        self._plan_schema = raw_schema(example)
        try:
            # the plan must pad with THIS servable's bucket floor —
            # warm_up tiles buckets from self.min_bucket, and a plan
            # padding to a different ladder would compile on the serving
            # path after the endpoint reported ready
            plan = compile_pipeline(model, example,
                                    min_bucket=self.min_bucket)
            self._plan = plan if plan.worthwhile else None
        except NotImplementedError:  # unported stage mix: stagewise serve
            self._plan = None

    def _run(self, table: Table) -> Table:
        # the plan's kernel admissibility was decided on the EXAMPLE's
        # raw dtypes (exact-compare stages decline f64); a request with
        # a different raw schema routes through model.transform, whose
        # own plan cache keys on the request schema
        if self._plan is not None:
            from ..api.chain import raw_schema

            if raw_schema(table) == self._plan_schema:
                return self._plan.transform(table)[0]
        return self.model.transform(table)[0]


def make_servable(model, example: Table, *, emb_cache: bool = False,
                  **kwargs: Any) -> ServableModel:
    """Adapt a fitted Model for serving, picking the specialized executor
    for the covered families (linear / KMeans / Wide&Deep; whole
    PipelineModels fuse their chainable stage runs into single-dispatch
    segments; GBT and every other row-independent transform serve through
    the generic adapter, whose predict entry points are bucket-routed
    since this PR).

    ``emb_cache=True`` (WideDeep only) serves through the
    device-resident embedding-row cache (``serving/embcache.py``,
    ISSUE 14): only the hot table blocks live in HBM;
    ``cache_block_rows`` / ``cache_capacity_blocks`` size it.

    ``precision="int8"`` (the registry-dispatched families + the cached
    WideDeep path) quantizes the published params at bind time
    (per-channel max-abs, ``kernels/quantize.py``) and scores through
    the op's "int8" registry backend — roughly 4x smaller resident
    params (2x for the row cache's codes+scales pools) at an accuracy
    envelope the parity matrix gates.  Families without a quantized
    seam raise TypeError rather than silently serving f32."""
    from ..api.pipeline import PipelineModel
    from ..models.clustering.kmeans import KMeansModel
    from ..models.common.linear import LinearModelBase
    from ..models.recommendation.widedeep import WideDeepModel
    from ..retrieval.ivf import IVFIndex

    if isinstance(model, PipelineModel):
        cls: type = _PipelineServable
    elif isinstance(model, LinearModelBase):
        cls = _LinearServable
    elif isinstance(model, KMeansModel):
        cls = _KMeansServable
    elif isinstance(model, IVFIndex):
        cls = _RetrieveServable
    elif isinstance(model, WideDeepModel):
        if emb_cache:
            from .embcache import CachedWideDeepServable

            return CachedWideDeepServable(model, example, **kwargs)
        cls = _WideDeepServable
    else:
        cls = ServableModel
    if emb_cache:
        raise TypeError(
            f"emb_cache=True only applies to WideDeepModel (its stacked "
            f"vocab tables are the cacheable operand), not "
            f"{type(model).__name__}")
    return cls(model, example, **kwargs)
