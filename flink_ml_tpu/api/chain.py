"""Operator chaining: fuse runs of row-wise pipeline stages into ONE
jitted program.

The reference's host runtime (Flink) chains consecutive operators into a
single task precisely to eliminate per-operator serialization hops.  Our
stagewise ``PipelineModel.transform`` pays the device-era equivalent —
one jit dispatch **and one host→device→host round trip per stage** —
because every feature transform does ``np.asarray(jit(...)(jnp.asarray(X)))``
on a host-resident Table.  This module removes that boundary:

- **Kernel protocol.**  A stage advertises chainability by implementing
  ``transform_kernel(schema) -> StageKernel | None`` (capability method:
  unported stages simply lack it, ported stages return ``None`` for
  configurations/schemas they cannot express as a pure device fn — e.g.
  string-domain columns, ``handleInvalid="error"`` policies whose raise
  is host control flow).  A :class:`StageKernel` is a pure
  ``columns -> columns`` device function plus a params pytree; all
  instance state lives in ``params`` (runtime device arguments), all
  shape/name configuration in a hashable ``static`` tuple.

- **Segments.**  :func:`compile_pipeline` walks the stage list and
  greedily groups maximal runs of chainable row-independent stages into
  segments; each segment runs as ONE jitted program over a device-resident
  column dict.  Intermediates never materialize on host; only columns the
  output Table (or a terminal's host finalizer) actually needs transfer
  back.  Non-chainable stages (``RandomSplitter``, SQL, string-domain
  tokenizers, GBT — see ``gbt_stage.py``) break the chain and run
  stagewise between segments.

- **Compile sharing.**  The segment runner is THE kernel registry's
  shared plan-static jit (``kernels/registry.py`` — one ``jax.jit``
  whose static argument is the tuple of per-stage ``(fn, static)``
  pairs and whose params are runtime device arrays, device-put once at
  plan build — no per-call re-transfer, and NOT baked as XLA
  constants).  Two plans with the same stage types, column names, and
  shapes — e.g. the per-fold pipelines of a CrossValidator, or
  consecutive hot-swapped model generations — therefore share one
  compiled executable per (schema, bucket), and so do the OTHER
  consumers of the same surface: the serving executors and the models'
  standalone transforms dispatch identical single-stage plans, with
  compile/cache-hit accounting on ``kernels.registry.kernel_stats``.

- **Bit-exactness.**  Every ported kernel mirrors the stage's stagewise
  arithmetic expression at the same f32 precision (host-side exact-compare
  stages carry f32 edge *surrogates* — see ``vector_ops.py``), rows pad
  to the same power-of-two buckets the stagewise predict entry points
  use, and every chained op is row-independent, so the fused output is
  bit-exact with the stagewise path.  Terminal dot products additionally
  route through a context-stable contraction
  (``models/common/linear.py::_stable_margins``): a k=1 matvec would
  accumulate differently standalone vs inside a fused program.

- **Dtype hygiene.**  Host float64 columns silently retrace every jitted
  transform and double the transfer bytes; segment entry normalizes
  floating columns to :attr:`ChainConfig.dtype` (f32 by default) and
  integer/bool columns to int32 on the HOST, so an f64 and an f32 input
  table hit the same compiled program and move half the bytes.
"""

from __future__ import annotations

import threading

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..data.table import Table
from ..kernels.registry import dispatch as _kernel_dispatch
from ..kernels.registry import dispatch_count  # noqa: F401  (re-export)
from ..obs.trace import tracer
from ..utils.padding import DEFAULT_MIN_BUCKET, pad_rows_to_bucket

__all__ = ["StageKernel", "ChainConfig", "CompiledSegment",
           "CompiledPipeline", "UnsafeColumnValues", "apply_kernel",
           "apply_kernel_or_none", "as_matrix", "numeric_entry",
           "compile_pipeline", "run_kernel",
           "chain_disabled", "dispatch_count", "f32_ceil", "f32_floor"]


def as_matrix(col):
    """Chain-side mirror of ``linalg.stack_vectors``'s 1-D promotion: a
    scalar column is n samples of dim 1, not one n-dim row.  Kernels use
    this instead of spelling the reshape locally so the invariant lives
    in one place (works on device and host arrays alike)."""
    return col.reshape(-1, 1) if col.ndim == 1 else col


def numeric_entry(schema, col: str, *, exact_compare: bool = False):
    """The ``(shape, dtype)`` schema entry when ``col`` is
    chain-admissible — present and plain numeric (object/string columns
    stay stagewise) — else ``None``.  This is THE protocol admissibility
    rule; kernels call it instead of respelling the kind check.

    ``exact_compare=True`` additionally rejects float64 columns: segment
    entry rounds them to f32, and a kernel whose OUTPUT is an exact
    comparison decision (threshold crossing, bucket index, vocabulary
    equality) could round a value across the boundary the host-f64
    stagewise compare respects — the f32 threshold surrogates
    (:func:`f32_ceil`/:func:`f32_floor`) are only exact for values that
    are already f32.  Such stages decline to chain on f64 columns and
    run stagewise at full precision instead (continuous kernels keep
    chaining: their contract is value-exactness at f32, which f64 entry
    rounding satisfies by construction)."""
    entry = schema.get(col)
    if entry is None or entry[1].kind not in "fiub":
        return None
    if exact_compare and entry[1].kind == "f" and entry[1].itemsize > 4:
        return None
    return entry


# --------------------------------------------------------------------------
# protocol
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class StageKernel:
    """One stage's pure device kernel.

    ``fn(static, params, cols) -> {produced name: array}`` must be a
    MODULE-LEVEL function (its identity is the jit cache key — a per-call
    closure would defeat cross-plan compile sharing); everything the fn
    reads beyond the column dict goes through ``static`` (hashable,
    shape/name-level) or ``params`` (pytree of arrays, device-put once at
    plan build and passed as runtime jit arguments).

    ``post`` (host, optional) marks a chain TERMINAL: it receives the
    host copies of this stage's produced columns and returns the final
    output columns (e.g. a linear model's f64 decision/raw mapping).  A
    terminal's device outputs are staging values only, so nothing may
    consume them in-segment — the segment ends at the terminal.

    ``pre`` (host, optional) validates raw input columns (e.g.
    Wide&Deep's categorical id range check).  It runs on the segment's
    HOST entry columns, so a stage with a ``pre`` only chains while every
    column named in ``pre_cols`` is a segment-entry passthrough (columns
    produced mid-segment exist only on device).
    """

    fn: Callable[[tuple, Any, Dict[str, Any]], Dict[str, Any]]
    static: tuple
    params: Any
    consumes: Tuple[str, ...]
    produces: Tuple[str, ...]
    post: Optional[Callable[[Dict[str, np.ndarray]], Dict[str, np.ndarray]]] = None
    pre: Optional[Callable[[Dict[str, np.ndarray]], None]] = None
    pre_cols: Tuple[str, ...] = ()


@dataclass(frozen=True)
class ChainConfig:
    """Plan-build configuration (defaults match the stagewise predict
    entry points, so fused and stagewise pad to identical shapes)."""

    dtype: Any = np.float32
    min_bucket: int = DEFAULT_MIN_BUCKET


# --------------------------------------------------------------------------
# enable/disable switch (the tests' stagewise oracle)
# --------------------------------------------------------------------------

_STATE = threading.local()


def _enabled() -> bool:
    return getattr(_STATE, "enabled", True)


class chain_disabled:
    """Context manager forcing the stagewise path — the bit-exactness
    oracle in tests."""

    def __enter__(self):
        self._prev = _enabled()
        _STATE.enabled = False
        return self

    def __exit__(self, *exc):
        _STATE.enabled = self._prev
        return False


# --------------------------------------------------------------------------
# exact f32 comparison surrogates
# --------------------------------------------------------------------------

def f32_ceil(x: np.ndarray) -> np.ndarray:
    """Smallest float32 >= x (elementwise).  For any f32 value ``v`` and
    f64 threshold ``t``: ``t <= v  ⟺  f32_ceil(t) <= v`` — there is no
    f32 value strictly between ``t`` and ``f32_ceil(t)``.  This is what
    lets the host-f64 exact-compare stages (Bucketizer, KBinsDiscretizer)
    run their searchsorted semantics bit-exactly inside an f32 segment."""
    x = np.asarray(x, np.float64)
    c = x.astype(np.float32)
    low = c.astype(np.float64) < x
    out = c.copy()
    out[low] = np.nextafter(c[low], np.float32(np.inf))
    return out


def f32_floor(x: np.ndarray) -> np.ndarray:
    """Largest float32 <= x (elementwise): ``v > t  ⟺  v > f32_floor(t)``
    for f32 ``v``."""
    x = np.asarray(x, np.float64)
    c = x.astype(np.float32)
    high = c.astype(np.float64) > x
    out = c.copy()
    out[high] = np.nextafter(c[high], np.float32(-np.inf))
    return out


# --------------------------------------------------------------------------
# the shared segment runner — ONE jit for every plan
# --------------------------------------------------------------------------
# The runner itself (the plan-static jit with the rounding barrier) moved
# to kernels/registry.py: it is THE repo-wide dispatch surface now, shared
# with the serving executors and the models' own predict entry points, so
# the same (plan, schema, bucket) warmed by any consumer is a compile-cache
# hit for the others.  This module keeps the chain-facing helpers.


def run_kernel(kernel: StageKernel, table: Table, *,
               params: Any = None, dtype=np.float32,
               min_bucket: int = DEFAULT_MIN_BUCKET,
               op: Optional[str] = None) -> Dict[str, np.ndarray]:
    """Run ONE stage's kernel as a single-stage plan through the shared
    registry dispatch (normalize -> pre -> bucket-pad -> dispatch ->
    fetch -> post).  ``params`` overrides ``kernel.params`` with
    already-device-resident arrays (the serving executors device-put
    once per generation instead of re-transferring per request); ``op``
    labels the registry's per-op counters.

    Raises :class:`UnsafeColumnValues` when a consumed integer column
    carries values outside the f32-exact range — callers fall back to
    their legacy host path for that call (see
    :func:`apply_kernel_or_none`)."""
    host = {n: _normalize_col(table[n], dtype) for n in kernel.consumes}
    if kernel.pre is not None:
        kernel.pre(host)
    with tracer.span("bucket_pad", cat="kernel", op=op):
        padded, n = pad_rows_to_bucket(tuple(host.values()),
                                       min_bucket=min_bucket)
        cols = dict(zip(host, padded))
    out = _kernel_dispatch(((kernel.fn, kernel.static),),
                           (kernel.params if params is None else params,),
                           cols, op=op)
    # device_execute: the np.asarray fetch IS the completion fence (the
    # StepTimer probe pattern — device_get on the host side of the
    # dispatch boundary, never a block inside a step fn), so this span
    # covers queue + device compute + transfer of the produced columns
    with tracer.span("device_execute", cat="kernel", op=op,
                     bucket=int(next(iter(cols.values())).shape[0])
                     if cols else 0):
        fetched = {name: np.asarray(out[name])[:n]
                   for name in kernel.produces}
    if kernel.post is not None:
        fetched.update(kernel.post(fetched))
    return fetched


def apply_kernel(kernel: StageKernel, table: Table, *,
                 dtype=np.float32,
                 min_bucket: int = DEFAULT_MIN_BUCKET) -> Dict[str, np.ndarray]:
    """Run ONE stage's kernel stagewise (a single-stage segment).

    Ported stages whose legacy transform was host-f64 numpy route their
    standalone ``transform`` through this, so the stagewise and fused
    paths literally share one compiled expression — bit-exactness between
    them is by construction, and the stage's offline transform gains the
    bucket-padded zero-retrace behavior of the predict entry points."""
    return run_kernel(kernel, table, dtype=dtype, min_bucket=min_bucket)


#: integers beyond +-2^24 are not exactly representable in the f32 the
#: kernels compare/promote with (and 2^31 would overflow the int32 cast);
#: a batch carrying them falls back stagewise rather than silently
#: diverging from the host-f64 path
_INT_EXACT_BOUND = 1 << 24


class UnsafeColumnValues(Exception):
    """Batch values the f32 segment cannot represent exactly — the caller
    falls back to the stagewise path for THIS call (plan stays valid)."""


def _normalize_col(arr: np.ndarray, dtype) -> np.ndarray:
    """Host-side dtype hygiene: floating -> config dtype, int/bool ->
    int32.  Casting BEFORE device_put halves the transfer bytes for f64
    inputs and makes f64-vs-f32 callers share one compiled program."""
    arr = np.asarray(arr)
    if arr.dtype.kind == "f" and arr.dtype != np.dtype(dtype):
        return arr.astype(dtype)
    if arr.dtype.kind in "iu":
        if arr.size and (int(arr.min()) < -_INT_EXACT_BOUND
                         or int(arr.max()) > _INT_EXACT_BOUND):
            raise UnsafeColumnValues(
                f"integer column values exceed +-2^24 "
                f"({int(arr.min())}..{int(arr.max())})")
        if arr.dtype != np.dtype(np.int32):
            return arr.astype(np.int32)
    elif arr.dtype.kind == "b":
        return arr.astype(np.int32)
    return arr


def apply_kernel_or_none(kernel: Optional[StageKernel], table: Table,
                         **kwargs) -> Optional[Dict[str, np.ndarray]]:
    """:func:`apply_kernel` that answers ``None`` instead of raising when
    the kernel is absent or this batch's values are f32-unsafe — the
    standalone stage transforms branch to their legacy host math on
    ``None``."""
    if kernel is None:
        return None
    try:
        return apply_kernel(kernel, table, **kwargs)
    except UnsafeColumnValues:
        return None


def raw_schema(table: Table) -> tuple:
    """Hashable (name, trailing shape, RAW dtype) signature.  Plan caches
    key on this — not on the device-normalized view — because kernel
    admissibility depends on the input float width (exact-compare stages
    decline f64, see :func:`numeric_entry`); an f64 and an f32 view of
    the same flow need different plans, whose matching segments still
    share jit executables through the plan-static segment runner."""
    return tuple((n, s, dt.str) for n, (s, dt)
                 in sorted(table.schema().items()))


def _device_schema(table: Table, dtype) -> tuple:
    """The normalized (name, trailing shape, device dtype) signature a
    plan is keyed on — f64 and f32 views of the same data collide."""
    sig = []
    for name, (shape, dt) in table.schema().items():
        if dt.kind == "f":
            dt = np.dtype(dtype)
        elif dt.kind in "iub":
            dt = np.dtype(np.int32)
        sig.append((name, shape, dt.str))
    return tuple(sig)


# --------------------------------------------------------------------------
# compiled plan
# --------------------------------------------------------------------------

class CompiledSegment:
    """A maximal run of chainable stages compiled as one program.

    ``run`` normalizes + pads the entry columns on host, makes ONE jitted
    call, fetches only the columns the output (or a terminal's host
    finalizer) needs, and reassembles the Table in the stagewise column
    order.  Entry columns that no kernel replaces are reattached from the
    ORIGINAL host arrays — bit-exact passthrough with zero transfer."""

    def __init__(self, stages: Sequence, kernels: Sequence[StageKernel],
                 out_names: Sequence[str], config: ChainConfig):
        self.stages = list(stages)
        self.kernels = list(kernels)
        self.config = config
        self.plan = tuple((k.fn, k.static) for k in kernels)
        # device_put once: params ride every call as device-resident args
        self.params = tuple(jax.device_put(k.params) for k in kernels)
        produced: set = set()
        for k in kernels:
            produced.update(k.produces)
        self.produced = produced
        # columns that must cross host->device: everything any kernel
        # consumes that an earlier kernel did not itself produce
        entry: List[str] = []
        seen: set = set()
        for k in kernels:
            for name in k.consumes:
                if name not in seen and name not in entry:
                    entry.append(name)
            seen.update(k.produces)
        self.entry_cols = tuple(entry)
        for k in kernels:
            missing = [c for c in k.pre_cols if c not in self.entry_cols]
            if missing:
                # fail at plan build, not with a KeyError on the first
                # serving request: pre() only ever sees host entry columns
                raise ValueError(
                    f"StageKernel pre_cols {missing} are not entry columns "
                    f"of their segment — a host pre hook can only validate "
                    f"columns some kernel in the segment consumes from the "
                    f"segment input")
        self.out_names = tuple(out_names)
        terminal = kernels[-1] if kernels and kernels[-1].post else None
        # device->host fetch set: final columns a kernel produced, plus
        # the terminal's staging outputs its host finalizer reads
        fetch = [n for n in self.out_names if n in produced]
        if terminal is not None:
            fetch += [n for n in terminal.produces if n not in fetch]
        self.fetch_cols = tuple(fetch)
        self.posts = [k.post for k in kernels if k.post]
        self.pres = [k.pre for k in kernels if k.pre]

    @property
    def num_stages(self) -> int:
        return len(self.stages)

    def run(self, table: Table) -> Table:
        cfg = self.config
        try:
            host = {n: _normalize_col(table[n], cfg.dtype)
                    for n in self.entry_cols}
        except UnsafeColumnValues:
            # this batch carries integers f32 cannot represent exactly —
            # run the segment's own stages stagewise (per-call; the plan
            # stays valid for safe batches)
            for stage in self.stages:
                (table,) = stage.transform(table)
            return table
        for pre in self.pres:
            pre(host)
        n = table.num_rows
        if host:
            padded, n = pad_rows_to_bucket(
                tuple(host.values()), min_bucket=cfg.min_bucket)
            cols = dict(zip(host, padded))
        else:
            cols = {}
        out = _kernel_dispatch(self.plan, self.params, cols)
        fetched = {name: np.asarray(out[name])[:n]
                   for name in self.fetch_cols}
        for post in self.posts:
            fetched.update(post(fetched))
        final: Dict[str, np.ndarray] = {}
        for name in self.out_names:
            final[name] = (fetched[name] if name in fetched
                           else table[name])
        return Table(final)


class _HostStage:
    """A non-chainable stage in the plan: runs its own transform
    (possibly multiplying tables, e.g. RandomSplitter)."""

    def __init__(self, stage):
        self.stage = stage

    def run_all(self, tables: List[Table]) -> List[Table]:
        out: List[Table] = []
        for t in tables:
            out.extend(self.stage.transform(t))
        return out


class CompiledPipeline:
    """The fused execution plan: segments interleaved with stagewise
    fallback stages, applied table-wise (a multi-output host stage fans
    the flow out; later items map over every table)."""

    def __init__(self, items: List, config: ChainConfig,
                 schema_key: tuple):
        self.items = items
        self.config = config
        self.schema_key = schema_key

    @property
    def segments(self) -> List[CompiledSegment]:
        return [i for i in self.items if isinstance(i, CompiledSegment)]

    @property
    def worthwhile(self) -> bool:
        """Fusing pays once any segment merges >= 2 stages; a plan of
        singletons is the stagewise path with extra bookkeeping."""
        return any(s.num_stages >= 2 for s in self.segments)

    def describe(self) -> List[Tuple[str, int]]:
        """[('segment', n_stages) | ('stage', 1)] in pipeline order —
        what the chain-break tests assert segment boundaries on."""
        return [("segment", i.num_stages) if isinstance(i, CompiledSegment)
                else ("stage", 1) for i in self.items]

    def transform(self, *inputs) -> List[Table]:
        tables = list(inputs)
        for item in self.items:
            if isinstance(item, CompiledSegment):
                tables = [item.run(t) for t in tables]
            else:
                tables = item.run_all(tables)
        return tables


def compile_pipeline(pipeline_model, example: Table, *,
                     dtype=np.float32,
                     min_bucket: int = DEFAULT_MIN_BUCKET) -> CompiledPipeline:
    """Compile a fitted ``PipelineModel`` into a fused plan.

    Walks the stage list with ``example`` (any table carrying the request
    schema — row VALUES only steer non-chainable fallback stages), asking
    each stage for its kernel at the current schema and greedily grouping
    maximal chainable runs into :class:`CompiledSegment`\\s.  A terminal
    kernel (one with a host ``post``) closes its segment; a stage without
    a kernel breaks the chain and runs stagewise.
    """
    config = ChainConfig(dtype=dtype, min_bucket=min_bucket)
    items: List = []
    current = example
    run_stages: List = []
    run_kernels: List[StageKernel] = []
    produced_in_run: set = set()

    def flush(out_table: Table) -> None:
        nonlocal run_stages, run_kernels, produced_in_run
        if not run_stages:
            return
        seg = CompiledSegment(run_stages, run_kernels,
                              out_table.column_names, config)
        items.append(seg)
        run_stages, run_kernels, produced_in_run = [], [], set()

    for stage in pipeline_model.stages:
        kernel = None
        if hasattr(stage, "transform_kernel"):
            try:
                kernel = stage.transform_kernel(current.schema())
            except NotImplementedError:
                kernel = None
        if kernel is not None and kernel.pre is not None and \
                any(c in produced_in_run for c in kernel.pre_cols):
            # host pre-validation needs raw entry columns; a mid-segment
            # input only exists on device — close the running segment so
            # its outputs host-materialize and this stage opens a FRESH
            # segment whose entry columns pre() can see (it stays fused,
            # just across a segment boundary, instead of silently
            # skipping validation or dropping to per-stage dispatch)
            flush(current)
        next_table = stage.transform(current)[0]
        if kernel is not None:
            run_stages.append(stage)
            run_kernels.append(kernel)
            produced_in_run.update(kernel.produces)
            current = next_table
            if kernel.post is not None:       # terminal closes the segment
                flush(current)
        else:
            flush(current)
            items.append(_HostStage(stage))
            current = next_table
    flush(current)
    return CompiledPipeline(items, config,
                            _device_schema(example, dtype))
