"""Wide&Deep two-tower recommender (BASELINE.json stretch config 5).

Not present in the reference (its iteration runtime was never stretched to
DNNs — that's the point of this config): a wide linear tower over
categorical ids + dense features, and a deep tower of embeddings + MLP,
trained jointly with Adam on binary cross-entropy.

TPU-native design:
- one stacked embedding table ``(total_vocab, emb_dim)`` — lookups are a
  single gather, MXU-friendly; per-field vocabularies are offset into it
- the whole multi-epoch training loop is fused (``iterate`` + inner
  ``lax.scan`` over mini-batches), parameters and optimizer state live in
  HBM between epochs
- sharding: batch over the mesh's ``data`` axis; with a ``model`` axis the
  embedding dim and MLP hidden dims shard over it (tensor parallelism) —
  see ``build_sharded_train_step`` which __graft_entry__ dry-runs multichip
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from ...api.stage import Estimator, Model
from ...data.table import Table
from ...iteration import (
    HandedOver,
    IterationBodyResult,
    IterationConfig,
    iterate,
    with_program_key,
)
from ...linalg import float32_rows
from ...obs.trace import tracer
from ...params.param import (
    BoolParam,
    FloatParam,
    IntArrayParam,
    IntParam,
    ParamValidators,
    StringParam,
)
from ...params.shared import (
    HasGlobalBatchSize,
    HasLabelCol,
    HasMaxIter,
    HasPredictionCol,
    HasRawPredictionCol,
    HasSeed,
)
from ...parallel.mesh import default_mesh, replicate
from ...utils import persist
from ..common.losses import logistic_loss
from ..common.sgd import (
    DEFAULT_GLOBAL_BATCH,
    plan_epoch_layout,
    prepare_epoch_tensor,
)

__all__ = ["WideDeep", "WideDeepModel", "WideDeepParams"]


class WideDeepParams(HasLabelCol, HasPredictionCol, HasRawPredictionCol,
                     HasMaxIter, HasGlobalBatchSize, HasSeed):
    DENSE_FEATURES_COL = StringParam(
        "denseFeaturesCol", "Dense feature matrix column.",
        default="denseFeatures")
    CAT_FEATURES_COL = StringParam(
        "catFeaturesCol", "Categorical id matrix column (int).",
        default="catFeatures")
    VOCAB_SIZES = IntArrayParam(
        "vocabSizes", "Vocabulary size per categorical field.",
        default=None, validator=lambda v: v is None or (len(v) > 0 and
                                                        all(s > 0 for s in v)))
    EMBEDDING_DIM = IntParam("embeddingDim", "Embedding width per field.",
                             default=8, validator=ParamValidators.gt(0))
    HIDDEN_UNITS = IntArrayParam("hiddenUnits", "Deep-tower MLP widths.",
                                 default=(64, 32))
    LEARNING_RATE = FloatParam("learningRate", "Adam learning rate.",
                               default=1e-2, validator=ParamValidators.gt(0))
    LAZY_EMB_OPT = BoolParam(
        "lazyEmbeddingOptimizer",
        "LazyAdam for the embedding/wide-cat tables: Adam state and "
        "parameters update only at the rows each batch touches; "
        "untouched rows keep param AND optimizer state exactly (no "
        "momentum tail) — the standard LazyAdam semantic deviation "
        "from dense Adam.  NOTE the r4 TPU measurement: at 2^20 total "
        "vocab the dense streams WIN (18.8 vs 42.5 ms/step — XLA's "
        "213k-row scatter costs more than streaming the whole table), "
        "so this stays opt-in for its semantics, and for vocabularies "
        "large enough that full-table m/v/param streams dominate or "
        "cannot fit.",
        default=False)
    ROUTED_EMB_GRAD = StringParam(
        "routedEmbeddingGrad",
        "Statically-routed table gradients (ops/emb_grad.py) for the "
        "dense-Adam fit: the bounded fit replays a fixed epoch tensor, "
        "so the per-step slot->row sort is computed once on the host "
        "and every training step's embedding/wide-table scatter becomes "
        "conflict-free streaming work (sorted permutation gather + "
        "segmented fold + unique sorted scatter-set) instead of XLA's "
        "per-slot random read-modify-write — the same static-routing "
        "insight as the LR family's ELL kernels.  Results equal the "
        "scatter-add up to f32 summation order.  'auto' (default) = on "
        "for the in-memory dense-Adam fit(), off for streaming fits "
        "(their batches are not replayed) and under "
        "lazyEmbeddingOptimizer; 'on' forces it (error if lazy); "
        "'off' keeps the autodiff scatter.",
        default="auto",
        validator=ParamValidators.in_array(("auto", "on", "off")))

    def get_vocab_sizes(self):
        return self.get(WideDeepParams.VOCAB_SIZES)

    def set_vocab_sizes(self, v):
        return self.set(WideDeepParams.VOCAB_SIZES, v)

    def set_embedding_dim(self, v: int):
        return self.set(WideDeepParams.EMBEDDING_DIM, v)

    def set_hidden_units(self, v):
        return self.set(WideDeepParams.HIDDEN_UNITS, v)

    def set_learning_rate(self, v: float):
        return self.set(WideDeepParams.LEARNING_RATE, v)


def _field_offsets(vocab_sizes) -> np.ndarray:
    return np.concatenate([[0], np.cumsum(vocab_sizes)[:-1]]).astype(np.int32)


#: half the width of the embedding table's uniform start: a standard
#: deviation of 0.05
_EMB_INIT_HALF_WIDTH = np.float32(0.05 * np.sqrt(3.0))


@partial(jax.jit, static_argnums=(1, 2))
def _init_tables(word, total_vocab: int, emb_dim: int):
    bits = jax.random.bits(jax.random.key(word), (total_vocab, emb_dim),
                           jnp.uint32)
    steps = (bits >> 8).astype(jnp.int32) - (1 << 23)
    emb = steps.astype(jnp.float32) * (_EMB_INIT_HALF_WIDTH / (1 << 23))
    return jnp.zeros((total_vocab,), jnp.float32), emb


def init_params(rng: np.random.Generator, d_dense: int, vocab_sizes,
                emb_dim: int, hidden) -> Dict[str, Any]:
    """THE start of a Wide&Deep fit, one rule for every size and every
    caller (``fit``, ``fit_outofcore``, the step builders, the tests and
    the benchmark's plain reference, which writes it out again):

    - the towers, on the host, from ``rng`` layer by layer: ``w`` =
      ``rng.normal(size=(fan_in, h)) * sqrt(2 / fan_in)`` as float32, ``b``
      zeros; the wide part (``wide_dense``, ``wide_b``, ``wide_cat``) zeros;
    - then ONE word from the same stream, ``rng.integers(0, 2**32)``,
      keys the embedding table, which is drawn where it is used, on the
      device: ``bits = jax.random.bits(jax.random.key(word), (total_vocab,
      emb_dim), uint32)``, ``emb = ((bits >> 8) - 2**23) * (a / 2**23)``
      with ``a = float32(0.05 * sqrt(3))``: uniform on ``[-a, a)``, standard
      deviation 0.05.  An integer below 2**24 times one float32 constant
      is a single correctly rounded product, so the table is the same
      bit for bit on every backend, and no host copy of it exists.

    The tables (``emb``, ``wide_cat``) come back as device arrays, the
    rest as host arrays."""
    total_vocab = int(np.sum(vocab_sizes))
    n_fields = len(vocab_sizes)
    deep_in = d_dense + n_fields * emb_dim
    layers = []
    fan_in = deep_in
    for h in list(hidden) + [1]:
        scale = np.sqrt(2.0 / fan_in)
        layers.append({
            "w": (rng.normal(size=(fan_in, h)) * scale).astype(np.float32),
            "b": np.zeros((h,), np.float32),
        })
        fan_in = h
    word = np.uint32(rng.integers(0, 1 << 32))
    wide_cat, emb = _init_tables(word, total_vocab, int(emb_dim))
    return {
        "wide_cat": wide_cat,
        "wide_dense": np.zeros((d_dense,), np.float32),
        "wide_b": np.zeros((), np.float32),
        "emb": emb,
        "mlp": layers,
    }


def forward_from_rows(params: Dict[str, Any], dense: jnp.ndarray,
                      wide_rows: jnp.ndarray, emb_rows: jnp.ndarray
                      ) -> jnp.ndarray:
    """Logits from already-gathered table rows (``wide_rows (b, fields)``,
    ``emb_rows (b, fields, emb)``).  The routed-gradient step
    differentiates THROUGH the rows (treating the gathers as inputs) so
    it can route the table gradients itself; ``params`` needs only the
    non-table leaves here."""
    from ..common.linear import _stable_margins

    # k=1 contractions (the wide matvec, the final (h, 1) layer) go
    # through the context-stable GEMM form: their loop-fusion
    # accumulation order otherwise differs between the standalone score
    # program and a fused chain segment (see _stable_margins), breaking
    # the fused pipeline's bit-exactness at d >= 8.
    wide = (_stable_margins(dense, params["wide_dense"], 0.0)
            + jnp.sum(wide_rows, axis=1)
            + params["wide_b"])
    deep = jnp.concatenate(
        [dense, emb_rows.reshape(emb_rows.shape[0], -1)], axis=1)
    for i, layer in enumerate(params["mlp"]):
        if layer["w"].shape[1] == 1:
            deep = _stable_margins(deep, layer["w"][:, 0],
                                   layer["b"][0])[:, None]
        else:
            deep = deep @ layer["w"] + layer["b"]
        if i + 1 < len(params["mlp"]):
            deep = jax.nn.relu(deep)
    return wide + deep[:, 0]


def forward(params: Dict[str, Any], dense: jnp.ndarray,
            cat_ids: jnp.ndarray) -> jnp.ndarray:
    """Logits for a batch.  ``cat_ids`` are already offset into the stacked
    vocab (shape (batch, n_fields))."""
    return forward_from_rows(params, dense, params["wide_cat"][cat_ids],
                             params["emb"][cat_ids])


def bce_loss(params, dense, cat_ids, labels, mask):
    # Identical to the linear family's masked binary log-loss — one shared
    # implementation of the {0,1}->±1 softplus form and padding epsilon.
    return logistic_loss(forward(params, dense, cat_ids), labels, mask)


def _validate_cat_ids(cat: np.ndarray, vocab_sizes) -> np.ndarray:
    """Range-check raw per-field ids, then offset into the stacked vocab.
    Both fit() and transform() go through here: a jitted gather silently
    CLAMPS out-of-range indices, so serving an unseen id would otherwise
    return another field's embedding with no error."""
    if cat.shape[1] != len(vocab_sizes):
        raise ValueError(
            f"catFeatures has {cat.shape[1]} fields, vocabSizes has "
            f"{len(vocab_sizes)}")
    if np.any(cat < 0) or np.any(cat >= np.asarray(vocab_sizes)[None, :]):
        raise ValueError("categorical id out of vocab range")
    return cat + _field_offsets(vocab_sizes)[None, :]


class WideDeep(WideDeepParams, Estimator["WideDeepModel"]):
    """fit(table with denseFeatures (n,d) float, catFeatures (n,f) int,
    label (n,) {0,1})."""

    def fit(self, *inputs) -> "WideDeepModel":
        (table,) = inputs
        with tracer.fit_span(type(self).__name__):
            return self._fit(table)

    def _fit(self, table: Table) -> "WideDeepModel":
        """``fit`` under its root span.  The phase spans (``fit.gather``,
        ``fit.arrange`` with its parts ``.layout``, ``.route`` and
        ``.params``, ``fit.upload``, then ``iterate.dispatch`` inside
        ``iterate``, ``fit.fetch``) follow each other without a gap and
        add no fence: each covers what the host does in it.

        The tables and their optimizer state are made on the device
        (:func:`init_params`) and handed over to the loop, which donates
        them as they are: at a vocabulary that fills the chip there is no
        room for a second copy, and no host copy exists on the way in."""
        vocab_sizes = self.get_vocab_sizes()
        if vocab_sizes is None:
            raise ValueError("WideDeep requires vocabSizes to be set")
        mesh = default_mesh()
        n_dev = int(mesh.shape["data"])

        with tracer.span("fit.gather", "fit"):
            # a column that already has the type is passed on as it is
            dense = float32_rows(table[self.DENSE_FEATURES_COL])
            cat = np.asarray(table[self.CAT_FEATURES_COL], np.int32)
            labels = np.asarray(table[self.get_label_col()], np.float32)
            cat = _validate_cat_ids(cat, vocab_sizes)

        lazy = bool(self.LAZY_EMB_OPT)
        routed_mode = self.get(WideDeepParams.ROUTED_EMB_GRAD)
        route = None
        with tracer.span("fit.arrange", "fit"):
            with tracer.span("fit.arrange.layout", "fit"):
                n = dense.shape[0]
                steps, batch, perm = plan_epoch_layout(
                    n, self.get_global_batch_size() or DEFAULT_GLOBAL_BATCH,
                    n_dev, self.get_seed())

                def layout(arr):
                    return prepare_epoch_tensor(arr, perm, steps, batch)

                mask = layout(np.ones((n,), np.float32))
                X = layout(dense)
                C = layout(cat)
                y = layout(labels)
            if routed_mode == "on" or (routed_mode == "auto" and not lazy):
                from ...ops.emb_grad import emb_grad_route

                # the epoch tensor C is replayed every epoch, so the
                # slot->row sort is static — built once here, host-side
                # (device=False: replicate() below does the one device_put;
                # placement="auto": gather until the inverse map outgrows
                # its budget at large vocab x many steps, then scatter)
                with tracer.span("fit.arrange.route", "fit") as span:
                    route = emb_grad_route(C, int(np.sum(vocab_sizes)),
                                           device=False, placement="auto")
                    span.note(
                        placement=route.placement,
                        table_update=_table_update_name(
                            _routed_adam_entries(route, lazy, n_dev == 1,
                                                 self.EMBEDDING_DIM)),
                        fold_passes=route.fold_passes,
                        unique_max=int(route.unique_per_step.max()),
                        unique_mean=float(route.unique_per_step.mean()),
                        route_bytes=sum(int(a.nbytes)
                                        for a in route.stacked_arrays()))
            with tracer.span("fit.arrange.params", "fit"):
                rng = np.random.default_rng(self.get_seed() + 1)  # init draws
                params = replicate(
                    init_params(rng, dense.shape[1], vocab_sizes,
                                self.EMBEDDING_DIM, self.HIDDEN_UNITS), mesh)
                step_fn, opt_state = _make_train_ops(
                    params, self.LEARNING_RATE, lazy, route=route)
                opt_state = replicate(opt_state, mesh)

        with tracer.span("fit.upload", "fit"):
            bsh = NamedSharding(mesh, P(None, "data"))
            X = jax.device_put(X, NamedSharding(mesh, P(None, "data", None)))
            C = jax.device_put(C, NamedSharding(mesh, P(None, "data", None)))
            y, mask = jax.device_put(y, bsh), jax.device_put(mask, bsh)
            route_data = ()
            if route is not None:
                route_data = tuple(replicate(a, mesh)
                                   for a in route.stacked_arrays())

        max_epochs = self.get_max_iter()
        init_state = HandedOver((
            params, opt_state,
            jnp.full((max_epochs,), jnp.nan, jnp.float32)))
        del params, opt_state
        result = iterate(_epoch_body(step_fn, steps), init_state,
                         (X, C, y, mask) + route_data, max_epochs=max_epochs,
                         config=IterationConfig(mode="fused"))
        fitted, _, loss_buf = result.state

        model = WideDeepModel()
        model.copy_params_from(self)
        with tracer.span("fit.fetch", "fit"):
            model._params = jax.device_get(fitted)
            model._loss_log = list(np.asarray(jax.device_get(loss_buf)))
        model._vocab_sizes = tuple(int(v) for v in vocab_sizes)
        model.route_placement = None if route is None else route.placement
        model.table_update = getattr(step_fn, "table_update", None)
        return model

    def fit_outofcore(self, make_reader, *, mesh=None,
                      prefetch_depth: int = 2, prefetch_workers: int = 1,
                      prefetch_put_workers: int = 1,
                      prefetch_stats=None,
                      steps_per_dispatch: int = 8,
                      checkpoint=None,
                      checkpoint_every_steps: int = 0,
                      resume: bool = False,
                      membership=None) -> "WideDeepModel":
        """Out-of-core ``fit``: epochs stream from ``make_reader()`` (the
        ``sgd_fit_outofcore`` reader protocol — a fresh per-epoch
        iterator of host batch dicts with this estimator's column names;
        epoch-aware factories receive ``epoch=``) instead of holding the
        (rows, fields) epoch tensors in HBM — the Criteo-scale shape for
        the stretch config.  Batches pad to the first batch's row count
        (padding rows carry mask 0 and are inert in BOTH optimizers: the
        loss is mask-weighted and the lazy table update drops weight-0
        ids), transfer via :func:`prefetch_to_device` overlapping the
        jitted Adam step, and the model/optimizer state never leaves
        device memory between epochs.  The mesh's ``data`` axis shards
        each batch.

        **Chunked dispatch** (``steps_per_dispatch=W``, default 8):
        single-process fits stack ``W`` consecutive batches into one
        device chunk and run all ``W`` Adam steps as one jitted
        ``lax.scan`` with a donated carry — one host dispatch per ``W``
        steps (the ``sgd_fit_outofcore`` posture; see its docstring).
        The final short chunk pads with a validity mask whose dead
        steps freeze params AND optimizer state, so any two ``W``
        values are bit-exact on the same stream.

        **Multi-host**: pass a process-spanning mesh and call from EVERY
        process with a reader over THAT process's data shard (the
        ``sgd_fit_outofcore`` posture): the global batch is the per-step
        concatenation over processes, assembled inside the prefetch
        pipeline, and every process must deliver the SAME number of
        equal-sized batches per epoch (mismatches deadlock in the
        collectives).  Multi-process fits keep the classic per-batch
        loop (chunk assembly is per-process-local).

        **Checkpoints + elastic membership** (``checkpoint=``,
        ``checkpoint_every_steps=``, ``resume=``, ``membership=`` —
        the ``sgd_fit_outofcore`` protocol, chunked single-process
        path): cuts land at chunk boundaries carrying params, Adam
        state, the running loss accumulators AND mesh-shape metadata;
        ``resume=True`` restores the newest valid cut, re-seeks the
        reader and continues deterministically.  With an
        :class:`~flink_ml_tpu.parallel.elastic.ElasticCoordinator` the
        fit polls membership once per chunk boundary and a changed
        fleet cuts a checkpoint and raises
        :class:`~flink_ml_tpu.parallel.elastic.ResizeRequested` for
        ``resilient_fit(elastic=...)`` to restore onto the new mesh —
        params and optimizer state are replicated, so the re-shard is
        pure placement and the resize is bit-exact vs a fixed fleet of
        the new size restoring the same cut.  Elastic fits shard the
        batch over EVERY mesh axis jointly (dcn x data)."""
        from ...data.prefetch import prefetch_to_device
        from ...parallel.mesh import (
            assemble_process_local,
            fetch_replicated,
            local_axis_multiple,
            mesh_process_count,
        )
        from ...utils.padding import FixedRowBatcher
        from ..common.sgd import _reader_for_epoch

        vocab_sizes = self.get_vocab_sizes()
        if vocab_sizes is None:
            raise ValueError("WideDeep requires vocabSizes to be set")
        if self.get(WideDeepParams.ROUTED_EMB_GRAD) == "on":
            raise ValueError(
                "routedEmbeddingGrad='on' cannot apply to the streaming "
                "fit: its batches are not replayed, so no static route "
                "exists — use 'auto' (streams on the autodiff scatter) "
                "or the in-memory fit()")
        mesh = mesh or default_mesh()
        put_fn = (assemble_process_local
                  if mesh_process_count(mesh) > 1 else None)
        chunked = mesh_process_count(mesh) == 1

        from ...iteration.checkpoint import (
            CheckpointConfig,
            CheckpointManager,
            mesh_shape_meta,
        )

        manager = None
        if isinstance(checkpoint, CheckpointManager):
            manager = checkpoint
        elif isinstance(checkpoint, CheckpointConfig):
            manager = CheckpointManager(checkpoint)
        if manager is not None and not chunked:
            raise ValueError(
                "checkpointing the streaming WideDeep fit needs the "
                "chunked single-process path (cuts land at chunk "
                "boundaries)")
        if membership is not None and manager is None:
            raise ValueError(
                "elastic membership requires a checkpoint manager: a "
                "resize IS a restore onto the new mesh")
        batch_axes = "data"
        row_multiple = local_axis_multiple(mesh)
        if membership is not None and len(mesh.axis_names) > 1:
            # elastic fleet: the batch shards over every mesh axis
            # jointly (dcn x data) so the resized dcn extent changes the
            # shard count, not the math
            batch_axes = tuple(str(a) for a in mesh.axis_names)
            row_multiple = int(np.prod([int(mesh.shape[a])
                                        for a in mesh.axis_names]))
        batcher = FixedRowBatcher(row_multiple)
        dense_col, cat_col = self.DENSE_FEATURES_COL, self.CAT_FEATURES_COL
        label_col = self.get_label_col()

        rng = np.random.default_rng(self.get_seed() + 1)
        # params/step build lazily at the first batch (d_dense comes
        # from the stream, matching fit()'s init-draw RNG sequence)
        params = step_fn = opt_state = None

        def to_host_batch(b):
            dense = np.asarray(b[dense_col], np.float32)
            cat = _validate_cat_ids(np.asarray(b[cat_col], np.int32),
                                    vocab_sizes)
            y = np.asarray(b[label_col], np.float32)
            mask = np.ones((y.shape[0],), np.float32)
            # padding rows: mask 0 + cat id 0 — inert in both optimizers
            # (mask-weighted loss; lazy update drops weight-0 ids)
            return batcher.pad((dense, cat, y, mask), have=y.shape[0])

        specs = (P(batch_axes, None), P(batch_axes, None), P(batch_axes),
                 P(batch_axes))
        # chunked dispatch (single-process): W batches per jitted scan —
        # W=1 is the bit-exact fallback through the SAME scan program
        W = max(1, int(steps_per_dispatch)) if chunked else 1
        if chunked:
            from ...data.prefetch import chunk_consumer_plan

            sharding, chunk_depth = chunk_consumer_plan(
                mesh, specs, W, prefetch_depth)
        else:
            sharding = tuple(NamedSharding(mesh, p) for p in specs)

        def _build_chunk_step(raw_step):
            # the shared masked scan freezes the WHOLE carried state —
            # here (params, opt_state), so dead (padded) steps freeze
            # the optimizer moments too — bit-exact vs the unpadded
            # stream
            from ...data.prefetch import masked_chunk_scan

            def step(state, *batch):
                params, opt_state = state
                params, opt_state, loss = raw_step(params, opt_state,
                                                   *batch)
                return (params, opt_state), loss

            def _chunk_runner(state, loss_sum, chunk, cmask):
                return masked_chunk_scan(step, state, loss_sum, chunk,
                                         cmask)

            return jax.jit(_chunk_runner, donate_argnums=(0, 1))

        def _lazy_init(d_dense: int):
            # init + optax state build on HOST values, then replicate
            # both: optax.init on a non-addressable process-spanning
            # array would create mismatched local state (every process
            # seeds identically)
            host_params = init_params(
                rng, d_dense, vocab_sizes,
                self.EMBEDDING_DIM, self.HIDDEN_UNITS)
            raw_step, host_opt = _make_train_ops(
                host_params, self.LEARNING_RATE,
                bool(self.LAZY_EMB_OPT))
            return (replicate(host_params, mesh),
                    replicate(host_opt, mesh), raw_step)

        epoch_sums: List = []   # per-epoch (device scalar, n_batches):
        max_epochs = self.get_max_iter()  # fetched ONCE after the loop so
        add = jax.jit(jnp.add)            # epoch boundaries never sync

        global_step = 0         # checkpoint tick: batches over all epochs
        start_epoch = 0
        skip_steps = 0          # batches already consumed in start_epoch
        resume_loss_sum = None
        resume_n_batches = 0
        if manager is not None and resume:
            restored = manager.restore_latest()
            if restored is not None:
                global_step, saved, meta = restored
                host_params = jax.device_get(saved["params"])
                raw_step, _ = _make_train_ops(
                    host_params, self.LEARNING_RATE,
                    bool(self.LAZY_EMB_OPT))
                params = replicate(host_params, mesh)
                opt_state = replicate(jax.device_get(saved["opt_state"]),
                                      mesh)
                step_fn = (_build_chunk_step(raw_step) if chunked
                           else jax.jit(raw_step, donate_argnums=(0, 1)))
                start_epoch = int(meta["train_epoch"])
                skip_steps = int(meta["step_in_epoch"])
                resume_n_batches = int(meta["n_batches"])
                if resume_n_batches:
                    resume_loss_sum = jnp.asarray(saved["loss_sum"],
                                                  jnp.float32)
                epoch_sums = [(jnp.asarray(s, jnp.float32), int(n))
                              for s, n in saved["epoch_sums"]]

        def _save(epoch, step_in_epoch, loss_sum, n_batches):
            manager.save(global_step, {
                "params": params, "opt_state": opt_state,
                "loss_sum": (loss_sum if loss_sum is not None
                             else jnp.zeros((), jnp.float32)),
                "epoch_sums": [(s, int(n)) for s, n in epoch_sums],
            }, {
                "train_epoch": epoch, "step_in_epoch": step_in_epoch,
                "n_batches": n_batches,
                **mesh_shape_meta(mesh, participant_count=row_multiple),
            })

        for epoch in range(start_epoch, max_epochs):
            reader = _reader_for_epoch(make_reader, epoch)
            if epoch == start_epoch and skip_steps:
                from ..common.sgd import _seek_or_skip

                reader = _seek_or_skip(reader, skip_steps)
            loss_sum = resume_loss_sum
            n_batches = resume_n_batches
            step_in_epoch = skip_steps
            resume_loss_sum, resume_n_batches, skip_steps = None, 0, 0
            if chunked:
                # closed explicitly on every exit so a supervised
                # restart (resize/crash recovery) never races a zombie
                # reader thread for the shared source
                pipeline = prefetch_to_device(
                    reader, depth=chunk_depth,
                    transform=to_host_batch, sharding=sharding,
                    workers=prefetch_workers,
                    put_workers=prefetch_put_workers,
                    stats=prefetch_stats, chunks=W)
                try:
                    for chunk, cmask, n_valid in pipeline:
                        if step_fn is None:
                            params, opt_state, raw_step = _lazy_init(
                                int(chunk[0].shape[2]))
                            step_fn = _build_chunk_step(raw_step)
                        if loss_sum is None:
                            loss_sum = jnp.zeros((), jnp.float32)
                        (params, opt_state), loss_sum = step_fn(
                            (params, opt_state), loss_sum, chunk, cmask)
                        n_batches += n_valid
                        step_in_epoch += n_valid
                        global_step += n_valid
                        cut_done = False
                        if (manager is not None
                                and checkpoint_every_steps > 0
                                and step_in_epoch // checkpoint_every_steps
                                > (step_in_epoch - n_valid)
                                // checkpoint_every_steps):
                            _save(epoch, step_in_epoch, loss_sum,
                                  n_batches)
                            cut_done = True
                        # elastic membership: one poll per chunk
                        # boundary; a changed fleet cuts here and hands
                        # the resize to the supervisor
                        if membership is not None \
                                and membership.poll(global_step):
                            if manager is not None and not cut_done:
                                _save(epoch, step_in_epoch, loss_sum,
                                      n_batches)
                            from ...parallel.elastic import ResizeRequested

                            raise ResizeRequested(
                                step=global_step,
                                fleet_size=membership.fleet_size,
                                membership_epoch=(
                                    membership.membership_epoch))
                finally:
                    pipeline.close()
            else:
                for dev_batch in prefetch_to_device(
                        reader, depth=prefetch_depth,
                        transform=to_host_batch, sharding=sharding,
                        workers=prefetch_workers,
                        put_workers=prefetch_put_workers,
                        stats=prefetch_stats, put_fn=put_fn):
                    if step_fn is None:
                        params, opt_state, raw_step = _lazy_init(
                            int(dev_batch[0].shape[1]))
                        step_fn = jax.jit(raw_step, donate_argnums=(0, 1))
                    params, opt_state, loss = step_fn(params, opt_state,
                                                      *dev_batch)
                    loss_sum = (loss if loss_sum is None
                                else add(loss_sum, loss))
                    n_batches += 1
            if loss_sum is None:
                raise ValueError("make_reader() returned an empty epoch")
            epoch_sums.append((loss_sum, n_batches))
            if manager is not None:
                _save(epoch + 1, 0, None, 0)   # epoch-boundary cut
        loss_log = [float(np.asarray(fetch_replicated(s))) / n
                    for s, n in epoch_sums]

        model = WideDeepModel()
        model.copy_params_from(self)
        model._params = fetch_replicated(params)
        model._vocab_sizes = tuple(int(v) for v in vocab_sizes)
        model._loss_log = loss_log
        return model

    def save(self, path: str) -> None:
        persist.save_metadata(self, path)

    @classmethod
    def load(cls, path: str) -> "WideDeep":
        return persist.load_stage_param(path)


@jax.jit
def _jit_scores(params, dense, cat_ids):
    return jax.nn.sigmoid(forward(params, dense, cat_ids))


def _widedeep_chain_kernel(static, params, cols):
    """Chain-terminal scores — expression-identical to ``_jit_scores``;
    the raw per-field ids offset into the stacked vocab in-device (an
    exact int add; the range check runs host-side as the kernel's
    ``pre``)."""
    (dcol, ccol, scol) = static
    dense = cols[dcol].astype(jnp.float32)
    cat = cols[ccol] + params["offsets"][None, :]
    return {scol: jax.nn.sigmoid(forward(params["net"], dense, cat))}


class WideDeepModel(WideDeepParams, Model):
    def __init__(self):
        super().__init__()
        self._params: Optional[Dict[str, Any]] = None
        self._vocab_sizes: Optional[Tuple[int, ...]] = None
        self._loss_log: List[float] = []
        #: how the fit's static route placed the table gradients
        #: (``ops/emb_grad.py``: "gather" or "scatter"); None where the
        #: fit took no route, or the model was not fitted here
        self.route_placement: Optional[str] = None
        #: how the fit's routed step updated the tables: ``"fused"`` (the
        #: optimizer's pass placed the touched rows' gradient itself) or
        #: ``"dense_grad"`` (a table-shaped gradient was formed first);
        #: None as ``route_placement`` is
        self.table_update: Optional[str] = None

    @property
    def loss_log(self) -> List[float]:
        """Per-epoch mean training loss (the linear family's accessor)."""
        return list(self._loss_log)

    def _require_model(self):
        if self._params is None:
            raise RuntimeError("WideDeepModel has no model data")

    def transform_kernel(self, schema):
        """Chain TERMINAL: one fused sigmoid(forward) over the segment's
        device columns.  The categorical id range check (host control
        flow) runs as the kernel's ``pre`` on the segment's entry
        columns, so the stage only chains while catFeatures passes
        through from the segment input untouched."""
        from ...api.chain import StageKernel, numeric_entry

        self._require_model()
        dcol, ccol = self.DENSE_FEATURES_COL, self.CAT_FEATURES_COL
        cat_entry = schema.get(ccol)
        if numeric_entry(schema, dcol) is None \
                or cat_entry is None or cat_entry[1].kind not in "iu" \
                or len(cat_entry[0]) != 1 \
                or cat_entry[0][0] != len(self._vocab_sizes):
            return None
        raw_col = self.get_raw_prediction_col()
        pred_col = self.get_prediction_col()
        score_col = f"__chain_scores__{pred_col}"
        vocab_sizes = self._vocab_sizes

        def pre(host):
            _validate_cat_ids(np.asarray(host[ccol]), vocab_sizes)

        def post(host):
            scores = host[score_col].astype(np.float64)
            return {raw_col: scores,
                    pred_col: (scores > 0.5).astype(np.int64)}

        return StageKernel(
            fn=_widedeep_chain_kernel, static=(dcol, ccol, score_col),
            params={"net": self._params,
                    "offsets": _field_offsets(vocab_sizes)},
            consumes=(dcol, ccol), produces=(score_col,),
            post=post, pre=pre, pre_cols=(ccol,))

    def transform(self, *inputs) -> List[Table]:
        (table,) = inputs
        self._require_model()
        # the kernel registry's shared dispatch surface (the chain
        # terminal's (fn, static) plan): offline transform, fused
        # pipelines, and serving share one compiled executable per
        # (schema, bucket); the in-kernel id offset is an exact int add,
        # the range check runs as the kernel's host pre exactly like
        # _validate_cat_ids
        from ...api.chain import apply_kernel_or_none

        kernel = self.transform_kernel(table.schema())
        cols = apply_kernel_or_none(kernel, table)
        if cols is not None:
            out = table
            for name in (n for n in cols if n not in kernel.produces):
                out = out.with_column(name, cols[name])
            return [out]
        dense = np.asarray(table[self.DENSE_FEATURES_COL],
                           np.float32)
        cat = np.asarray(table[self.CAT_FEATURES_COL], np.int32)
        cat = _validate_cat_ids(cat, self._vocab_sizes)
        # bucketed batch shape (utils/padding.py): one compiled forward per
        # power-of-two bucket serves every batch size; the per-row forward
        # makes zero-pad rows (id 0 is always a valid slot) inert
        from ...utils.padding import pad_rows_to_bucket

        (dense_p, cat_p), n = pad_rows_to_bucket((dense, cat))
        scores = np.asarray(_jit_scores(self._params, dense_p, cat_p),
                            np.float64)[:n]
        out = table.with_column(self.get_raw_prediction_col(), scores)
        out = out.with_column(self.get_prediction_col(),
                              (scores > 0.5).astype(np.int64))
        return [out]

    # -- model data ---------------------------------------------------------
    def _flat_arrays(self) -> Dict[str, np.ndarray]:
        flat = {k: np.asarray(self._params[k])
                for k in ("emb", "wide_cat", "wide_dense", "wide_b")}
        for i, layer in enumerate(self._params["mlp"]):
            flat[f"mlp_{i}_w"] = np.asarray(layer["w"])
            flat[f"mlp_{i}_b"] = np.asarray(layer["b"])
        return flat

    def _set_flat_arrays(self, flat, vocab_sizes) -> None:
        vocab_sizes = tuple(int(v) for v in vocab_sizes)
        n_layers = sum(1 for k in flat if k.startswith("mlp_")
                       and k.endswith("_w"))
        params = {k: np.asarray(flat[k], np.float32)
                  for k in ("emb", "wide_cat", "wide_dense", "wide_b")}
        params["mlp"] = [{"w": np.asarray(flat[f"mlp_{i}_w"], np.float32),
                          "b": np.asarray(flat[f"mlp_{i}_b"], np.float32)}
                         for i in range(n_layers)]
        if params["emb"].shape[0] != sum(vocab_sizes) \
                or params["wide_cat"].shape != params["emb"].shape[:1]:
            raise ValueError(
                f"model data holds {params['emb'].shape[0]} embedding and "
                f"{params['wide_cat'].shape[0]} wide rows, vocabSizes sum "
                f"to {sum(vocab_sizes)}")
        self._params = params
        self._vocab_sizes = vocab_sizes

    def set_model_data(self, *inputs) -> "WideDeepModel":
        """One row: ``emb``, ``wide_cat``, ``wide_dense``, ``wide_b`` and
        the towers' ``mlp_<i>_w`` / ``mlp_<i>_b`` by layer.  The
        vocabulary is the model's ``vocabSizes`` param."""
        (table,) = inputs
        vocab_sizes = self.get_vocab_sizes()
        if vocab_sizes is None:
            raise ValueError("WideDeepModel requires vocabSizes to be set")
        self._set_flat_arrays(
            {name: table[name][0] for name in table.column_names},
            vocab_sizes)
        return self

    def get_model_data(self) -> List[Table]:
        self._require_model()
        return [Table({name: arr[None]
                       for name, arr in self._flat_arrays().items()})]

    # -- persistence --------------------------------------------------------
    def save(self, path: str) -> None:
        self._require_model()
        persist.save_metadata(
            self, path, {"vocabSizes": list(self._vocab_sizes)})
        persist.save_model_arrays(path, "model", self._flat_arrays())

    @classmethod
    def load(cls, path: str) -> "WideDeepModel":
        model = persist.load_stage_param(path)
        meta = persist.load_metadata(path)
        model._set_flat_arrays(persist.load_model_arrays(path, "model"),
                               meta["vocabSizes"])
        return model


def _epoch_body(step_fn, steps: int):
    """One epoch of ``fit`` as an ``iterate`` body: ``steps`` batches of
    the replayed epoch tensors through ``step_fn`` (:func:`_make_train_ops`'),
    the epoch's mean loss written into the state's log.  It states its
    program key (``iteration/body.py: with_program_key``): ``steps`` and
    the step's own."""

    def epoch_body(state, epoch, data):
        Xd, Cd, yd, md = data[:4]
        rt = data[4:]
        params, opt_state, loss_log = state

        def batch_step(carry, i):
            params, opt_state = carry
            params, opt_state, loss = step_fn(
                params, opt_state, Xd[i], Cd[i], yd[i], md[i],
                *(a[i] for a in rt))
            return (params, opt_state), loss

        (params, opt_state), losses = jax.lax.scan(
            batch_step, (params, opt_state),
            jnp.arange(steps, dtype=jnp.int32))
        loss_log = loss_log.at[epoch].set(jnp.mean(losses))
        return IterationBodyResult((params, opt_state, loss_log))

    return with_program_key(epoch_body, _epoch_body, steps,
                            step_fn.program_key)


# embedding-shaped tables whose per-step gradient support is the batch's
# id set — the lazy optimizer updates only those rows
_LAZY_TABLE_KEYS = ("emb", "wide_cat")


def _on_one_device(x) -> bool:
    """True for a concrete array that lies on one device (not for a
    tracer, nor for an array replicated or sharded over a mesh)."""
    sharding = getattr(x, "sharding", None)
    return sharding is not None and len(sharding.device_set) == 1


def _routed_adam_entries(route, lazy: bool, one_device: bool, emb_dim: int):
    """The registry entries of op ``routed_adam_update`` that a routed
    step updates its two tables with, by table, or None where it forms
    the table-shaped gradients and hands them to optax with the towers'.
    The op takes the ``scatter`` placement's run sums, is dense Adam, and
    is one program on one device: the ``gather`` placement (small
    vocabularies), LazyAdam and tables replicated over a mesh keep the
    other path."""
    if (route is None or lazy or route.placement != "scatter"
            or not one_device):
        return None
    from ...kernels.registry import lookup

    return {"emb": lookup("routed_adam_update",
                          sig=(route.num_rows, emb_dim)),
            "wide_cat": lookup("routed_adam_update",
                               sig=(route.num_rows, 0))}


def _table_update_name(adam_entries) -> str:
    """``"fused"``: the optimizer's pass over the embedding table places
    the touched rows' gradient itself (the ``pallas`` backend of
    ``routed_adam_update``); ``"dense_grad"``: a table-shaped gradient is
    formed first."""
    fused = (adam_entries is not None
             and adam_entries["emb"].backend == "pallas")
    return "fused" if fused else "dense_grad"


def _make_train_ops(params, lr: float, lazy: bool, route=None,
                    b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8):
    """Build ``(batch_step, opt_state0)`` for the Wide&Deep training loop.

    ``lazy=False``: dense ``optax.adam`` over every parameter (the
    reference oracle semantics).

    ``route`` (an ``ops.emb_grad.EmbGradRoute``, dense-Adam only): the
    returned step takes the route's per-step arrays after the batch, one
    step's slice of ``route.stacked_arrays()`` — three under the
    ``gather`` placement (``order, sorted_ids, pos_map``), four under
    ``scatter`` (``order, sorted_ids, out_pos, out_ids``) — and computes
    the embedding/wide-table gradients with the statically-routed
    placement instead of autodiff's random-RMW scatter-add; all other
    gradients and the Adam update are identical.  See the
    ``routedEmbeddingGrad`` param doc.  The step's device operations
    carry ``jax.named_scope`` s by what they are for: ``widedeep.lookup``
    (the row gathers), ``widedeep.towers`` (forward and backward of the
    wide sum and the deep tower), ``widedeep.table_grad`` (permutation
    gather, fold and placement, for both tables) and
    ``widedeep.optimizer`` (Adam over every parameter).

    How the two tables are updated follows from what the builder sees,
    not from a param (:func:`_routed_adam_entries`): under the
    ``scatter`` placement with the tables on ONE device, ``table_grad``
    ends at the run sums (``ops.emb_grad.routed_run_sums``: the touched
    rows' folded gradient, ``(U, E)``) and the optimizer's scope updates
    each table through registry op ``routed_adam_update``
    (``ops/adam_table_pallas.py``), optax keeping the towers and the
    step count both halves share.  On a TPU the embedding table takes the
    op's fused pass, which places the run sums while ``p``, ``m`` and
    ``v`` stream through VMEM: no table-shaped gradient exists
    (``table_update == "fused"``, on the returned step, the model and the
    ``fit.arrange.route`` span).  The wide table's vector of scalars and
    every table off the TPU take the op's XLA composition (zeros,
    sorted unique scatter-set, Adam in optax's expressions).  The
    ``gather`` placement and tables replicated over a mesh form the
    dense gradient and hand it to optax with the rest, as before
    (``"dense_grad"``).  Either way it is dense Adam in float32 on every
    row, every step.

    ``lazy=True`` (LazyAdam, ``lazyEmbeddingOptimizer``): dense Adam
    touches every row of the ``(total_vocab, emb_dim)`` embedding and
    ``(total_vocab,)`` wide tables each step — m/v/param read+write
    streams over rows whose gradient is exactly zero.  The lazy step
    instead:

    1. takes the standard dense-shaped gradient (XLA's scatter-add from
       the gather's transpose — one zero-init + 213k-row scatter, the
       only full-table-shaped cost left),
    2. gathers the batch's ``ids = cat_ids.reshape(-1)`` rows of
       grad/m/v/param (duplicate ids read the SAME combined gradient
       row, so every duplicate computes identical values),
    3. applies exact Adam math at those rows and scatter-``set``s them
       back — duplicate writes are idempotent, so the result is
       deterministic.

    Rows a batch does not touch keep param AND optimizer state exactly
    (no momentum tail, no bias-correction drift): the standard LazyAdam
    semantic deviation from dense Adam.  A row touched by EVERY step has
    a bit-for-bit dense-Adam history — the oracle `tests/test_widedeep.py`
    asserts both properties.  The MLP/wide-dense/bias params always use
    dense ``optax.adam``; the shared step count drives bias correction
    for both halves.

    Measured reality (r4, one v5e chip, 2^20 total vocab, batch 8192):
    the DENSE step wins — 18.8 vs 42.5 ms — because XLA lowers the
    213k-row gather/scatter pair to serialized random HBM access while
    the full-table m/v/param update is three perfectly-streamed passes
    (the same asymmetry that motivated the static-routing ELL kernel
    for LR, ``ops/ell_scatter.py``).  Lazy is therefore an opt-in: use
    it for its freshness semantics, or when the vocabulary is so large
    that full-table streams dominate the step or the m/v tables cannot
    be afforded at all (2^22+ total vocab did not fit this chip's
    visible HBM to measure the crossover)."""
    opt = optax.adam(lr)
    grad_fn = jax.value_and_grad(bce_loss)

    def keyed(step, *how):
        # what the step's trace reads beside its arguments, for the epoch
        # body's program key (``_epoch_body``): the builder's scalars, the
        # module's functions it calls (by what their names hold now) and
        # ``how`` the tables are updated; no array, and not the route
        return with_program_key(
            step, _make_train_ops, lr, lazy, b1, b2, eps, forward_from_rows,
            bce_loss, logistic_loss, _LAZY_TABLE_KEYS, *how)

    def split(tree):
        tables = {k: tree[k] for k in _LAZY_TABLE_KEYS}
        rest = {k: v for k, v in tree.items() if k not in _LAZY_TABLE_KEYS}
        return tables, rest

    if route is not None:
        if lazy:
            raise ValueError(
                "routed table gradients are a dense-Adam path; disable "
                "lazyEmbeddingOptimizer or set routedEmbeddingGrad='off'")
        adam_entries = _routed_adam_entries(
            route, lazy, _on_one_device(params["emb"]),
            params["emb"].shape[1])
        if adam_entries is None:
            # registry op ``routed_table_grad``, resolved ONCE at
            # step-build: the step body never branches on backend
            table_grad = route.resolve_apply()

            def update(params, opt_state, g_rest, g_tables, out_ids):
                updates, opt_state = opt.update({**g_rest, **g_tables},
                                                opt_state, params)
                return optax.apply_updates(params, updates), opt_state
        else:
            from ...ops.emb_grad import routed_run_sums

            def table_grad(g_flat, order, sorted_ids, out_pos, out_ids):
                return routed_run_sums(g_flat, order, sorted_ids, out_pos,
                                       fold_passes=route.fold_passes)

            def update(params, opt_state, g_rest, run_sums, out_ids):
                # optax for the towers; op ``routed_adam_update`` for the
                # tables, on the step count the towers' update returns
                tables, rest = split(params)
                adam, *tail = opt_state
                (mu_t, mu_r), (nu_t, nu_r) = split(adam.mu), split(adam.nu)
                updates, (adam, *tail) = opt.update(
                    g_rest, (adam._replace(mu=mu_r, nu=nu_r), *tail), rest)
                rest = optax.apply_updates(rest, updates)
                for k in _LAZY_TABLE_KEYS:
                    tables[k], mu_t[k], nu_t[k] = adam_entries[k].fn(
                        tables[k], mu_t[k], nu_t[k], run_sums[k], out_ids,
                        adam.count, lr=lr, b1=b1, b2=b2, eps=eps)
                adam = adam._replace(mu={**adam.mu, **mu_t},
                                     nu={**adam.nu, **nu_t})
                return {**rest, **tables}, (adam, *tail)

        def batch_step(params, opt_state, dense, cat_ids, labels, mask,
                       *route_arrays):
            _, rest = split(params)
            with jax.named_scope("widedeep.lookup"):
                emb_rows = params["emb"][cat_ids]
                wide_rows = params["wide_cat"][cat_ids]

            def loss_rows(rest, emb_rows, wide_rows):
                return logistic_loss(
                    forward_from_rows(rest, dense, wide_rows, emb_rows),
                    labels, mask)

            with jax.named_scope("widedeep.towers"):
                loss, (g_rest, g_emb, g_wide) = jax.value_and_grad(
                    loss_rows, argnums=(0, 1, 2))(rest, emb_rows, wide_rows)
            emb_dim = emb_rows.shape[-1]
            with jax.named_scope("widedeep.table_grad"):
                g_tables = {
                    "emb": table_grad(g_emb.reshape(-1, emb_dim),
                                      *route_arrays),
                    "wide_cat": table_grad(g_wide.reshape(-1),
                                           *route_arrays),
                }
            with jax.named_scope("widedeep.optimizer"):
                params, opt_state = update(params, opt_state, g_rest,
                                           g_tables, route_arrays[-1])
            return params, opt_state, loss

        batch_step.table_update = _table_update_name(adam_entries)
        # the registry's answers by their entries: ``routed_table_grad``'s
        # where the step forms the table gradients, else both tables'
        # ``routed_adam_update``
        resolved = (table_grad.entry if adam_entries is None else
                    (routed_run_sums,
                     *(adam_entries[k] for k in _LAZY_TABLE_KEYS)))
        return keyed(batch_step, route.placement, route.fold_passes,
                     route.num_rows, batch_step.table_update,
                     resolved), opt.init(params)
    if not lazy:
        def batch_step(params, opt_state, dense, cat_ids, labels, mask):
            loss, grads = grad_fn(params, dense, cat_ids, labels, mask)
            updates, opt_state = opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        return keyed(batch_step), opt.init(params)

    tables0, rest0 = split(params)
    opt_state0 = {
        "rest": opt.init(rest0),
        "m": jax.tree_util.tree_map(jnp.zeros_like, tables0),
        "v": jax.tree_util.tree_map(jnp.zeros_like, tables0),
        "t": jnp.zeros((), jnp.int32),
    }

    def batch_step(params, opt_state, dense, cat_ids, labels, mask):
        loss, grads = grad_fn(params, dense, cat_ids, labels, mask)
        tables, rest = split(params)
        g_tab, g_rest = split(grads)
        rest_updates, rest_state = opt.update(g_rest, opt_state["rest"],
                                              rest)
        rest = optax.apply_updates(rest, rest_updates)
        t = opt_state["t"] + 1
        # optax.scale_by_adam's exact bias correction: 1 - decay**count
        bc1 = 1.0 - jnp.power(b1, t.astype(jnp.float32))
        bc2 = 1.0 - jnp.power(b2, t.astype(jnp.float32))
        # weight-0 rows (epoch padding carries cat id 0) must NOT count
        # as touched — id 0 would collect phantom momentum-tail updates.
        # Their ids go out of bounds so every scatter drops them; the
        # gathers use a clamped copy (the computed value is discarded).
        total = tables["emb"].shape[0]
        ids = jnp.where(mask[:, None] > 0, cat_ids, total).reshape(-1)
        gids = jnp.minimum(ids, total - 1)
        new_tab, new_m, new_v = {}, {}, {}
        for k in _LAZY_TABLE_KEYS:
            g_rows = g_tab[k][gids]
            m_rows = b1 * opt_state["m"][k][gids] + (1.0 - b1) * g_rows
            v_rows = (b2 * opt_state["v"][k][gids]
                      + (1.0 - b2) * jnp.square(g_rows))
            step_rows = lr * (m_rows / bc1) / (
                jnp.sqrt(v_rows / bc2) + eps)
            new_m[k] = opt_state["m"][k].at[ids].set(m_rows, mode="drop")
            new_v[k] = opt_state["v"][k].at[ids].set(v_rows, mode="drop")
            new_tab[k] = tables[k].at[ids].set(
                tables[k][gids] - step_rows, mode="drop")
        new_state = {"rest": rest_state, "m": new_m, "v": new_v, "t": t}
        return {**rest, **new_tab}, new_state, loss

    return keyed(batch_step), opt_state0


def build_reference_train_step(d_dense: int, vocab_sizes, emb_dim: int,
                               hidden, lr: float = 1e-2,
                               lazy_embeddings: bool = False,
                               route=None):
    """The unsharded single-device oracle for :func:`build_sharded_train_step`
    — SAME init seed (0), optimizer, and loss, no shardings anywhere.
    Returns (train_step, params, opt_state).  The dp x tp step must
    reproduce this one allclose on loss AND updated params (a wrong
    psum/axis placement still converges, so only exact equivalence catches
    it); asserted by tests/test_widedeep.py and __graft_entry__'s multichip
    dryrun.  ``lazy_embeddings`` swaps in the LazyAdam table update;
    ``route`` swaps in the statically-routed table gradients (see
    :func:`_make_train_ops` — the step then takes the route's per-step
    arrays as well).  The parameters are :func:`init_params`' rule on the
    stream ``default_rng(0)``."""
    params = jax.tree_util.tree_map(
        jnp.asarray,
        init_params(np.random.default_rng(0), d_dense, vocab_sizes, emb_dim,
                    hidden))
    batch_step, opt_state = _make_train_ops(params, lr, lazy_embeddings,
                                            route=route)
    return jax.jit(batch_step), params, opt_state


def assert_sharded_matches_reference(sharded_params, sharded_loss,
                                     ref_params, ref_loss) -> None:
    """Allclose on loss and every param leaf (f32 tolerances: cross-device
    reduction order differs from the single-device program)."""
    np.testing.assert_allclose(float(np.asarray(sharded_loss)),
                               float(np.asarray(ref_loss)),
                               rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(sharded_params)),
                    jax.tree_util.tree_leaves(jax.device_get(ref_params))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def build_sharded_train_step(mesh, d_dense: int, vocab_sizes, emb_dim: int,
                             hidden, lr: float = 1e-2, grad_reduce=None):
    """A dp x tp training step for the multichip dry run: embeddings and MLP
    hidden dims sharded over 'model', batch over 'data'.  Returns
    (train_step, sharded_params, opt, sharded_opt_state, shard_batch_fn).

    ``grad_reduce``
    (:class:`~flink_ml_tpu.parallel.grad_reduce.GradReduceConfig`):
    ``None``/``mode="exact"`` keep the implicit-GSPMD step above
    unchanged.  A compressed mode routes the DENSE-tower gradients
    (``wide_dense``/``wide_b``/``mlp``) through
    :func:`~flink_ml_tpu.parallel.grad_reduce.reduce_gradients` — the
    data axis goes manual (``shard_map``) while the ``model`` axis stays
    under GSPMD auto partitioning, so Megatron-style tensor parallelism
    composes untouched.  The embedding/wide-table gradients stay EXACT:
    their per-step support is the batch's id set, i.e. they are already
    sparse by construction and top-k would only re-compress a scatter.
    The step then takes (and returns) the reducer state, and the builder
    returns a 6-tuple with its initial value appended:
    ``(train_step, params, opt, opt_state, shard_batch_fn, gr_state0)``
    with ``train_step(params, opt_state, gr_state, dense, cat_ids,
    labels, mask) -> (params, opt_state, gr_state, loss)``.

    ``grad_reduce.bucket_count`` / ``adaptive`` route the dense-tower
    reduce through the bucketed transport and the per-leaf density
    ladder; ``overlap=True`` makes the dense-tower grads one-step stale
    (the pending buffer rides ``gr_state``) while table grads stay
    fresh — callers that want the final pending applied run one extra
    step on a zero-mask batch."""
    rng = np.random.default_rng(0)
    params = init_params(rng, d_dense, vocab_sizes, emb_dim, hidden)

    def param_spec(path_params):
        specs = {
            "wide_cat": P(), "wide_dense": P(), "wide_b": P(),
            "emb": P(None, "model"),
        }
        mlp_specs = []
        n = len(path_params["mlp"])
        for i in range(n):
            # Megatron-style pairing: even layers column-parallel (outputs
            # sharded over 'model'), odd layers row-parallel (inputs sharded;
            # XLA inserts the psum that gathers activations back).
            if i % 2 == 0 and i + 1 < n:
                mlp_specs.append({"w": P(None, "model"), "b": P("model")})
            elif i % 2 == 1:
                mlp_specs.append({"w": P("model", None), "b": P()})
            else:  # final (or only) layer: replicated scalar head
                mlp_specs.append({"w": P(), "b": P()})
        return {**{k: specs[k] for k in specs}, "mlp": mlp_specs}

    specs = param_spec(params)
    sharded_params = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, specs, is_leaf=lambda x: isinstance(x, np.ndarray))

    opt = optax.adam(lr)
    opt_state = opt.init(sharded_params)
    grad_fn = jax.value_and_grad(bce_loss)

    if grad_reduce is not None and grad_reduce.mode != "exact":
        return _build_reduced_sharded_step(mesh, grad_reduce, sharded_params,
                                           opt, opt_state, grad_fn)

    @jax.jit
    def train_step(params, opt_state, dense, cat_ids, labels, mask):
        loss, grads = grad_fn(params, dense, cat_ids, labels, mask)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    def shard_batch_fn(dense, cat_ids, labels, mask):
        return (
            jax.device_put(dense, NamedSharding(mesh, P("data", None))),
            jax.device_put(cat_ids, NamedSharding(mesh, P("data", None))),
            jax.device_put(labels, NamedSharding(mesh, P("data"))),
            jax.device_put(mask, NamedSharding(mesh, P("data"))),
        )

    return train_step, sharded_params, opt, opt_state, shard_batch_fn


def _build_reduced_sharded_step(mesh, gr, sharded_params, opt, opt_state,
                                grad_fn):
    """The compressed-reduction variant of :func:`build_sharded_train_step`
    (see its docstring for the contract): manual ``shard_map`` over the
    reduction axes, every OTHER mesh axis (``model``) left to GSPMD auto
    partitioning, dense-tower grads through ``reduce_gradients`` — on
    the recursive-halving/doubling wire protocol by default, so the
    reducer state here also carries the per-round fill-in/union
    accounting leaves — table grads exact."""
    from ...parallel import grad_reduce as GR
    from ...parallel.collectives import shard_map_fn

    axes, n_red, batch_axis = GR.mesh_layout(gr, mesh)

    def split(tree):
        tables = {k: tree[k] for k in _LAZY_TABLE_KEYS}
        rest = {k: v for k, v in tree.items() if k not in _LAZY_TABLE_KEYS}
        return tables, rest

    _, rest0 = split(sharded_params)
    gr_state0 = jax.tree_util.tree_map(
        lambda x: jax.device_put(x, NamedSharding(mesh, P(batch_axis))),
        GR.init_state(gr, jax.tree_util.tree_map(np.asarray, rest0), n_red))

    # Stage 1 — per-device gradients, 'model' under GSPMD auto so the
    # Megatron sharding composes: table grads reduce EXACTLY here (their
    # support is the batch's id set — sparse by construction); the dense
    # tower comes back STACKED per participant for stage 2.
    def local_grads(params, dense, cat_ids, labels, mask):
        loss_l, grads = grad_fn(params, dense, cat_ids, labels, mask)
        # bce_loss is a mask-weighted LOCAL mean; renormalize to the
        # global denominator so loss and gradient equal the
        # single-program objective (the _mixed_update_sharded stance)
        denom_l = jnp.maximum(jnp.sum(mask), 1e-12)
        denom = jax.lax.psum(denom_l, axes)
        loss = jax.lax.psum(loss_l * denom_l, axes) / denom
        grads = jax.tree_util.tree_map(lambda g: g * (denom_l / denom),
                                       grads)
        g_tab, g_rest = split(grads)
        g_tab = jax.tree_util.tree_map(lambda g: jax.lax.psum(g, axes),
                                       g_tab)
        return loss, g_tab, jax.tree_util.tree_map(
            lambda g: g[None], g_rest)

    grads_fn = shard_map_fn(
        local_grads, mesh,
        in_specs=(P(), P(batch_axis, None), P(batch_axis, None),
                  P(batch_axis), P(batch_axis)),
        out_specs=(P(), P(), P(batch_axis)),
        axis_names=frozenset(axes))

    # Stage 2 — the compressed reduction runs FULLY manual (every mesh
    # axis bound): this XLA's partitioner aborts on lax.top_k inside a
    # manual-subgroup (auto) region, and the dense-tower leaves carry no
    # model sharding anyway, so model peers just replicate the reduce.
    # With overlap the PREVIOUS step's pending dense-tower grads are
    # reduced (their bucket collectives carry no dependence on this
    # step's forward/backward) and this step's land in the pending
    # buffer; table grads stay fresh — mixing a one-step-stale dense
    # tower with fresh tables is absorbed by the EF residual like the
    # sparsification itself.
    def reduce_local(g_stacked, gr_state):
        g_l = jax.tree_util.tree_map(lambda a: a[0], g_stacked)
        st = GR.squeeze_state(gr_state)
        if GR.wants_overlap(gr):
            red, new_state = GR.pipelined_reduce(g_l, st, gr)
        else:
            red, new_state = GR.reduce_gradients(g_l, st, gr)
        return red, GR.unsqueeze_state(new_state)

    reduce_fn = shard_map_fn(
        reduce_local, mesh,
        in_specs=(P(batch_axis), P(batch_axis)),
        out_specs=(P(), P(batch_axis)))

    @jax.jit
    def train_step(params, opt_state, gr_state, dense, cat_ids, labels,
                   mask):
        loss, g_tab, g_stacked = grads_fn(params, dense, cat_ids, labels,
                                          mask)
        g_rest, gr_state = reduce_fn(g_stacked, gr_state)
        grads = {**g_tab, **g_rest}
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state, gr_state,
                loss)

    def shard_batch_fn(dense, cat_ids, labels, mask):
        return (
            jax.device_put(dense, NamedSharding(mesh, P(batch_axis, None))),
            jax.device_put(cat_ids, NamedSharding(mesh, P(batch_axis, None))),
            jax.device_put(labels, NamedSharding(mesh, P(batch_axis))),
            jax.device_put(mask, NamedSharding(mesh, P(batch_axis))),
        )

    return (train_step, sharded_params, opt, opt_state, shard_batch_fn,
            gr_state0)


# ---------------------------------------------------------------------------
# kernel-registry entry: op ``widedeep_scores`` (stage convention) — the
# chain-terminal sigmoid(forward) plan shared by offline transform,
# fused pipelines, and the serving executor.
# ---------------------------------------------------------------------------

def _register_widedeep_kernels() -> None:
    from ...kernels.registry import register_kernel

    register_kernel("widedeep_scores", "xla", _widedeep_chain_kernel,
                    convention="stage")


_register_widedeep_kernels()
