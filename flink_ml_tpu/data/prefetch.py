"""Host->device prefetch — the feed that keeps the MXU from waiting on IO.

The reference streams records through Flink's network stack with built-in
backpressure (SURVEY §2.10); on TPU the analog problem is keeping the device
fed: ``device_put`` of batch N+1 (and the host-side read/decode behind it)
must overlap the jitted step on batch N, or every step pays
HBM-transfer + disk latency serially.

``prefetch_to_device`` wraps any host-batch iterator with a bounded
pipeline: a reader thread pulls host batches (hitting the data cache's
fadvise readahead, `data/datacache.py`), ``workers`` threads run the
decode ``transform`` (ordered reassembly — results stay in source order),
and a putter thread schedules the async ``device_put``, parking in-flight
device buffers in a depth-bounded queue — classic double buffering at
``depth=2``, deeper if decode jitter demands it.  The bound is the
backpressure: the reader never runs more than ``depth + in-flight
transforms`` batches ahead of the consumer, so host RAM stays flat on
out-of-core epochs.

``stats`` (a :class:`PrefetchStats`) attributes the pipeline's time:
cumulative seconds spent reading host batches, transforming, in
``device_put``, and how long the CONSUMER sat waiting on an empty queue
(the infeed gap — if this is ~0 the device is the bottleneck, not the
ingest).  This is the instrumentation VERDICT r2 asked for: it separates
host-decode from transfer from compute so the out-of-core benchmark can
attribute its overhead.

``chunks=W`` turns the pipeline's unit of work from one batch into a
CHUNK of ``W`` consecutive batches stacked along a new leading axis —
the feed side of chunked-scan dispatch: the consumer runs one jitted
``lax.scan`` over the chunk, so ``W`` optimizer steps cost one host
dispatch, and the ``device_put`` of chunk N+1 still overlaps compute on
chunk N (the same double buffering, one level up).  The final short
chunk pads by repeating its last batch; the per-chunk validity mask
(1.0 for real batches) makes the pad steps inert in a masked scan.
Chunk mode yields ``(chunk, mask, n_valid)`` triples — ``chunk`` the
stacked device pytree, ``mask`` a device ``(W,)`` f32, ``n_valid`` the
host-side real-batch count (no device sync needed to count steps).
"""

from __future__ import annotations

import queue
import threading
import time

from concurrent import futures

from dataclasses import dataclass, field
from typing import Any, Callable, Iterable, Iterator, Optional

import jax
import numpy as np

__all__ = ["prefetch_to_device", "PrefetchStats", "masked_chunk_scan",
           "chunk_consumer_plan"]

_END = object()


@dataclass
class PrefetchStats:
    """Cumulative pipeline timing (seconds) and batch count.  Single
    writer per field (each stage runs on one thread; transform workers
    accumulate under the lock).

    In ``chunks=W`` mode ``transform_s`` covers decode AND chunk
    assembly (both run in the decode workers); ``assemble_s`` breaks out
    the stack/pad/mask portion, ``put_s``/``wait_s`` become per-CHUNK
    transfer/wait time, and ``chunks`` counts dispatched chunks
    (``batches`` keeps counting real batches)."""
    read_s: float = 0.0        # source iterator next()
    transform_s: float = 0.0   # decode/pad (sum over workers)
    put_s: float = 0.0         # device_put scheduling
    wait_s: float = 0.0        # consumer blocked on empty queue
    batches: int = 0
    assemble_s: float = 0.0    # chunk stack/pad/mask (within transform_s)
    chunks: int = 0
    chunk_size: Optional[int] = None   # W in chunks=W mode, else None
    _lock: threading.Lock = field(default_factory=threading.Lock,
                                  repr=False)

    def pad_fraction(self) -> float:
        """Fraction of dispatched chunk steps that were padding (the final
        short chunk repeats its last batch): ``(chunks*W - batches) /
        (chunks*W)``.  0.0 outside chunk mode or before any chunk."""
        if not self.chunks or not self.chunk_size:
            return 0.0
        slots = self.chunks * self.chunk_size
        return (slots - self.batches) / slots

    def as_dict(self) -> dict:
        d = {"read_s": round(self.read_s, 4),
             "transform_s": round(self.transform_s, 4),
             "put_s": round(self.put_s, 4),
             "consumer_wait_s": round(self.wait_s, 4),
             "batches": self.batches}
        if self.chunks:
            d["chunk_assemble_s"] = round(self.assemble_s, 4)
            d["chunks"] = self.chunks
            d["pad_fraction"] = round(self.pad_fraction(), 4)
        return d

    def publish(self, group) -> None:
        """Write the current stats into a ``utils.metrics.MetricGroup`` as
        gauges (the observability follow-up to the chunked-dispatch layer:
        internal fields become scrapeable endpoint metrics).  Gauge names
        match :meth:`as_dict` plus ``chunks_emitted`` / ``put_overlap_s``
        aliases for the per-chunk view; safe to call repeatedly — gauges
        are overwritten in place."""
        group.gauge("read_s").set(round(self.read_s, 4))
        group.gauge("transform_s").set(round(self.transform_s, 4))
        group.gauge("put_overlap_s").set(round(self.put_s, 4))
        group.gauge("consumer_wait_s").set(round(self.wait_s, 4))
        group.gauge("batches").set(self.batches)
        group.gauge("chunks_emitted").set(self.chunks)
        group.gauge("pad_fraction").set(round(self.pad_fraction(), 4))
        group.gauge("chunk_assemble_s").set(round(self.assemble_s, 4))


def _grouped(batches: Iterable[Any], size: int) -> Iterator[list]:
    """Consecutive ``size``-item groups of ``batches`` (final group
    short).  A mid-group source error propagates immediately — items
    already read in the broken group are dropped, which keeps the error
    in stream order from the consumer's point of view."""
    group: list = []
    for item in batches:
        group.append(item)
        if len(group) == size:
            yield group
            group = []
    if group:
        yield group


def _assemble_chunk(items: list, size: int):
    """Stack ``items`` (pytrees of equal-shaped leaves) along a new
    leading axis, padding short chunks by repeating the last item;
    returns ``(chunk, mask (size,) f32, n_valid)``."""
    n_valid = len(items)
    if n_valid < size:
        items = items + [items[-1]] * (size - n_valid)
    chunk = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *items)
    mask = np.zeros((size,), np.float32)
    mask[:n_valid] = 1.0
    return chunk, mask, n_valid


def masked_chunk_scan(step: Callable, state: Any, loss_sum, chunk, mask,
                      probe=None):
    """THE consumer half of ``chunks=W``: run ``step(state, *batch) ->
    (new_state, loss)`` over every stacked batch of ``chunk`` as one
    ``lax.scan``, freezing ``state`` and skipping the loss accumulation
    on masked (padded) steps — dead steps are exact no-ops, which is
    what makes any two ``W`` values bit-exact on the same stream.  One
    copy of the freeze/accumulate logic shared by the sgd and WideDeep
    streaming fits (callers jit + donate the ``(state, loss_sum)``
    carry); the hosted ``iterate`` chunk loop carries extra epoch/vote
    structure and stays separate.

    ``probe`` (a :class:`~flink_ml_tpu.obs.StepProbe`, ISSUE 13)
    optionally rides the carry recording the per-step ``loss`` — it is
    frozen on dead steps exactly like the state, so the recorded series
    is W-independent; callers fetch it in one batched transfer at the
    chunk boundary and pass a ``reset()`` probe into the next dispatch.
    ``probe=None`` keeps the 2-tuple carry byte-identical to the
    pre-probe program (the W-bit-exactness contract rides on program
    identity, not just the math)."""
    import jax.numpy as jnp

    if probe is None:
        def scan_step(carry, xs):
            state, loss_sum = carry
            *batch, m = xs
            new_state, loss = step(state, *batch)
            valid = m > 0
            state = jax.tree_util.tree_map(
                lambda n, o: jnp.where(valid, n, o), new_state, state)
            loss_sum = loss_sum + jnp.where(valid, loss, 0.0)
            return (state, loss_sum), None

        (state, loss_sum), _ = jax.lax.scan(scan_step, (state, loss_sum),
                                            tuple(chunk) + (mask,))
        return state, loss_sum

    def probed_step(carry, xs):
        state, loss_sum, probe = carry
        *batch, m = xs
        new_state, loss = step(state, *batch)
        valid = m > 0
        state = jax.tree_util.tree_map(
            lambda n, o: jnp.where(valid, n, o), new_state, state)
        loss_sum = loss_sum + jnp.where(valid, loss, 0.0)
        probe = jax.tree_util.tree_map(
            lambda n, o: jnp.where(valid, n, o),
            probe.record(loss=loss), probe)
        return (state, loss_sum, probe), None

    (state, loss_sum, probe), _ = jax.lax.scan(
        probed_step, (state, loss_sum, probe), tuple(chunk) + (mask,))
    return state, loss_sum, probe


def chunk_consumer_plan(mesh, specs, W: int, prefetch_depth: int):
    """THE shared consumer wiring for ``chunks=W`` prefetch (one copy
    for every adopter — sgd and WideDeep both use it): returns
    ``(sharding, depth)`` where ``sharding`` describes the ``(chunk,
    mask)`` pair — each per-batch PartitionSpec in ``specs`` gains a
    leading (unsharded) chunk axis, the validity mask replicates — and
    ``depth`` converts the caller's per-batch ``prefetch_depth`` into
    chunks (``ceil(prefetch_depth / W)``).  NOTE the floor: staging
    cannot drop below ONE chunk, so chunked mode keeps ``W`` batches
    staged plus ``W`` in compute regardless of ``prefetch_depth`` —
    memory-constrained deployments bound the footprint by lowering
    ``steps_per_dispatch``, not ``prefetch_depth``."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = (tuple(NamedSharding(mesh, P(None, *p)) for p in specs),
                NamedSharding(mesh, P()))
    return sharding, max(1, -(-prefetch_depth // W))


def prefetch_to_device(batches: Iterable[Any], *, depth: int = 2,
                       sharding: Optional[Any] = None,
                       transform: Optional[Callable[[Any], Any]] = None,
                       workers: int = 1,
                       put_workers: int = 1,
                       stats: Optional[PrefetchStats] = None,
                       put_fn: Optional[Callable[[Any, Any], Any]] = None,
                       chunks: Optional[int] = None,
                       metric_group: Optional[Any] = None,
                       retry_policy: Optional[Any] = None
                       ) -> Iterator[Any]:
    """Iterate device-resident copies of ``batches``, staying ``depth``
    UNITS OF WORK ahead of the consumer — a unit is one batch, or one
    ``chunks=W``-batch chunk in chunk mode (so staging memory scales
    with ``depth * W`` batches there; chunking callers size ``depth``
    in chunks, typically 1).

    ``sharding`` (e.g. a ``NamedSharding`` or a pytree of them matching the
    batch structure) is passed to ``device_put``; ``transform`` runs on
    ``workers`` background threads before the transfer (decode/pad/astype —
    keeps that work off the consumer thread; results are reassembled in
    source order, so worker count never changes what the consumer sees).

    ``put_workers`` issues the transfers themselves from that many
    threads — where a single ``device_put`` is latency-bound but
    concurrent ones overlap, parallel puts hide most of the per-batch
    latency (whether they do on a local TPU is unverified: ROADMAP
    S1).  Results are reassembled in source order, so the consumer
    sees the same stream at any worker count.

    Exceptions raised by the source iterator or the transform are re-raised
    at the consuming ``next()`` call.

    ``put_fn(batch, sharding)`` overrides the transfer itself (default
    ``jax.device_put``) — multi-host callers pass an assembly that builds
    non-fully-addressable global arrays from each process's local batch
    (``jax.make_array_from_process_local_data``).

    ``chunks=W`` (an int >= 1; default None = classic per-batch yields)
    groups every ``W`` consecutive (transformed) batches into one
    stacked chunk (see module docstring); ``sharding`` then describes
    the ``(chunk, mask)`` pair — stacked leaves carry a leading chunk
    axis — and the iterator yields ``(chunk, mask, n_valid)`` triples.
    ``chunks=1`` keeps one batch per chunk but still emits the stacked
    triple form, so a ``W=1`` consumer runs the SAME scan program as
    ``W>1`` (the bit-exact fallback).  Incompatible with ``put_fn``
    (process-local assembly is per-batch); multi-process callers use
    ``chunks=None``.

    ``metric_group`` (a ``utils.metrics.MetricGroup``) publishes the
    cumulative :class:`PrefetchStats` as live gauges — chunks emitted, pad
    fraction, put-overlap time, per-stage seconds — refreshed at every
    yielded item and once more at stream end, so a fit's ingest pipeline
    is observable through the same registry as its epoch metrics.

    ``retry_policy`` (a ``robustness.retry.RetryPolicy``) retries the
    SOURCE pull on classified-transient errors with exponential backoff
    — a flaky read costs a sleep on the reader thread (overlapped by
    whatever is already staged), not the fit.  ``batches`` is wrapped in
    a ``RetryingIterator`` at the raw-source level (below chunk
    grouping), so object-shaped sources retry in place and cursor-backed
    generator sources re-iterate at their cursor; a bare generator that
    dies on a transient fails LOUDLY (``StreamRetryUnsupported``) rather
    than truncating silently.  The source must not consume an item on a
    failed pull (raise-before-read, the ``FaultPlan.wrap_source``
    contract) or be idempotent at the failed position; fatal errors
    still propagate in stream order.
    """
    if depth < 1:
        raise ValueError(f"depth must be >= 1, got {depth}")
    if workers < 1:
        raise ValueError(f"workers must be >= 1, got {workers}")
    if put_workers < 1:
        raise ValueError(f"put_workers must be >= 1, got {put_workers}")
    if chunks is not None and chunks < 1:
        raise ValueError(f"chunks must be >= 1 (or None), got {chunks}")
    if chunks is not None and put_fn is not None:
        raise ValueError(
            "chunks= does not compose with put_fn (process-local "
            "assembly is per-batch); use chunks=None on process-"
            "spanning meshes")
    st = stats or PrefetchStats()
    if chunks is not None:
        st.chunk_size = chunks
    if retry_policy is not None:
        # wrap the RAW source, below the chunk grouping: retrying above
        # a generator adapter would read StopIteration off its dead
        # frame and silently truncate (robustness.retry.RetryingIterator
        # docs); StopIteration itself is never classified retryable, so
        # end-of-stream passes through the policy untouched
        from ..robustness.retry import RetryingIterator

        batches = RetryingIterator(batches, retry_policy)

    if chunks is not None:
        item_transform = transform
        batches = _grouped(batches, chunks)

        def transform(group):  # noqa: F811 — chunk-mode transform
            items = ([item_transform(b) for b in group]
                     if item_transform is not None else list(group))
            t0 = time.perf_counter()
            assembled = _assemble_chunk(items, chunks)
            with st._lock:
                st.assemble_s += time.perf_counter() - t0
                st.chunks += 1
            return assembled

    def put(batch, sh):
        if chunks is not None:
            chunk, mask, n_valid = batch
            payload = (chunk, mask)
            moved = jax.device_put(payload, sh) if sh is not None \
                else jax.device_put(payload)
            return moved + (n_valid,)
        # honor the documented 2-arg put_fn contract on BOTH branches
        if put_fn is not None:
            return put_fn(batch, sh)
        return jax.device_put(batch, sh) if sh is not None \
            else jax.device_put(batch)
    q: queue.Queue = queue.Queue(maxsize=depth)
    stop = threading.Event()

    def put_or_abandon(dst: queue.Queue, item) -> None:
        """Stop-aware put: never parks forever if the consumer walked away
        (an untimed put here would leak the thread + queued device buffers)."""
        while not stop.is_set():
            try:
                dst.put(item, timeout=0.1)
                return
            except queue.Full:
                continue

    def timed_transform(batch):
        t0 = time.perf_counter()
        out = transform(batch) if transform is not None else batch
        with st._lock:
            st.transform_s += time.perf_counter() - t0
        return out

    if workers == 1 and put_workers == 1:
        def worker():
            try:
                src = iter(batches)
                while True:
                    t0 = time.perf_counter()
                    try:
                        batch = next(src)
                    except StopIteration:
                        break
                    st.read_s += time.perf_counter() - t0
                    if stop.is_set():
                        return
                    batch = timed_transform(batch)
                    t0 = time.perf_counter()
                    batch = put(batch, sharding)
                    st.put_s += time.perf_counter() - t0
                    put_or_abandon(q, batch)
                put_or_abandon(q, _END)
            except BaseException as exc:  # noqa: BLE001 — re-raised at consumer
                put_or_abandon(q, exc)

        threads = [threading.Thread(target=worker, daemon=True,
                                    name="flink-ml-tpu-prefetch")]
    else:
        from concurrent.futures import ThreadPoolExecutor

        pool = ThreadPoolExecutor(max_workers=workers,
                                  thread_name_prefix="flink-ml-tpu-decode")
        fq: queue.Queue = queue.Queue(maxsize=depth + workers + put_workers)
        # ordered reassembly shared by the putter pool: seq -> device
        # batch, flushed to q in source order as the prefix completes
        flush_lock = threading.Lock()
        pending: dict = {}
        flush_state = {"next": 0, "total": None, "finished": False,
                       "draining": False}
        # latched once an in-stream error entry is FLUSHED: the consumer
        # will raise at that seq, so later transfers are pure waste —
        # putters check this before waiting on decodes / issuing puts
        failed = threading.Event()

        def _collect_ready_locked() -> list:
            """Pop the completed prefix (appending the terminal _END once
            the reader's total is known and reached).  Caller holds
            flush_lock; no queue puts happen here — the blocking puts run
            OUTSIDE the lock so put concurrency survives backpressure (a
            full q must stall only the emitter, not every putter trying
            to register a completion)."""
            ready: list = []
            while flush_state["next"] in pending:
                entry = pending.pop(flush_state["next"])
                if isinstance(entry, BaseException):
                    failed.set()
                ready.append(entry)
                flush_state["next"] += 1
            if (flush_state["total"] is not None
                    and flush_state["next"] >= flush_state["total"]
                    and not flush_state["finished"]):
                flush_state["finished"] = True
                ready.append(_END)
            return ready

        def _flush_ready():
            """Emit every ready entry to q in source order.  Exactly one
            thread drains at a time (the ``draining`` flag): a second
            completer registers its entry and leaves — the active drainer
            re-collects after each emit round, so nothing is stranded —
            and the single-drainer rule is what preserves source order
            now that the puts happen outside flush_lock."""
            flush_lock.acquire()
            try:
                if flush_state["draining"]:
                    return
                flush_state["draining"] = True
                try:
                    while True:
                        ready = _collect_ready_locked()
                        if not ready:
                            return
                        flush_lock.release()
                        try:
                            for entry in ready:
                                put_or_abandon(q, entry)
                        finally:
                            flush_lock.acquire()
                finally:
                    flush_state["draining"] = False
            finally:
                flush_lock.release()

        def reader():
            seq = 0
            try:
                src = iter(batches)
                while True:
                    t0 = time.perf_counter()
                    try:
                        batch = next(src)
                    except StopIteration:
                        break
                    st.read_s += time.perf_counter() - t0
                    if stop.is_set():
                        return
                    if failed.is_set():
                        break   # consumer will raise; stop reading ahead
                    put_or_abandon(
                        fq, (seq, pool.submit(timed_transform, batch)))
                    seq += 1
                with flush_lock:
                    flush_state["total"] = seq
                _flush_ready()   # covers the empty stream
            except BaseException as exc:  # noqa: BLE001
                # deliver the error IN STREAM ORDER: it enters the
                # reassembly at the next seq, so every batch already
                # read and decoded reaches the consumer first (callers
                # that checkpoint from the last consumed batch rely on
                # this)
                with flush_lock:
                    pending[seq] = exc
                    flush_state["total"] = seq + 1
                _flush_ready()
            for _ in range(put_workers):
                put_or_abandon(fq, _END)

        def get_or_abandon(src: queue.Queue):
            """Stop-aware get: the putter must exit when the consumer
            walks away, or it leaks for process lifetime."""
            while not stop.is_set():
                try:
                    return src.get(timeout=0.1)
                except queue.Empty:
                    continue
            return _END

        def putter():
            while True:
                # a flushed in-stream error means the consumer raises at
                # that seq: stop pulling work — every further device_put
                # would transfer batches nobody will ever read
                if failed.is_set():
                    return
                item = get_or_abandon(fq)
                if item is _END:
                    return
                seq, fut = item
                # stop-aware future wait, mirroring put/get_or_abandon:
                # an abandoned consumer must not leave this thread
                # blocked behind a hung transform.  Poll done-ness
                # rather than catching TimeoutError from result() —
                # futures.TimeoutError IS the builtin TimeoutError on
                # 3.11+, so a transform failing with e.g.
                # socket.timeout must still propagate, not spin.
                while not stop.is_set() and not failed.is_set() \
                        and not fut.done():
                    futures.wait([fut], timeout=0.1)
                if stop.is_set() or failed.is_set():
                    fut.cancel()
                    return
                try:
                    batch = fut.result()
                    if failed.is_set():   # error flushed during decode
                        return
                    t0 = time.perf_counter()
                    entry = put(batch, sharding)
                    with st._lock:
                        st.put_s += time.perf_counter() - t0
                except BaseException as exc:  # noqa: BLE001
                    # transform/put errors ride the reassembly at their
                    # own seq: every earlier batch is delivered first,
                    # exactly like the reader's error path
                    entry = exc
                with flush_lock:
                    pending[seq] = entry
                _flush_ready()
                if isinstance(entry, BaseException):
                    return

        threads = [threading.Thread(target=reader, daemon=True,
                                    name="flink-ml-tpu-prefetch-read")]
        threads += [threading.Thread(target=putter, daemon=True,
                                     name=f"flink-ml-tpu-prefetch-put-{i}")
                    for i in range(put_workers)]

    for t in threads:
        t.start()
    try:
        while True:
            t0 = time.perf_counter()
            item = q.get()
            st.wait_s += time.perf_counter() - t0
            if item is _END:
                return
            if isinstance(item, BaseException):
                raise item
            st.batches += item[2] if chunks is not None else 1
            if metric_group is not None:
                st.publish(metric_group)
            yield item
    finally:
        stop.set()
        # Quiesce the pipeline threads before returning control: an
        # abandoned-but-alive reader still holds the SOURCE iterator, and
        # a supervised fit (robustness.resilient_fit) re-attempts over
        # the same live source — a zombie reader would race the new
        # attempt's pulls (observed: windows silently consumed between
        # WAL replay and the live tail).  The join is bounded: a reader
        # parked inside a blocking live-source pull cannot be
        # interrupted — it dies at its next stop check; sources feeding
        # supervised fits should deliver or fail, not park forever.
        for t in threads:
            t.join(timeout=5.0)
            if t.is_alive():
                import logging

                logging.getLogger("flink_ml_tpu.robustness").warning(
                    "prefetch thread %s still alive after close "
                    "(blocked in a live-source pull?); it will exit at "
                    "its next stop check", t.name)
        if metric_group is not None:
            st.publish(metric_group)
        if workers > 1 or put_workers > 1:
            pool.shutdown(wait=False, cancel_futures=True)
