"""Tier-1 wiring for the unified observability layer (ISSUE 13).

Four blocks:

1. **Tracer mechanics** — ring wraparound, disabled-path no-op,
   retroactive spans, Chrome-trace/JSONL export round trips; a span is
   also a profiler annotation, and ``fit()`` carries its phase spans;
   ``iterate.dispatch`` is its five stages, the staged call is the plain
   jitted call to the bit, and ``cache_hit`` says what served it.
2. **One metrics tree** — every surface merges into one snapshot, the
   Prometheus exposition parses line by line, the never-published
   staleness gauge exports ABSENT (the ``-1`` sentinel regression),
   the background sampler's JSONL survives a torn tail.
3. **StepProbe** — device-side recording under jit/scan, one-transfer
   fetch, masked-freeze parity (probe on/off bit-exact through the real
   chunked fit), ServingMetrics edge cases.
4. **THE acceptance** — one enabled tracer follows a correlation chain
   from WAL ingest through checkpoint cut and delta publish to a served
   request, in the exported trace; serving with tracing on adds ZERO
   new XLA lowerings after warm-up.
"""

import json
import math
import os
import re

import numpy as np
import pytest

from flink_ml_tpu import Table
from flink_ml_tpu.obs import (
    MetricsTree,
    ObsSampler,
    SpanTracer,
    StepProbe,
    default_tree,
    prometheus_text,
    read_samples,
)
from flink_ml_tpu.obs import trace as trace_mod
from flink_ml_tpu.serving.metrics import ServingMetrics


@pytest.fixture(autouse=True)
def _quiet_global_tracer():
    """Every test leaves the process-wide tracer disabled and empty."""
    yield
    trace_mod.tracer.disable()
    trace_mod.tracer.clear()


# ---------------------------------------------------------------------------
# 1. tracer mechanics
# ---------------------------------------------------------------------------

def test_tracer_disabled_records_nothing():
    t = SpanTracer(capacity=8)
    # ring off: a span is the profiler's annotation alone (inert with no
    # session); ``note`` still chains, nothing is recorded
    with t.span("a", op="x") as span:
        assert span.note(request_id=1) is span
    t.instant("b")
    t.add("c", 0.0, 1.0)
    assert t.spans() == [] and t.count == 0


# the phase spans of one fit(), in the order they must appear
FIT_PHASES = ("fit.gather", "fit.arrange", "fit.upload",
              "iterate.dispatch", "fit.fetch")


def _fit_case(name):
    """``(make_estimator, table, arrays_of_model)`` for a small fit."""
    rng = np.random.default_rng(11)
    if name == "kmeans":
        from flink_ml_tpu.models.clustering.kmeans import KMeans

        table = Table({"features": rng.normal(size=(300, 5))})
        return (lambda: KMeans().set_k(3).set_max_iter(3).set_seed(7),
                table,
                lambda m: [np.asarray(m.get_model_data()[0]["centroids"])])
    from flink_ml_tpu.models.classification.logisticregression import (
        LogisticRegression,
    )

    n, n_dense, n_cat, d = 256, 4, 3, 128
    dense = rng.normal(size=(n, n_dense)).astype(np.float32)
    cat = rng.integers(n_dense, d, size=(n, n_cat)).astype(np.int32)
    table = Table({"features_dense": dense, "features_indices": cat,
                   "label": (dense[:, 0] > 0).astype(np.float64)})
    return (lambda: (LogisticRegression().set_num_features(d)
                     .set_max_iter(2).set_tol(0).set_seed(5)
                     .set_global_batch_size(64)),
            table,
            lambda m: [m._state.coefficients,
                       np.asarray(m._state.intercept),
                       np.asarray(m._loss_log)])


@pytest.fixture
def profiler_session(tmp_path):
    """``run(fn)`` calls ``fn`` inside a ``jax.profiler`` session and
    returns ``(fn's result, the program's spans read back from the
    .xplane.pb)``: ``(name, start_ns, end_ns, stats)`` of the host
    events named ``fit*`` / ``iterate.dispatch``, in time order."""
    import glob

    import jax

    def run(fn):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            result = fn()
        finally:
            jax.profiler.stop_trace()
        (pb,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
        spans = []
        for plane in jax.profiler.ProfileData.from_file(pb).planes:
            if plane.name.startswith("/device:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name == "fit" or e.name.startswith(
                            ("fit.", "iterate.dispatch")):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns,
                                      dict(e.stats)))
        return result, sorted(spans, key=lambda s: (s[1], -s[2]))

    return run


@pytest.mark.parametrize("case", ["kmeans", "lr_mixed"])
def test_fit_spans_land_in_the_profiler_trace_with_the_ring_off(
        case, profiler_session):
    make, table, _ = _fit_case(case)
    assert not trace_mod.tracer.enabled
    _, spans = profiler_session(
        lambda: [make().fit(table), make().fit(table)])
    assert trace_mod.tracer.count == 0
    roots = [s for s in spans if s[0] == "fit"]
    assert len(roots) == 2
    assert roots[0][2] <= roots[1][1]
    fit_ids = [r[3]["fit"] for r in roots]
    assert fit_ids[0] != fit_ids[1]
    for _, lo, hi, stats in roots:
        assert stats["op"] == type(make()).__name__
        inside = [s for s in spans if s[0] != "fit" and lo <= s[1] < hi]
        assert all(s[2] <= hi for s in inside)
        assert {s[3]["fit"] for s in inside} == {stats["fit"]}
        phases = [s for s in inside if s[0] in FIT_PHASES]
        # each phase is there, first met in this order (a phase may come
        # in adjacent pieces, one per function that does part of it; on a
        # one-process mesh KMeans' rows go up in rounds and the devices lay
        # a round out while the next is put, PR 39, so ``fit.arrange`` and
        # ``fit.upload`` may take turns; nothing else comes twice) ...
        names = [s[0] for s in phases]
        assert tuple(dict.fromkeys(names)) == FIT_PHASES
        order = [n for i, n in enumerate(names)
                 if i == 0 or names[i - 1] != n]
        assert [n for n in order
                if n not in ("fit.arrange", "fit.upload")] == [
                    "fit.gather", "iterate.dispatch", "fit.fetch"]
        # ... and no two overlap
        assert all(a[2] <= b[1] for a, b in zip(phases, phases[1:]))
        # a child span lies inside a span of its parent's name
        for name, c_lo, c_hi, _ in inside:
            if name not in FIT_PHASES:
                parent = name.rsplit(".", 1)[0]
                assert any(p[0] == parent and p[1] <= c_lo and c_hi <= p[2]
                           for p in phases), name


@pytest.mark.parametrize("case", ["kmeans", "lr_mixed"])
def test_fit_spans_land_in_the_ring_when_it_is_on(case):
    make, table, _ = _fit_case(case)
    tracer = trace_mod.tracer
    tracer.enable()
    make().fit(table)
    make().fit(table)
    tracer.disable()
    roots = list(tracer.find("fit"))
    assert len(roots) == 2
    assert roots[0].ids["fit"] != roots[1].ids["fit"]
    for root in roots:
        names = {s.name for s in tracer.find(fit=root.ids["fit"])}
        assert set(FIT_PHASES) <= names and "fit" in names
        for s in tracer.find(fit=root.ids["fit"]):
            assert root.t0 <= s.t0 and s.t0 + s.dur <= root.t0 + root.dur


@pytest.mark.parametrize("case", ["kmeans", "lr_mixed"])
def test_fit_is_the_same_model_traced_or_not_and_records_nothing_when_off(
        case, profiler_session):
    make, table, arrays = _fit_case(case)
    tracer = trace_mod.tracer
    plain = arrays(make().fit(table))
    assert tracer.count == 0 and tracer.spans() == []
    traced, spans = profiler_session(lambda: arrays(make().fit(table)))
    assert any(s[0] == "fit" for s in spans)
    assert len(plain) == len(traced)
    for a, b in zip(plain, traced):
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()


# the stages of ``iterate.dispatch``, its flat children, in order
DISPATCH_STAGES = tuple("iterate.dispatch." + stage for stage in (
    "probe", "trace", "lower", "compile", "enqueue"))


def _loop(votes, mode="fused", handed_over=False, width=6):
    """``run() -> (result, the state it was given)``: one ``iterate``
    inside a root span, as an estimator's ``fit`` would call it.  With
    ``votes`` the body has a criterion (fused: the ``while_loop`` branch),
    without it emits an output an epoch (the ``lax.scan`` branch)."""
    import jax.numpy as jnp

    from flink_ml_tpu.iteration import (
        HandedOver,
        IterationBodyResult,
        IterationConfig,
        iterate,
    )

    def body(state, epoch, data):
        new = jnp.tanh(state @ data) * 0.5 + state * 0.25
        if votes:
            return IterationBodyResult(
                new, termination=jnp.abs(new - state).max() > 1e-3)
        return IterationBodyResult(new, outputs=jnp.sum(new * new))

    def run():
        rng = np.random.default_rng(3)
        state = jnp.asarray(rng.normal(size=(4, width)), jnp.float32)
        data = jnp.asarray(rng.normal(size=(width, width)), jnp.float32)
        with trace_mod.tracer.fit_span("Loop"):
            result = iterate(
                body, HandedOver(state) if handed_over else state, data,
                max_epochs=9 if votes else 5,
                config=IterationConfig(mode=mode))
        return result, state

    return run


def _criteria_loop(**kwargs):
    return _loop(True, **kwargs)


def _scan_loop(**kwargs):
    return _loop(False, **kwargs)


@pytest.mark.parametrize("case", ["kmeans", "lr_mixed", "criteria"])
def test_the_dispatch_is_its_five_stages_in_the_profiler_trace(
        case, profiler_session):
    if case == "criteria":
        run = _criteria_loop()
    else:
        make, table, _ = _fit_case(case)
        run = lambda: make().fit(table)  # noqa: E731
    _, spans = profiler_session(lambda: [run(), run()])
    roots = [s for s in spans if s[0] == "fit"]
    assert len(roots) == 2
    reused = []
    for _, lo, hi, stats in roots:
        inside = [s for s in spans if lo <= s[1] and s[2] <= hi]
        (whole,) = [s for s in inside if s[0] == "iterate.dispatch"]
        stages = [s for s in inside if s[0].startswith("iterate.dispatch.")]
        # once each, in order, one after the other, inside the dispatch:
        # in a fit that builds its program and in one that reuses it
        assert tuple(s[0] for s in stages) == DISPATCH_STAGES
        assert all(a[2] <= b[1] for a, b in zip(stages, stages[1:]))
        assert whole[1] <= stages[0][1] and stages[-1][2] <= whole[2]
        assert {s[3]["fit"] for s in stages} == {stats["fit"]}
        # what the compile-cache request was answered with and whether
        # the process's own entry answered, and nothing of the kind on
        # another stage
        assert stages[3][3]["cache_hit"] in (0, 1)
        reused.append(stages[3][3]["reused"])
        for note in ("cache_hit", "reused"):
            assert all(note not in s[3] for s in stages[:3] + stages[4:])
    # KMeans' body and the linear family's epoch body state their program
    # keys; the loop's is a plain closure, built anew in every fit
    assert reused == ([0, 0] if case == "criteria" else [0, 1])


@pytest.fixture
def compile_cache_at(tmp_path):
    """JAX's persistent compile cache in an empty directory of the
    test's own, every compile kept; the process's settings put back."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    settings = {"jax_compilation_cache_dir": str(tmp_path / "xla_cache"),
                "jax_persistent_cache_min_compile_time_secs": 0.0,
                "jax_persistent_cache_min_entry_size_bytes": 0}
    before = {name: getattr(jax.config, name) for name in settings}
    for name, value in settings.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()
    yield tmp_path / "xla_cache"
    for name, value in before.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("case", ["kmeans", "criteria"])
def test_cache_hit_is_0_on_a_shapes_first_fit_and_1_on_its_second(
        case, compile_cache_at):
    """What answers a fit's request for its program.  A plain closure
    (``criteria``) asks the persistent cache every time: compiled, then
    served.  A keyed body (``kmeans``): compiled; then the process's own
    entry, the cache not asked; then, the entries emptied, the persistent
    cache again."""
    from flink_ml_tpu.iteration import clear_programs

    if case == "criteria":
        run = _criteria_loop(width=7)
    else:
        from flink_ml_tpu.models.clustering.kmeans import KMeans

        table = Table({"features":
                       np.random.default_rng(2).normal(size=(211, 7))})
        run = lambda: (KMeans().set_k(4).set_max_iter(2)  # noqa: E731
                       .set_seed(3).fit(table))
    tracer = trace_mod.tracer
    tracer.enable()
    run()
    entries = len(os.listdir(compile_cache_at))
    assert entries >= 1
    run()
    clear_programs()
    run()
    tracer.disable()
    requests = list(tracer.find("iterate.dispatch.compile"))
    assert [s.ids["cache_hit"] for s in requests] == [0, 1, 1]
    assert [s.ids["reused"] for s in requests] == (
        [0, 1, 0] if case == "kmeans" else [0, 0, 0])
    # the later fits asked with the first's key or not at all: nothing
    # added
    assert len(os.listdir(compile_cache_at)) == entries
    fits = [s.ids["fit"] for s in tracer.find("fit")]
    assert [s.ids["fit"] for s in requests] == fits


@pytest.mark.parametrize("branch", ["scan", "while_loop"])
def test_the_staged_dispatch_is_the_plain_jitted_call_to_the_bit(
        branch, monkeypatch, compile_cache_at):
    import jax

    from flink_ml_tpu.iteration import core

    make = _scan_loop if branch == "scan" else _criteria_loop
    staged, given = make(handed_over=True)()
    # a handed-over state is donated as it is: consumed by the call
    assert given.is_deleted()
    kept, mine = make()()
    assert not mine.is_deleted()
    entries = sorted(os.listdir(compile_cache_at))

    calls = []

    def plain(run, state, data):
        calls.append(run)
        return run      # the enqueue is then the jitted call, as it was

    monkeypatch.setattr(core, "_compile_staged", plain)
    reference, given = make(handed_over=True)()
    assert len(calls) == 1 and given.is_deleted()
    # the jitted call asks the persistent cache with the staged call's
    # key: it is served, and adds no entry
    assert sorted(os.listdir(compile_cache_at)) == entries

    for result in (staged, kept):
        assert result.num_epochs == reference.num_epochs
        got = jax.tree_util.tree_leaves((result.state, result.outputs))
        want = jax.tree_util.tree_leaves(
            (reference.state, reference.outputs))
        assert len(got) == len(want) >= 1
        for a, b in zip(got, want):
            a, b = np.asarray(a), np.asarray(b)
            assert a.dtype == b.dtype and a.shape == b.shape
            assert a.tobytes() == b.tobytes()
    if branch == "while_loop":
        assert 1 < staged.num_epochs <= 9
        for name, curve in reference.side["epoch_trace"].items():
            assert (staged.side["epoch_trace"][name].tobytes()
                    == curve.tobytes())
    else:
        assert np.asarray(staged.outputs).shape == (5,)


def test_an_auto_calls_probe_is_a_dispatch_piece_of_its_own():
    tracer = trace_mod.tracer
    tracer.enable()
    # no vote: auto takes the fused loop, whose dispatch probes again
    result, _ = _scan_loop(mode="auto")()
    spans = [s for s in tracer.spans() if s.name.startswith("iterate")]
    pieces = [s for s in spans if s.name == "iterate.dispatch"]
    probes = [s for s in spans if s.name == "iterate.dispatch.probe"]
    assert len(pieces) == 2 and len(probes) == 2
    pieces.sort(key=lambda s: s.t0)
    probes.sort(key=lambda s: s.t0)
    first, second = pieces
    assert first.t0 + first.dur <= second.t0
    for probe, piece in zip(probes, pieces):
        assert piece.t0 <= probe.t0
        assert probe.t0 + probe.dur <= piece.t0 + piece.dur
    # the first piece holds the probe and nothing else
    others = [s for s in spans if s.name.startswith("iterate.dispatch.")
              and s.name != "iterate.dispatch.probe"]
    assert sorted(s.name for s in others) == sorted(DISPATCH_STAGES[1:])
    assert all(second.t0 <= s.t0 for s in others)
    assert len({s.ids["fit"] for s in spans}) == 1
    assert result.num_epochs == 5

    # a vote: auto takes the hosted loop, and the probe is all there is
    tracer.clear()
    hosted, _ = _criteria_loop(mode="auto")()
    spans = [s.name for s in tracer.spans() if s.name.startswith("iterate")]
    assert sorted(spans) == ["iterate.dispatch", "iterate.dispatch.probe"]
    assert hosted.side["termination_reason"] in ("criteria", "max_epochs")


def test_tracer_ring_wraparound_keeps_newest():
    t = SpanTracer(capacity=4).enable()
    for i in range(6):
        t.add(f"s{i}", 0.0, 0.1, step=i)
    assert t.count == 6 and t.dropped == 2
    assert [s.name for s in t.spans()] == ["s2", "s3", "s4", "s5"]


def test_tracer_span_note_and_find():
    t = SpanTracer(capacity=8).enable()
    with t.span("serve", generation=1) as span:
        span.note(request_id=7)
    found = list(t.find("serve", request_id=7))
    assert len(found) == 1 and found[0].ids["generation"] == 1
    assert list(t.find("serve", request_id=99)) == []


def test_chrome_export_round_trips(tmp_path):
    t = SpanTracer(capacity=16).enable()
    with t.span("outer", cat="serving", request_id=3):
        t.instant("mark", window=5)
    path = str(tmp_path / "trace.json")
    n = t.export_chrome(path)
    assert n == 2
    loaded = json.load(open(path))
    events = loaded["traceEvents"]
    by_name = {e["name"]: e for e in events}
    outer, mark = by_name["outer"], by_name["mark"]
    # the Chrome-trace contract Perfetto loads: X events carry ts+dur,
    # instants carry a scope, args hold the correlation ids
    assert outer["ph"] == "X" and outer["dur"] >= 0
    assert mark["ph"] == "i" and mark["s"] == "t"
    assert outer["args"]["request_id"] == 3
    assert mark["args"]["window"] == 5
    assert all(e["ts"] >= 0 and e["pid"] == os.getpid() for e in events)
    # the instant falls INSIDE the enclosing span's interval
    assert outer["ts"] <= mark["ts"] <= outer["ts"] + outer["dur"]


def test_jsonl_export_round_trips(tmp_path):
    t = SpanTracer(capacity=8).enable()
    t.add("a", 1.0, 2.0, step=4)
    path = str(tmp_path / "trace.jsonl")
    assert t.export_jsonl(path) == 1
    lines = [json.loads(line) for line in open(path)]
    assert lines[0]["name"] == "a" and lines[0]["step"] == 4
    assert lines[0]["dur_s"] == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# 2. the metrics tree
# ---------------------------------------------------------------------------

def _publish_once(m: ServingMetrics, generation=1, t0=1000.0):
    m.on_publish(generation, mode="delta", payload_bytes=64, now=t0)


def test_metrics_tree_merges_every_surface():
    from flink_ml_tpu.kernels.registry import kernel_stats
    from flink_ml_tpu.robustness.supervisor import RecoveryReport

    m = ServingMetrics()
    m.on_batch(n_requests=2, rows=3, bucket=8, latencies_s=[0.01, 0.02],
               queue_depth=0, generation=1)
    report = RecoveryReport(restarts=1, recovered=True)
    stream_info = {"impl": "dense-stream",
                   "step_trace": {"loss": np.asarray([1.0, 0.5])}}
    tree = default_tree(serving=m, recovery=report,
                        stream_info=stream_info, tracer=trace_mod.tracer)
    snap = tree.snapshot()
    assert snap["serving"]["requests"] == 2
    assert snap["recovery"]["restarts"] == 1
    assert snap["training"]["step_trace"]["loss"] == [1.0, 0.5]
    assert snap["trace"]["enabled"] is False
    assert snap["kernels"]["dispatches"] == kernel_stats.dispatches
    assert "aot" in snap["kernels"] and "tuned_ops" in snap["kernels"]
    json.dumps(snap)        # JSON-clean end to end (numpy normalized)


def test_default_tree_registers_autoscale_provider():
    """ISSUE 17: the autoscale controller's self-view hangs off the same
    tree it reads — counters, the live placement, and the decision
    latency (NaN before the first tick: absent in prometheus, the
    never-faked stance) round-trip snapshot -> exposition."""
    from flink_ml_tpu.autoscale import (AutoscaleController,
                                        AutoscalePolicy, PlacementStore,
                                        PolicyConfig, SignalSource)

    store = PlacementStore(4)
    store.publish({"svc": [0, 1]}, 2)
    inner = MetricsTree()
    controller = AutoscaleController(
        store=store,
        policy=AutoscalePolicy(PolicyConfig(p99_target_ms=50.0,
                                            total_chips=4)),
        signals=SignalSource(inner))
    tree = default_tree(autoscale=controller)
    snap = tree.snapshot()
    assert snap["autoscale"]["ticks"] == 0
    assert snap["autoscale"]["placement_generation"] == 1
    assert snap["autoscale"]["placement_learner_workers"] == 2
    assert math.isnan(snap["autoscale"]["decision_latency_s"])
    text = prometheus_text(snap)
    assert "flink_ml_tpu_autoscale_placement_generation 1" in text
    assert "decision_latency_s" not in text      # NaN = absent
    json.dumps(snap)
    controller.tick()
    snap = tree.snapshot()
    assert snap["autoscale"]["ticks"] == 1
    assert snap["autoscale"]["decision_latency_s"] >= 0.0
    assert "decision_latency_s" in prometheus_text(snap)


def test_metrics_tree_provider_kinds_and_none():
    tree = MetricsTree()
    tree.register("fn", lambda: {"a": 1})
    tree.register("ref", {"b": np.int64(2)})
    tree.register("absent", lambda: None)
    snap = tree.snapshot()
    assert snap == {"fn": {"a": 1}, "ref": {"b": 2}}
    with pytest.raises(TypeError, match="unsnapshotable"):
        tree.register("bad", 42)


_PROM_LINE = re.compile(
    r"^(?:# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* gauge"
    r"|[a-zA-Z_:][a-zA-Z0-9_:]* -?[0-9.eE+-]+(?:\.[0-9]+)?)$")


def test_prometheus_exposition_parses():
    """Every emitted line is either a TYPE comment or `name value` with
    a legal metric name — the strict-parse half of the acceptance."""
    m = ServingMetrics()
    m.on_batch(n_requests=1, rows=1, bucket=8, latencies_s=[0.005],
               queue_depth=0, generation=2)
    text = prometheus_text(default_tree(serving=m).snapshot())
    lines = text.strip().split("\n")
    assert len(lines) >= 10
    for line in lines:
        assert _PROM_LINE.match(line), f"unparseable line: {line!r}"
    # dotted MetricGroup keys flatten into legal names with values
    assert re.search(
        r"^flink_ml_tpu_serving_requests 1(\.0)?$", text, re.M)
    # strings (health) are skipped, not mangled into bad samples
    assert "SERVING" not in text


def test_staleness_sentinel_never_exports_negative():
    """ISSUE 13 satellite regression: never-published staleness is NaN
    on the gauge and ABSENT from the exposition — not a fake ``-1``
    age.  After a publish it exports as a real non-negative number."""
    m = ServingMetrics()
    m.touch_staleness()
    assert math.isnan(m.staleness_seconds)
    snap = m.snapshot()
    assert math.isnan(snap["model_staleness_seconds"])
    text = prometheus_text({"serving": m.group.snapshot()})
    assert "model_staleness_seconds" not in text
    assert "-1" not in text.split()
    _publish_once(m, t0=1000.0)
    m.touch_staleness(now=1002.5)
    assert m.staleness_seconds == pytest.approx(2.5)
    text = prometheus_text({"serving": m.group.snapshot()})
    assert re.search(
        r"^flink_ml_tpu_serving_model_staleness_seconds 2\.5$", text, re.M)


def test_sampler_appends_and_survives_torn_tail(tmp_path):
    path = str(tmp_path / "series.jsonl")
    tree = MetricsTree().register("x", lambda: {"v": 1})
    clock = iter([10.0, 11.0]).__next__
    sampler = ObsSampler(tree, path, interval_s=60.0, clock=clock)
    sampler.sample()
    sampler.sample()
    # crash mid-append: a torn final line is dropped by the reader
    with open(path, "a") as f:
        f.write('{"t": 12.0, "x": {"v"')
    samples = read_samples(path)
    assert [s["t"] for s in samples] == [10.0, 11.0]
    assert samples[0]["x"] == {"v": 1}
    assert sampler.samples_written == 2


def test_sampler_mid_series_corruption_raises(tmp_path):
    path = str(tmp_path / "series.jsonl")
    with open(path, "w") as f:
        f.write('{"t": 1}\nGARBAGE\n{"t": 2}\n')
    with pytest.raises(ValueError, match="not the tail"):
        read_samples(path)


def test_sampler_background_thread_ticks(tmp_path):
    import time as _time

    path = str(tmp_path / "bg.jsonl")
    tree = MetricsTree().register("x", lambda: {"v": 2})
    sampler = ObsSampler(tree, path, interval_s=0.01).start()
    deadline = _time.time() + 5.0
    while sampler.samples_written < 2 and _time.time() < deadline:
        _time.sleep(0.01)
    sampler.stop()
    assert len(read_samples(path)) >= 2


# ---------------------------------------------------------------------------
# 3a. ServingMetrics edge cases (ISSUE 13 satellite)
# ---------------------------------------------------------------------------

def test_publishes_per_sec_ewma_first_publish():
    """The FIRST publish has no predecessor interval: the rate gauge
    must stay unset (no fake spike from a zero interval); the second
    publish seeds the EWMA with the true instantaneous rate."""
    m = ServingMetrics()
    _publish_once(m, generation=1, t0=1000.0)
    assert m.snapshot()["publishes_per_sec"] is None
    m.on_publish(2, mode="delta", now=1002.0)
    assert m.snapshot()["publishes_per_sec"] == pytest.approx(0.5)
    m.on_publish(3, mode="delta", now=1004.0)        # EWMA stays put
    assert m.snapshot()["publishes_per_sec"] == pytest.approx(0.5)


def test_latency_ring_quantiles_at_wraparound():
    """Past the window the ring holds exactly the newest ``window``
    samples (write order irrelevant to quantiles): quantiles must match
    numpy over that set, not over a stale prefix."""
    from flink_ml_tpu.serving.metrics import LatencyTracker

    tracker = LatencyTracker(window=8)
    for v in range(1, 13):                 # 12 records, window 8
        tracker.record(float(v))
    assert tracker.count == 12
    newest = np.asarray([5.0, 6, 7, 8, 9, 10, 11, 12])
    p50, p99 = tracker.quantiles((0.5, 0.99))
    assert p50 == pytest.approx(float(np.quantile(newest, 0.5)))
    assert p99 == pytest.approx(float(np.quantile(newest, 0.99)))


def test_kernel_gauges_republish_skips_if_unchanged():
    """The kernels.* re-export refreshes only when the dispatch counter
    moved — an idle endpoint's metric tick must not re-walk the
    registry snapshot."""
    from flink_ml_tpu.api.chain import StageKernel, run_kernel
    from flink_ml_tpu.kernels.registry import kernel_stats

    m = ServingMetrics()
    m.publish()
    sentinel = object()
    gauge = m.group.add_group("kernels").gauge("dispatches")
    gauge.set(sentinel)
    m.publish()                            # counter unchanged -> skipped
    assert gauge.value is sentinel
    kernel = StageKernel(
        fn=_double_fn, static=(), params=None,
        consumes=("obs_col",), produces=("obs_out",))
    run_kernel(kernel, Table({"obs_col": np.ones((4,), np.float32)}),
               op="_obs_gauge_op")
    m.publish()                            # counter moved -> refreshed
    assert gauge.value == kernel_stats.dispatches


def _double_fn(static, params, cols):
    return {"obs_out": cols["obs_col"] * 2.0}


# ---------------------------------------------------------------------------
# 3b. StepProbe
# ---------------------------------------------------------------------------

def test_probe_records_under_scan_and_fetches_once():
    import jax
    import jax.numpy as jnp

    probe = StepProbe.create(("loss", "grad_norm"), 4)

    @jax.jit
    def run(probe, xs):
        def step(p, x):
            return p.record(loss=x, grad_norm=x * 2), None

        p, _ = jax.lax.scan(step, probe, xs)
        return p

    out = run(probe, jnp.arange(3, dtype=jnp.float32))
    got = out.fetch()
    np.testing.assert_array_equal(got["loss"], [0.0, 1.0, 2.0])
    np.testing.assert_array_equal(got["grad_norm"], [0.0, 2.0, 4.0])
    fresh = out.reset().fetch()
    assert fresh["loss"].shape == (0,)


def test_probe_partial_channels_and_validation():
    probe = StepProbe.create(("a", "b"), 2)
    got = probe.record(a=1.0).fetch()
    assert got["a"][0] == 1.0 and math.isnan(got["b"][0])
    with pytest.raises(ValueError, match="unknown probe channel"):
        probe.record(c=1.0)
    with pytest.raises(ValueError, match="duplicate"):
        StepProbe.create(("a", "a"), 2)
    # past-capacity records drop instead of corrupting the buffer
    full = probe.record(a=1.0).record(a=2.0).record(a=3.0)
    np.testing.assert_array_equal(full.fetch()["a"], [1.0, 2.0])


def test_probe_rides_pytree_boundaries():
    import jax

    probe = StepProbe.create(("loss",), 3).record(loss=7.0)
    leaves, treedef = jax.tree_util.tree_flatten(probe)
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert rebuilt.names == ("loss",) and rebuilt.capacity == 3
    np.testing.assert_array_equal(rebuilt.fetch()["loss"], [7.0])


def test_chunked_fit_step_probe_bitexact_and_traced():
    """sgd_fit_outofcore(step_probe=True): the probe changes NOTHING
    about the result (bit-exact params + loss log vs probe-off on the
    same stream) and stream_info carries the full per-step loss series
    across chunk boundaries, padded tail excluded."""
    from flink_ml_tpu.models.common.losses import squared_loss
    from flink_ml_tpu.models.common.sgd import SGDConfig, sgd_fit_outofcore

    def mk():
        rng = np.random.default_rng(7)

        def make_reader():
            for _ in range(10):           # 10 batches, W=4 -> padded tail
                X = rng.normal(size=(16, 4)).astype(np.float32)
                yield {"features": X,
                       "label": (X @ np.arange(1, 5)).astype(np.float32)}

        return make_reader

    cfg = SGDConfig(max_epochs=2, tol=0.0)
    info: dict = {}
    s1, log1 = sgd_fit_outofcore(squared_loss, mk(), num_features=4,
                                 config=cfg, steps_per_dispatch=4,
                                 stream_info=info, step_probe=True)
    s2, log2 = sgd_fit_outofcore(squared_loss, mk(), num_features=4,
                                 config=cfg, steps_per_dispatch=4)
    assert s1.coefficients.tobytes() == s2.coefficients.tobytes()
    assert log1 == log2
    trace = info["step_trace"]["loss"]
    assert trace.shape == (20,)           # 10 steps x 2 epochs, no pad
    assert np.all(np.isfinite(trace))
    # the per-step series is consistent with the epoch aggregate
    assert np.mean(trace[:10]) == pytest.approx(log1[0], rel=1e-5)


def test_chunked_fit_probe_lowerings_do_not_scale_with_chunks():
    """The probe rides the ONE chunk-scan program and its per-chunk
    fetch/reset are transfers + cached tiny ops, not new programs: a
    warmed probed fit lowers the same count at 1 epoch and at 4 (12
    chunk dispatches) — zero per-chunk/per-epoch retraces with the
    probe attached."""
    from flink_ml_tpu.utils.backend import count_compiles

    from flink_ml_tpu.models.common.losses import squared_loss
    from flink_ml_tpu.models.common.sgd import SGDConfig, sgd_fit_outofcore

    def mk():
        rng = np.random.default_rng(5)

        def make_reader():
            for _ in range(8):
                X = rng.normal(size=(16, 4)).astype(np.float32)
                yield {"features": X,
                       "label": (X @ np.arange(1, 5)).astype(np.float32)}

        return make_reader

    def lowerings(epochs: int) -> int:
        cfg = SGDConfig(max_epochs=epochs, tol=0.0)
        with count_compiles() as count:
            sgd_fit_outofcore(squared_loss, mk(), num_features=4,
                              config=cfg, steps_per_dispatch=4,
                              cache_decoded=False, step_probe=True)
        return count()

    lowerings(1)                          # one-time compiles warm here
    assert lowerings(1) == lowerings(4)


def test_step_probe_refused_off_the_chunked_path():
    from flink_ml_tpu.models.common.losses import squared_loss
    from flink_ml_tpu.models.common.sgd import SGDConfig, sgd_fit_outofcore
    from flink_ml_tpu.parallel.mesh import default_mesh

    mesh = default_mesh()
    if int(np.prod(list(mesh.shape.values()))) == mesh.devices.size \
            and len(set(d.process_index for d in mesh.devices.flat)) == 1:
        pytest.skip("single-process mesh: the chunked path engages")
    with pytest.raises(ValueError, match="chunked single-process"):
        sgd_fit_outofcore(squared_loss, lambda: iter(()), num_features=4,
                          config=SGDConfig(max_epochs=1), mesh=mesh,
                          step_probe=True)


def test_fused_iterate_epoch_trace_still_reports():
    """The PR 9 epoch-trace surface survived the StepProbe port: fused
    workset iterations still surface trimmed active-fraction /
    termination curves."""
    import jax.numpy as jnp

    from flink_ml_tpu.iteration import (
        IterationBodyResult,
        IterationConfig,
        Workset,
        iterate,
    )

    def body(state, ws, epoch, data):
        new = state + ws.mask
        return IterationBodyResult(
            (new, Workset((new < data).astype(jnp.float32), ws.bounds)))

    res = iterate(body, jnp.zeros(4), jnp.asarray([1.0, 2.0, 3.0, 2.0]),
                  max_epochs=8, workset=Workset(jnp.ones(4, jnp.float32)),
                  config=IterationConfig(mode="fused"))
    trace = res.side["epoch_trace"]
    assert trace["active_fraction"].shape == (res.num_epochs,)
    assert np.all(np.isfinite(trace["active_fraction"]))
    assert res.num_epochs < 8             # drained before max_epochs


# ---------------------------------------------------------------------------
# 4. THE acceptance: end-to-end correlation + zero new lowerings
# ---------------------------------------------------------------------------

def _windows(start, stop, rows=16, d=4):
    for i in range(start, stop):
        rng = np.random.default_rng(1000 + i)
        X = rng.normal(size=(rows, d)).astype(np.float32)
        yield Table({"features": X,
                     "label": (X[:, 0] > 0).astype(np.float32)})


def test_trace_correlates_wal_cut_publish_and_request(tmp_path):
    """One enabled tracer, one correlation chain: WAL window N ->
    checkpoint cut T -> delta publish (step T, generation G) ->
    generation G served request R — all present and joinable in the
    exported Chrome trace."""
    from flink_ml_tpu.iteration.checkpoint import CheckpointConfig
    from flink_ml_tpu.models.classification.logisticregression import (
        LogisticRegression,
    )
    from flink_ml_tpu.models.common.losses import logistic_loss
    from flink_ml_tpu.online import ContinuousLearner
    from flink_ml_tpu.serving import serve_model

    windows = list(_windows(0, 8))
    boot = LogisticRegression().set_max_iter(1).fit(windows[0])
    endpoint = serve_model(boot, windows[0].drop("label").take(2),
                           max_batch_rows=32, max_wait_ms=0.5)
    tracer = trace_mod.tracer
    try:
        tracer.enable()
        learner = ContinuousLearner(
            loss_fn=logistic_loss, num_features=4,
            source=iter(windows), wal_dir=str(tmp_path / "wal"),
            endpoint=endpoint, batch_rows=16,
            checkpoint=CheckpointConfig(str(tmp_path / "ck")),
            publish_every_steps=4)
        learner.run(max_windows=8)
        out = endpoint.predict(windows[3].drop("label"))
        assert out.num_rows == 16
        tracer.disable()

        # -- the chain, link by link -----------------------------------
        wal = sorted(s.ids["window"] for s in tracer.find("wal_append"))
        assert wal == list(range(8))
        cuts = {s.ids["step"] for s in tracer.find("checkpoint_write")}
        assert {4, 8} <= cuts
        publishes = list(tracer.find("delta_publish"))
        pub_by_step = {s.ids["step"]: s for s in publishes}
        assert {4, 8} <= set(pub_by_step)
        # every publish's cut step has a checkpoint span (never serve
        # ahead of durable) and the WAL holds exactly the windows the
        # cut covers (one window = one step on the fixed grid)
        for step, span in pub_by_step.items():
            assert step in cuts
            assert {w for w in wal if w < step} == set(range(step))
            assert "generation" in span.ids
        live_gen = pub_by_step[8].ids["generation"]
        served = [s for s in tracer.find("request")
                  if s.ids.get("generation") == live_gen]
        assert served, "no request span on the published generation"
        assert all("request_id" in s.ids for s in served)
        # supporting spans of the request path showed up too
        assert any(tracer.find("queue_wait"))
        assert any(tracer.find("serve_batch"))
        assert any(tracer.find("train_chunk"))
        assert any(tracer.find("train_epoch"))

        # -- export round trip -----------------------------------------
        path = str(tmp_path / "trace.json")
        n = tracer.export_chrome(path)
        events = json.load(open(path))["traceEvents"]
        assert len(events) == n
        names = {e["name"] for e in events}
        assert {"wal_append", "checkpoint_write", "delta_publish",
                "request"} <= names
        pub_ev = [e for e in events if e["name"] == "delta_publish"
                  and e["args"].get("step") == 8]
        assert pub_ev and pub_ev[0]["args"]["generation"] == live_gen
    finally:
        tracer.disable()
        tracer.clear()
        endpoint.close()


def test_serving_with_tracing_adds_zero_lowerings():
    """Tracing is pure host bookkeeping: enabling it on a warmed
    endpoint compiles NOTHING (lowering-counter asserted) while the
    request-path spans all appear."""
    from flink_ml_tpu.utils.backend import count_compiles

    from flink_ml_tpu.models.classification.logisticregression import (
        LogisticRegression,
    )
    from flink_ml_tpu.serving import serve_model

    rng = np.random.default_rng(3)
    X = rng.normal(size=(48, 6)).astype(np.float32)
    train = Table({"features": X, "label": (X[:, 0] > 0).astype(np.float64)})
    model = LogisticRegression().set_max_iter(2).fit(train)
    feats = Table({"features": X})
    endpoint = serve_model(model, feats.take(2), max_batch_rows=64,
                           max_wait_ms=0.5)
    tracer = trace_mod.tracer
    try:
        endpoint.predict(feats.take(5))           # tracing off, warm
        tracer.enable()
        with count_compiles() as count:
            endpoint.predict(feats.take(5))
        assert count() == 0, (
            f"{count()} new lowerings with tracing enabled — the "
            "tracer leaked into a traced program")
        for name in ("queue_wait", "serve_batch", "request",
                     "registry_dispatch", "device_execute", "bucket_pad"):
            assert any(tracer.find(name)), f"missing span {name!r}"
    finally:
        tracer.disable()
        tracer.clear()
        endpoint.close()
