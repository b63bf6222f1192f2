"""Iteration runtime tests.

Mirrors the reference ITCase matrix (SURVEY §4): bounded all-round
iteration with exact per-round sums, termination by criteria vs max-round,
per-round lifecycle, listener callbacks, and stream-end termination.
The 4x1000 exact-sum anchor comes from
``BoundedAllRoundStreamIterationITCase.java:96-101`` (sum = 1,998,000).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ml_tpu.iteration import (
    EpochContext,
    FnListener,
    IterationBodyResult,
    IterationConfig,
    IterationListener,
    OperatorLifeCycle,
    iterate,
)
from flink_ml_tpu.parallel import data_sharding, device_mesh, shard_batch


def test_simple_carried_state():
    # x_{e+1} = x_e + 1 for 5 epochs
    res = iterate(lambda x, e: x + 1, jnp.asarray(0.0), max_epochs=5)
    assert float(res.state) == 5.0
    assert res.num_epochs == 5


def test_reduce_sum_anchor():
    # The reference's 4 parallel sources x records 0..999, reduced per round:
    # every round must see the exact sum 1,998,000.
    records = np.concatenate([np.arange(1000)] * 4).astype(np.float64)
    data = jnp.asarray(records)

    def body(state, epoch, d):
        round_sum = jnp.sum(d)
        return IterationBodyResult(feedback=state + 1, outputs=round_sum)

    res = iterate(body, jnp.asarray(0, jnp.int32), data, max_epochs=5,
                  config=IterationConfig(mode="hosted"))
    assert res.num_epochs == 5
    assert [float(o) for o in res.outputs] == [1998000.0] * 5

    # fused mode gives identical per-round sums (scan-stacked)
    res_f = iterate(body, jnp.asarray(0, jnp.int32), data, max_epochs=5,
                    config=IterationConfig(mode="fused"))
    np.testing.assert_array_equal(np.asarray(res_f.outputs), [1998000.0] * 5)


def test_termination_criteria():
    # RoundBasedTerminationCriteria analog: continue while epoch < 3.
    def body(x, epoch):
        return IterationBodyResult(feedback=x * 2, outputs=x,
                                   termination=epoch < 3)

    res = iterate(body, jnp.asarray(1.0), max_epochs=100,
                  config=IterationConfig(mode="hosted"))
    # epochs 0,1,2 vote continue; epoch 3 votes stop -> 4 body invocations
    assert res.num_epochs == 4
    assert float(res.state) == 16.0
    assert res.side["termination_reason"] == "criteria"


def test_termination_criteria_fused_matches_hosted():
    def body(x, epoch):
        return IterationBodyResult(feedback=x * 2, outputs=x,
                                   termination=epoch < 3)

    hosted = iterate(body, jnp.asarray(1.0), max_epochs=100,
                     config=IterationConfig(mode="hosted"))
    # fused + outputs + criteria: the documented keeps-last-epoch warning
    # must fire (the IterationListener-era evidence, VERDICT row 18)
    with pytest.warns(UserWarning, match="LAST epoch's outputs"):
        fused = iterate(body, jnp.asarray(1.0), max_epochs=100,
                        config=IterationConfig(mode="fused"))
    assert float(fused.state) == float(hosted.state)
    assert fused.num_epochs == hosted.num_epochs


def test_zero_feedback_terminates_immediately():
    # Termination vote false on the first epoch: 1-round case
    # (BoundedAllRoundStreamIterationITCase.java:116-142 criteria-from-
    # constants analog).
    res = iterate(
        lambda x, e: IterationBodyResult(x, None, jnp.asarray(False)),
        jnp.asarray(7.0), max_epochs=10, config=IterationConfig(mode="hosted"))
    assert res.num_epochs == 1
    assert float(res.state) == 7.0


def test_listeners_fire_per_epoch():
    seen = []
    terminated = []

    class Recorder(IterationListener):
        def on_epoch_watermark_incremented(self, epoch, ctx):
            seen.append((epoch, float(ctx.state)))

        def on_iteration_terminated(self, ctx):
            terminated.append(ctx.epoch)

    res = iterate(lambda x, e: x + 1, jnp.asarray(0.0), max_epochs=3,
                  listeners=[Recorder()])
    assert seen == [(0, 1.0), (1, 2.0), (2, 3.0)]
    assert terminated == [3]
    assert res.num_epochs == 3


def test_fn_listener_side_outputs():
    def on_epoch(epoch, ctx: EpochContext):
        ctx.output("epochs", epoch)

    res = iterate(lambda x, e: x + 1, jnp.asarray(0.0), max_epochs=3,
                  listeners=[FnListener(on_epoch=on_epoch)])
    assert res.side["epochs"] == [0, 1, 2]


def test_per_round_lifecycle():
    # PER_ROUND: body-local state re-initialised every epoch (the analog of
    # per-round operator instances, BoundedPerRoundStreamIterationITCase).
    calls = []

    def body(state, epoch):
        calls.append(float(jax.device_get(state)))
        return IterationBodyResult(state + 10, outputs=None)

    res = iterate(body, jnp.asarray(0.0), max_epochs=3,
                  config=IterationConfig(lifecycle=OperatorLifeCycle.PER_ROUND,
                                         mode="hosted", jit=False))
    # every epoch starts from the re-initialised state 0
    assert calls == [0.0, 0.0, 0.0]
    assert float(res.state) == 10.0


def test_stream_end_terminates():
    # Iterator data source: epoch = one window; exhaustion ends the iteration
    # (the bounded end of iterateUnboundedStreams).
    batches = iter([jnp.ones(4), jnp.ones(4) * 2, jnp.ones(4) * 3])

    def body(acc, epoch, d):
        return IterationBodyResult(acc + jnp.sum(d), outputs=None)

    res = iterate(body, jnp.asarray(0.0), batches, max_epochs=100,
                  config=IterationConfig(mode="hosted"))
    assert res.num_epochs == 3
    assert float(res.state) == 4 + 8 + 12
    assert res.side["termination_reason"] == "stream_end"


def test_epoch_passed_as_device_scalar():
    # epoch enters the jitted step as a traced scalar -> one compilation
    compilations = []

    def body(x, e):
        compilations.append(1)  # traced once per compile
        return x + e

    res = iterate(body, jnp.asarray(0, jnp.int32), max_epochs=5,
                  config=IterationConfig(mode="hosted"))
    assert sum(compilations) == 1  # no per-epoch recompile
    assert int(res.state) == 0 + 1 + 2 + 3 + 4


def test_sharded_state_iteration():
    # SPMD epoch step over an 8-device mesh: data batch-sharded, state
    # replicated; aggregation = jnp.sum (XLA inserts the psum over ICI).
    mesh = device_mesh()
    data = shard_batch(np.arange(64, dtype=np.float32), mesh)
    assert len(data.sharding.device_set) == 8

    def body(w, epoch, d):
        return IterationBodyResult(w + jnp.sum(d), outputs=None)

    res = iterate(body, jnp.asarray(0.0, jnp.float32), data, max_epochs=4)
    assert float(res.state) == 4 * np.arange(64).sum()


def test_invalid_mode_rejected():
    with pytest.raises(ValueError):
        IterationConfig(mode="warp")


def test_fused_requires_static_data():
    with pytest.raises(ValueError):
        iterate(lambda x, e, d: x, jnp.asarray(0.0), iter([1, 2]),
                max_epochs=2, config=IterationConfig(mode="fused"))


def test_donation_preserves_caller_state():
    # Donation must consume a private copy — the caller's initial_state
    # buffers stay alive and reusable across multiple iterate() calls.
    init = jnp.arange(4, dtype=jnp.float32)
    r1 = iterate(lambda x, e: x + 1, init, max_epochs=3,
                 config=IterationConfig(mode="hosted"))
    r2 = iterate(lambda x, e: x + 1, init, max_epochs=3,
                 config=IterationConfig(mode="fused"))
    np.testing.assert_array_equal(np.asarray(init), [0, 1, 2, 3])
    np.testing.assert_array_equal(np.asarray(r1.state), np.asarray(r2.state))


@pytest.mark.parametrize("mode", ["fused", "hosted"])
def test_handed_over_state_is_donated_as_it_is(mode):
    # HandedOver: the caller gives its buffers up, so the loop makes no
    # private copy; on the CPU a donation is not carried out, so what can
    # be shown here is that the loop works on the caller's own arrays
    # (a copy would be another object) and gives the same result.
    from flink_ml_tpu.iteration import HandedOver
    from flink_ml_tpu.iteration import core

    copied = []
    sound_copy = core._private_copy

    def spy(state):
        copied.append(state)
        return sound_copy(state)

    core._private_copy = spy
    try:
        kept = iterate(lambda x, e: x + 1, jnp.arange(4, dtype=jnp.float32),
                       max_epochs=3, config=IterationConfig(mode=mode))
        assert len(copied) == 1
        given = iterate(lambda x, e: x + 1,
                        HandedOver(jnp.arange(4, dtype=jnp.float32)),
                        max_epochs=3, config=IterationConfig(mode=mode))
        assert len(copied) == 1
    finally:
        core._private_copy = sound_copy
    np.testing.assert_array_equal(np.asarray(given.state),
                                  np.asarray(kept.state))
    assert given.num_epochs == kept.num_epochs == 3


def test_auto_mode_with_criteria_keeps_all_outputs():
    # auto must not pick fused (last-output-only) when a vote exists
    def body(x, epoch):
        return IterationBodyResult(x + 1, outputs=x, termination=epoch < 3)

    res = iterate(body, jnp.asarray(0.0), max_epochs=10)
    assert len(res.outputs) == 4  # full per-epoch log, not just the last


def test_tuple_state_never_unpacked():
    # A bare tuple return is the state itself, not (feedback, outputs)
    res = iterate(lambda s, e: (s[0] + 1, s[1] * 2),
                  (jnp.asarray(0.0), jnp.asarray(1.0)), max_epochs=3,
                  config=IterationConfig(mode="hosted"))
    assert float(res.state[0]) == 3.0
    assert float(res.state[1]) == 8.0


def test_mixed_replayed_and_per_epoch_inputs():
    # ReplayableDataStreamList analog: replayed device data + a live stream,
    # mixed in one dict (SURVEY §2.2).
    from flink_ml_tpu.iteration import PerEpoch, Replayed

    replayed = jnp.arange(8, dtype=jnp.float32)   # same every epoch
    stream = iter([jnp.asarray(1.0), jnp.asarray(2.0), jnp.asarray(3.0)])

    seen = []

    def body(acc, epoch, data):
        seen.append((float(jnp.sum(data["train"])), float(data["delta"])))
        return IterationBodyResult(acc + jnp.sum(data["train"]) * data["delta"])

    res = iterate(body, jnp.asarray(0.0),
                  {"train": Replayed(replayed), "delta": PerEpoch(stream)},
                  max_epochs=100, config=IterationConfig(mode="hosted",
                                                         jit=False))
    assert res.num_epochs == 3
    assert res.side["termination_reason"] == "stream_end"
    assert seen == [(28.0, 1.0), (28.0, 2.0), (28.0, 3.0)]
    assert float(res.state) == 28.0 * 6


def test_per_epoch_callable_marker():
    from flink_ml_tpu.iteration import PerEpoch

    res = iterate(
        lambda acc, e, d: IterationBodyResult(acc + d["x"]),
        jnp.asarray(0.0),
        {"x": PerEpoch(lambda epoch: jnp.asarray(float(epoch)))},
        max_epochs=4, config=IterationConfig(mode="hosted"))
    assert float(res.state) == 0 + 1 + 2 + 3


def test_replayed_marker_is_fusible():
    from flink_ml_tpu.iteration import Replayed

    data = {"x": Replayed(jnp.arange(4, dtype=jnp.float32))}
    res = iterate(lambda s, e, d: IterationBodyResult(s + jnp.sum(d["x"])),
                  jnp.asarray(0.0), data, max_epochs=3,
                  config=IterationConfig(mode="fused"))
    assert float(res.state) == 18.0


# ----------------------------------------------- mixed lifecycle (forEachRound)


def test_mixed_lifecycle_per_round_subtree():
    """Part of the state is per-round (re-initialised each epoch), part is
    carried — the ``IterationBody.forEachRound`` analog, semantics mirroring
    ``BoundedMixedLifeCycleStreamIterationITCase.java``: an all-round
    running reduce feeds a per-round accumulator that must start fresh every
    round."""
    data = jnp.arange(4.0)

    def body(state, epoch, d):
        # per-round scratch starts at 0 every epoch; if it carried, round_sum
        # would accumulate across rounds and the asserts below would fail
        round_sum = state["scratch"] + jnp.sum(d) + state["carried"]
        return IterationBodyResult(
            {"carried": state["carried"] + 1.0, "scratch": round_sum},
            outputs=round_sum)

    init = {"carried": jnp.asarray(0.0), "scratch": jnp.asarray(0.0)}
    result = iterate(body, init, data, max_epochs=4, per_round=("scratch",),
                     config=IterationConfig(mode="hosted"))
    # round e: scratch re-enters at 0, carried enters at e -> output 6 + e
    assert [float(o) for o in result.outputs] == [6.0, 7.0, 8.0, 9.0]
    assert float(result.state["carried"]) == 4.0
    # final state keeps the LAST round's per-round value (forEachRound output)
    assert float(result.state["scratch"]) == 9.0


def test_mixed_lifecycle_fused_matches_hosted():
    data = jnp.arange(3.0)

    def body(state, epoch, d):
        s = state["tmp"] + jnp.sum(d)
        return IterationBodyResult({"acc": state["acc"] + s, "tmp": s})

    init = {"acc": jnp.asarray(0.0), "tmp": jnp.asarray(0.0)}
    hosted = iterate(body, init, data, max_epochs=5, per_round=("tmp",),
                     config=IterationConfig(mode="hosted"))
    fused = iterate(body, init, data, max_epochs=5, per_round=("tmp",),
                    config=IterationConfig(mode="fused"))
    assert float(hosted.state["acc"]) == float(fused.state["acc"]) == 15.0
    assert float(fused.state["tmp"]) == 3.0


def test_mixed_lifecycle_validates_keys():
    with pytest.raises(KeyError, match="nope"):
        iterate(lambda s, e: s, {"a": jnp.asarray(0.0)}, max_epochs=1,
                per_round=("nope",))
    with pytest.raises(TypeError, match="dict"):
        iterate(lambda s, e: s, jnp.asarray(0.0), max_epochs=1,
                per_round=("a",))


# -- workset iterations (ISSUE 9) --------------------------------------------

def _counter_workset_body(state, ws, epoch, data):
    """Toy workset: per-element counters run up to per-element targets;
    an element leaves the workset once its target is reached."""
    from flink_ml_tpu.iteration import Workset

    new = state + ws.mask
    return IterationBodyResult(
        (new, Workset((new < data).astype(jnp.float32), ws.bounds)))


def test_workset_drains_and_exits_before_max_epochs():
    from flink_ml_tpu.iteration import Workset

    targets = jnp.asarray([2.0, 5.0, 3.0, 7.0])
    ws0 = Workset(jnp.ones(4, jnp.float32), {"aux": jnp.zeros(4)})
    res = iterate(_counter_workset_body, jnp.zeros(4), targets,
                  max_epochs=50, workset=ws0)
    np.testing.assert_array_equal(np.asarray(res.state), [2, 5, 3, 7])
    assert res.num_epochs == 7 < 50          # convergence-driven exit
    assert np.all(np.asarray(res.workset.mask) == 0)
    # bounds pytree rides untouched
    np.testing.assert_array_equal(np.asarray(res.workset.bounds["aux"]),
                                  np.zeros(4))


def test_workset_fused_matches_hosted_including_trace():
    from flink_ml_tpu.iteration import Workset

    targets = jnp.asarray([2.0, 5.0, 3.0, 7.0])
    ws0 = Workset(jnp.ones(4, jnp.float32))
    fused = iterate(_counter_workset_body, jnp.zeros(4), targets,
                    max_epochs=50, workset=ws0,
                    config=IterationConfig(mode="fused"))
    hosted = iterate(_counter_workset_body, jnp.zeros(4), targets,
                     max_epochs=50, workset=ws0,
                     config=IterationConfig(mode="hosted"))
    np.testing.assert_array_equal(np.asarray(fused.state),
                                  np.asarray(hosted.state))
    assert fused.num_epochs == hosted.num_epochs
    for key in ("active_fraction", "termination"):
        np.testing.assert_allclose(fused.side["epoch_trace"][key],
                                   hosted.side["epoch_trace"][key])


def test_workset_epoch_trace_records_decay_curve():
    from flink_ml_tpu.iteration import Workset

    targets = jnp.asarray([1.0, 2.0, 3.0, 4.0])
    res = iterate(_counter_workset_body, jnp.zeros(4), targets,
                  max_epochs=32, workset=Workset(jnp.ones(4, jnp.float32)))
    trace = res.side["epoch_trace"]
    # one entry per epoch actually run; the NaN prefill never leaks out
    assert trace["active_fraction"].shape == (res.num_epochs,)
    assert not np.any(np.isnan(trace["active_fraction"]))
    np.testing.assert_allclose(trace["active_fraction"],
                               [0.75, 0.5, 0.25, 0.0])
    # the final epoch votes stop (fraction hit zero)
    assert trace["termination"][-1] == 0.0
    assert np.all(trace["termination"][:-1] == 1.0)


def test_criteria_while_loop_emits_termination_trace_without_workset():
    # ISSUE 9 satellite: convergence curves survive the fused while_loop
    # even for plain criteria-driven bodies — active_fraction is NaN
    # (no workset), termination carries the per-epoch vote.
    def body(x, epoch):
        return IterationBodyResult(feedback=x * 2, termination=epoch < 3)

    res = iterate(body, jnp.asarray(1.0), max_epochs=100,
                  config=IterationConfig(mode="fused"))
    trace = res.side["epoch_trace"]
    assert res.num_epochs == 4
    assert np.all(np.isnan(trace["active_fraction"]))
    np.testing.assert_array_equal(trace["termination"], [1, 1, 1, 0])


def test_workset_body_vote_ands_with_active_fraction():
    from flink_ml_tpu.iteration import Workset

    # elements never drain, but the body votes stop at epoch 3
    def body(state, ws, epoch, data):
        return IterationBodyResult((state + 1, ws), termination=epoch < 3)

    res = iterate(body, jnp.zeros(4), jnp.ones(4), max_epochs=50,
                  workset=Workset(jnp.ones(4, jnp.float32)))
    assert res.num_epochs == 4
    assert float(np.asarray(res.workset.mask).sum()) == 4.0


def test_workset_tol_exits_at_nonzero_fraction():
    from flink_ml_tpu.iteration import Workset

    targets = jnp.asarray([2.0, 5.0, 3.0, 20.0])
    res = iterate(_counter_workset_body, jnp.zeros(4), targets,
                  max_epochs=50, workset=Workset(jnp.ones(4, jnp.float32)),
                  workset_tol=0.3)   # exit once <= 30% remain active
    # after epoch 5 only the target-20 element is active (25% <= 30%)
    assert res.num_epochs == 5
    assert float(np.asarray(res.workset.mask).sum()) == 1.0


def test_workset_rejects_per_round_and_wrong_type():
    from flink_ml_tpu.iteration import Workset

    with pytest.raises(TypeError, match="Workset"):
        iterate(_counter_workset_body, jnp.zeros(2), jnp.ones(2),
                max_epochs=3, workset=jnp.ones(2))
    with pytest.raises(ValueError, match="per-round"):
        iterate(_counter_workset_body, {"a": jnp.zeros(2)}, jnp.ones(2),
                max_epochs=3, workset=Workset(jnp.ones(2, jnp.float32)),
                per_round=["a"])


def test_workset_active_fraction_spans_mask_pytree():
    from flink_ml_tpu.iteration import Workset, active_fraction

    ws = Workset({"users": jnp.asarray([1.0, 0.0, 1.0]),
                  "items": jnp.asarray([0.0])})
    assert float(active_fraction(ws)) == 0.5


def test_the_fused_dispatch_runs_in_one_chunk_of_the_interpreters_frames():
    """CPython keeps a thread's frames in 16 KiB chunks and unmaps a chunk
    when its first frame returns: a loop of calls that straddles a chunk's
    end pays a map and an unmap a call.  ``core._in_one_chunk`` gives what
    it calls a chunk of its own, so no depth of the caller puts such an
    end inside the dispatch's tracing and lowering."""
    import time

    from flink_ml_tpu.iteration import core

    assert core._in_one_chunk.__code__.co_stacksize >= 1 << 16
    assert core._in_one_chunk(lambda a, b: (a, b), 1, 2) == (1, 2)

    def callee(a, b, c):
        return a

    def loop(n=20000):
        t = time.perf_counter()
        for _ in range(n):
            callee(1, 2, 3)
        return time.perf_counter() - t

    def at_depth(k, fn):
        return fn() if k == 0 else at_depth(k - 1, fn)

    bare = [at_depth(k, loop) for k in range(260)]
    typical = sorted(bare)[len(bare) // 2]
    worst = max(range(len(bare)), key=bare.__getitem__)
    if bare[worst] < 20 * typical:
        pytest.skip("this interpreter frees no frame chunk under a loop")
    # at the caller's depth where the loop straddled a chunk's end, and
    # a frame either side of it, the loop inside the chunk runs as ever
    for k in (max(worst - 1, 0), worst, worst + 1):
        inside = min(at_depth(k, lambda: core._in_one_chunk(loop))
                     for _ in range(3))
        assert inside < 5 * typical, (k, inside, typical, bare[worst])


# ---------------------------------------------------------------------------
# a fused program is built once a process and program key
# ---------------------------------------------------------------------------

def _keyed_loop(votes=False, scale=0.5, keyed=True):
    """``(make, calls)``: ``make()`` is a fresh body each call, as an
    estimator's factory gives one a ``fit``, stating the program key
    ``(_keyed_loop, votes, scale)`` if ``keyed``; ``calls`` counts its
    Python traces.  With ``votes`` it has a criterion (the ``while_loop``
    branch) and emits nothing, else an output an epoch (``lax.scan``)."""
    from flink_ml_tpu.iteration import with_program_key

    calls = []

    def make():
        def body(state, epoch, data):
            calls.append(epoch)
            new = jnp.tanh(state @ data) * scale + state * 0.25
            if votes:
                return IterationBodyResult(
                    new, termination=jnp.abs(new - state).max() > 1e-3)
            return IterationBodyResult(new, outputs=jnp.sum(new * new))

        return (with_program_key(body, _keyed_loop, votes, scale)
                if keyed else body)

    return make, calls


def _loop_arrays(width=6, dtype=jnp.float32):
    rng = np.random.default_rng(3)
    return (jnp.asarray(rng.normal(size=(4, width)), dtype),
            jnp.asarray(rng.normal(size=(width, width)), dtype))


def _fused(body, state, data, max_epochs=5, **config):
    return iterate(body, state, data, max_epochs=max_epochs,
                   config=IterationConfig(mode="fused", **config))


def _bits(result):
    return [np.asarray(x).tobytes() for x in jax.tree_util.tree_leaves(
        (result.state, result.outputs, result.num_epochs))]


@pytest.mark.parametrize("votes", [False, True],
                         ids=["scan", "while_loop"])
def test_a_keyed_body_is_traced_in_its_first_dispatch_alone(votes):
    from flink_ml_tpu.iteration import clear_programs, core

    make, calls = _keyed_loop(votes)
    state, data = _loop_arrays()
    first = _fused(make(), state, data, max_epochs=9)
    traces = len(calls)
    assert traces == 2                    # the probe's and the trace's
    again = [_fused(make(), state, data, max_epochs=9) for _ in range(2)]
    assert len(calls) == traces and len(core._programs) == 1
    clear_programs()
    assert len(core._programs) == 0
    rebuilt = _fused(make(), state, data, max_epochs=9)
    assert len(calls) == 2 * traces
    for other in again + [rebuilt]:
        assert _bits(other) == _bits(first)
    if votes:
        assert 1 < first.num_epochs <= 9
        for other in again + [rebuilt]:
            for name, curve in first.side["epoch_trace"].items():
                assert other.side["epoch_trace"][name].tobytes() == \
                    curve.tobytes()
    else:
        assert np.asarray(first.outputs).shape == (9,)


def _on(device):
    return lambda x: jax.device_put(x, jax.devices()[device])


@pytest.mark.parametrize("what", [
    "max_epochs", "shape", "dtype", "sharding", "committed", "donate_state",
    "key_scalar", "data_structure", "matmul_precision"])
def test_what_a_program_follows_from_builds_a_new_one_when_it_changes(what):
    """Each part of the full key, changed alone: another program is
    built (the body is traced again, a second entry is kept) and the
    first stays what the first dispatch's arguments get."""
    import contextlib

    from flink_ml_tpu.iteration import core

    make, calls = _keyed_loop()
    state, data = _loop_arrays()
    first = _fused(make(), state, data)
    traces = len(calls)

    other_make, other_state, other_data, how = make, state, data, {}
    context = contextlib.nullcontext()
    if what == "max_epochs":
        how = {"max_epochs": 6}
    elif what == "shape":
        other_state, other_data = _loop_arrays(width=7)
    elif what == "dtype":
        other_state, other_data = _loop_arrays(dtype=jnp.bfloat16)
    elif what == "sharding":
        other_state, other_data = _on(1)(state), _on(1)(data)
    elif what == "committed":
        assert not data.committed
        other_state, other_data = _on(0)(state), _on(0)(data)
        assert other_data.committed and other_data.sharding == data.sharding
    elif what == "donate_state":
        how = {"donate_state": False}
    elif what == "key_scalar":
        other_make, other_calls = _keyed_loop(scale=0.75)
    elif what == "data_structure":
        class Pair(tuple):
            pass

        jax.tree_util.register_pytree_node(
            Pair, lambda p: (tuple(p), None), lambda _, xs: Pair(xs))
        inner = make

        def other_make():
            body = inner()
            wrapped = lambda s, e, d: body(s, e, d[0])  # noqa: E731
            wrapped.program_key = body.program_key + ("first of a pair",)
            return wrapped

        other_data = Pair((data, data))
    else:
        context = jax.default_matmul_precision("highest")

    with context:
        second = _fused(other_make(), other_state, other_data, **how)
    assert len(core._programs) == 2
    if what == "key_scalar":
        assert len(other_calls) == 2
    else:
        assert len(calls) == 2 * traces
    # both stay: each argument list is served its own program again
    assert _bits(_fused(make(), state, data)) == _bits(first)
    with context:
        assert _bits(_fused(other_make(), other_state, other_data,
                            **how)) == _bits(second)
    assert len(core._programs) == 2
    if what in ("sharding", "committed", "donate_state", "data_structure"):
        assert _bits(second) == _bits(first)
    if what == "sharding":
        assert second.state.sharding != first.state.sharding


def test_an_unkeyed_closure_is_never_reused_and_leaves_no_entry():
    from flink_ml_tpu.iteration import core

    make, calls = _keyed_loop(keyed=False)
    state, data = _loop_arrays()
    results = [_fused(make(), state, data) for _ in range(3)]
    assert len(calls) == 3 * 2 and len(core._programs) == 0
    assert _bits(results[1]) == _bits(results[0]) == _bits(results[2])
    # nor are the wrappers iterate makes itself, around a keyed body
    keyed, calls = _keyed_loop()
    carried = {"x": state, "scratch": jnp.zeros(3)}

    def per_round_body(s, e, d):
        return {**s, "x": keyed()(s["x"], e, d).feedback}

    per_round_body.program_key = ("stated, but wrapped",)
    for _ in range(2):
        iterate(per_round_body, carried, data, max_epochs=3,
                per_round=["scratch"], config=IterationConfig(mode="fused"))
    assert len(core._programs) == 0


def test_a_program_key_holds_no_array():
    from flink_ml_tpu.iteration import with_program_key

    def body(s, e):
        return s

    for array in (np.zeros(3), jnp.zeros(3)):
        with pytest.raises(TypeError):
            with_program_key(body, "factory", array)
    assert not hasattr(body, "program_key")
    assert with_program_key(body, "factory", 3, 0.5).program_key == (
        "factory", 3, 0.5)


@pytest.mark.parametrize("votes", [False, True],
                         ids=["scan", "while_loop"])
def test_a_reuse_consumes_a_handed_over_state_and_spares_a_plain_one(votes):
    from flink_ml_tpu.iteration import HandedOver, core

    make, calls = _keyed_loop(votes)
    results = []
    for dispatch in ("build", "reuse", "reuse"):
        state, data = _loop_arrays()
        results.append(_fused(make(), state, data))
        assert not state.is_deleted() and not data.is_deleted()
        np.asarray(state)
        given, data = _loop_arrays()
        results.append(_fused(make(), HandedOver(given), data))
        assert given.is_deleted() and not data.is_deleted()
    # one program serves both: handing over is the caller's, not the
    # program's
    assert len(calls) == 2 and len(core._programs) == 1
    assert all(_bits(r) == _bits(results[0]) for r in results)


def test_no_entry_keeps_a_fits_arrays_alive():
    import gc
    import weakref

    from flink_ml_tpu.iteration import core

    class Plan:
        """What a factory's closure may hold beside the key's facts."""

        def __init__(self):
            self.index = np.arange(1 << 16)

    def fit():
        plan = Plan()
        make, _ = _keyed_loop()
        keyed = make()

        def body(state, epoch, data):
            assert plan.index.shape == (1 << 16,)
            return keyed(state, epoch, data)

        body.program_key = keyed.program_key
        state, data = _loop_arrays()
        result = _fused(body, state, data)
        refs = [weakref.ref(x) for x in
                (plan, body, state, data, result.state, result.outputs)]
        # bytes: on the CPU ``np.asarray`` is a view that holds the array
        return np.asarray(result.state).tobytes(), refs

    first, refs = fit()
    again, refs_again = fit()
    assert len(core._programs) == 1 and first == again
    gc.collect()
    assert [r() for r in refs + refs_again] == [None] * 12


def test_the_kept_programs_are_bounded_least_recently_used_out():
    from flink_ml_tpu.iteration import core

    state, data = _loop_arrays()
    bound = core._PROGRAMS_KEPT
    loops = [_keyed_loop(scale=0.1 + 0.01 * i) for i in range(bound + 1)]
    for make, _ in loops[:bound]:
        _fused(make(), state, data)
    assert len(core._programs) == bound
    _fused(loops[0][0](), state, data)           # the oldest, used again
    _fused(loops[bound][0](), state, data)       # one more than the bound
    assert len(core._programs) == bound
    _fused(loops[0][0](), state, data)           # still kept
    assert len(loops[0][1]) == 2
    _fused(loops[1][0](), state, data)           # the least recent went
    assert len(loops[1][1]) == 4
    assert len(core._programs) == bound


def test_threads_dispatching_shared_keys_all_get_the_answer():
    """More threads than the bound has room for keys, each dispatching
    every key, the interpreter switching every few bytecodes: a duplicate
    build is harmless, a torn or lost entry would show as a wrong answer,
    an error or more programs kept than the bound."""
    import sys
    import threading

    from flink_ml_tpu.iteration import core

    state, data = _loop_arrays()
    scales = [0.1 + 0.01 * i for i in range(core._PROGRAMS_KEPT + 2)]
    loops = [_keyed_loop(scale=scale) for scale in scales]
    want = [_bits(_fused(_keyed_loop(scale=scale, keyed=False)[0](),
                         state, data)) for scale in scales]
    n_threads = len(scales)
    barrier = threading.Barrier(n_threads)
    wrong, errors = [], []

    def client(first):
        try:
            barrier.wait(timeout=60)
            for turn in range(len(scales)):
                i = (first + turn) % len(scales)
                if _bits(_fused(loops[i][0](), state, data)) != want[i]:
                    wrong.append(i)
                if len(core._programs) > core._PROGRAMS_KEPT:
                    wrong.append("bound")
        except BaseException as e:      # noqa: BLE001 - reported below
            errors.append(e)

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(n_threads)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors and not wrong
    assert len(core._programs) == core._PROGRAMS_KEPT
