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
