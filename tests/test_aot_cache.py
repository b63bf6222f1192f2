"""AOT executable cache + registry autotuning (ISSUE 12).

Covers the acceptance contract end to end:

- cached-executable outputs bit-identical to a fresh compile of the
  same key (in-process A/B and cross-process via subprocess)
- a second process warming from a populated cache performs ZERO XLA
  lowerings for cached keys (lowering-counter asserted in a subprocess)
- the corruption sweep: truncated payload / flipped payload byte /
  stale-fingerprint meta / missing manifest all quarantine and fall
  back to a live compile — never a crash, never wrong bits — with the
  event accounted in ``kernel_stats``
- autotune winners are measured, persisted, and reloaded by a later
  process (fresh cache instance) without re-search; ``registry.lookup``
  honors a recorded backend decision
- serving warm-up reports readiness wall + per-bucket source, and the
  deploy path logs the one-line summary
"""

import json
import logging
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

import jax.numpy as jnp

from flink_ml_tpu import Table
from flink_ml_tpu.kernels import aot, autotune
from flink_ml_tpu.kernels import registry as kreg
from flink_ml_tpu.kernels.registry import kernel_stats

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))


@pytest.fixture
def cache(tmp_path):
    c = aot.ExecutableCache(str(tmp_path / "aotcache"))
    aot.set_cache(c)
    try:
        yield c
    finally:
        aot.set_cache(None)


def _lr_plan(d=6, rows=16, seed=3):
    from flink_ml_tpu.models.common.linear import _linear_chain_kernel

    rng = np.random.default_rng(seed)
    plan = ((_linear_chain_kernel, ("f", "m")),)
    params = ({"w": jnp.asarray(rng.normal(size=(d,)).astype(np.float32)),
               "b": np.float32(0.25)},)
    cols = {"f": rng.normal(size=(rows, d)).astype(np.float32)}
    return plan, params, cols


def _dispatch(plan, params, cols):
    return np.asarray(kreg.dispatch(plan, params, dict(cols), op="aot_t")["m"])


# ---------------------------------------------------------------------------
# bit-exactness + cache-source accounting
# ---------------------------------------------------------------------------

def test_aot_roundtrip_bit_identical_and_accounted(cache):
    plan, params, cols = _lr_plan()
    snap0 = kernel_stats.snapshot()["aot"]

    out_compile = _dispatch(plan, params, cols)      # miss: compile + store
    snap1 = kernel_stats.snapshot()["aot"]
    assert snap1["misses"] == snap0["misses"] + 1
    assert snap1["stores"] == snap0["stores"] + 1
    assert snap1["compile_ms"] > snap0["compile_ms"]

    # a fresh cache instance over the same root = a restarted process:
    # the executable must come back from disk, not a compile
    aot.set_cache(aot.ExecutableCache(cache.root))
    out_loaded = _dispatch(plan, params, cols)
    snap2 = kernel_stats.snapshot()["aot"]
    assert snap2["hits"] == snap1["hits"] + 1
    assert snap2["misses"] == snap1["misses"]
    assert snap2["load_ms"] > snap1["load_ms"]

    # and the plain-jit path (cache disabled) agrees bit for bit
    aot.set_cache(None)
    out_jit = _dispatch(plan, params, cols)
    assert np.array_equal(out_compile, out_loaded)
    assert np.array_equal(out_compile, out_jit)

    # per-op ledger carries the split the satellite asks for
    rec = kernel_stats.snapshot()["per_op"]["aot_t"]
    assert rec["aot_hits"] >= 1 and rec["aot_misses"] >= 1
    assert rec["compile_ms"] > 0 and rec["aot_load_ms"] > 0


def test_memory_memo_skips_disk_after_first_load(cache):
    plan, params, cols = _lr_plan(seed=4)
    _dispatch(plan, params, cols)
    snap1 = kernel_stats.snapshot()["aot"]
    _dispatch(plan, params, cols)                    # steady state
    snap2 = kernel_stats.snapshot()["aot"]
    assert (snap2["hits"], snap2["misses"]) == (snap1["hits"],
                                                snap1["misses"])


# ---------------------------------------------------------------------------
# corruption sweep: quarantine + transparent recompile, never a crash
# ---------------------------------------------------------------------------

def _entry_dirs(cache):
    root = os.path.join(cache.root, "exec")
    return [os.path.join(root, n) for n in sorted(os.listdir(root))
            if ".corrupt" not in n and ".tmp." not in n]


def _corrupt_truncate(entry):
    path = os.path.join(entry, "executable.bin")
    with open(path, "r+b") as f:
        f.truncate(os.path.getsize(path) // 2)


def _corrupt_flip(entry):
    path = os.path.join(entry, "executable.bin")
    with open(path, "r+b") as f:
        f.seek(os.path.getsize(path) // 2)
        byte = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([byte[0] ^ 0xFF]))


def _corrupt_stale_fingerprint(entry):
    # a version-SKEWED entry whose CRCs are perfectly valid: meta claims
    # another jaxlib, manifest + marker re-committed over the edit
    from flink_ml_tpu.robustness.durability import (write_commit_marker,
                                                    write_manifest)

    meta_path = os.path.join(entry, "meta.json")
    with open(meta_path) as f:
        meta = json.load(f)
    meta["fingerprint"]["jaxlib"] = "0.0.0-stale"
    with open(meta_path, "w") as f:       # graftlint: disable=atomic-writes
        json.dump(meta, f)                # — test helper forging damage
    write_manifest(entry)
    write_commit_marker(entry)


def _corrupt_drop_manifest(entry):
    os.remove(os.path.join(entry, "manifest.json"))


@pytest.mark.parametrize("damage", [
    _corrupt_truncate, _corrupt_flip, _corrupt_stale_fingerprint,
    _corrupt_drop_manifest,
], ids=["truncated", "flipped-byte", "stale-jaxlib", "missing-manifest"])
def test_corruption_quarantines_and_recompiles(cache, damage):
    plan, params, cols = _lr_plan(seed=5)
    reference = _dispatch(plan, params, cols)
    entries = _entry_dirs(cache)
    assert len(entries) == 1
    damage(entries[0])

    aot.set_cache(aot.ExecutableCache(cache.root))   # restarted process
    before = kernel_stats.snapshot()["aot"]
    out = _dispatch(plan, params, cols)              # must NOT raise
    after = kernel_stats.snapshot()["aot"]

    assert np.array_equal(out, reference)            # never wrong bits
    assert after["quarantined"] == before["quarantined"] + 1
    assert after["misses"] == before["misses"] + 1   # transparent recompile
    corrupt = [n for n in os.listdir(os.path.join(cache.root, "exec"))
               if ".corrupt" in n]
    assert len(corrupt) == 1
    # the recompile re-stored a valid entry: the NEXT restart loads it
    aot.set_cache(aot.ExecutableCache(cache.root))
    assert np.array_equal(_dispatch(plan, params, cols), reference)
    assert kernel_stats.snapshot()["aot"]["hits"] == after["hits"] + 1


def test_uncommitted_tmp_entry_is_invisible(cache):
    """A crash mid-store (tmp dir never renamed) must read as a plain
    miss — the commit point is the os.replace, so no quarantine and no
    crash."""
    plan, params, cols = _lr_plan(seed=6)
    reference = _dispatch(plan, params, cols)
    entry = _entry_dirs(cache)[0]
    os.rename(entry, entry + ".tmp.999")             # un-commit it
    aot.set_cache(aot.ExecutableCache(cache.root))
    before = kernel_stats.snapshot()["aot"]
    assert np.array_equal(_dispatch(plan, params, cols), reference)
    after = kernel_stats.snapshot()["aot"]
    assert after["quarantined"] == before["quarantined"]
    assert after["misses"] == before["misses"] + 1


def test_store_failure_degrades_to_in_process_serving(cache, monkeypatch):
    """A broken cache VOLUME (ENOSPC, permissions) must never take down
    dispatch: the freshly-compiled executable serves in-process and the
    failure is accounted, not raised."""
    from flink_ml_tpu.robustness import durability

    def broken_commit(dirpath, **kw):
        raise OSError(28, "No space left on device")

    monkeypatch.setattr(durability, "commit_dir", broken_commit)
    plan, params, cols = _lr_plan(seed=8)
    before = kernel_stats.snapshot()["aot"]
    out = _dispatch(plan, params, cols)              # must NOT raise
    after = kernel_stats.snapshot()["aot"]
    assert out.shape == (16,)
    assert after["store_failed"] == before["store_failed"] + 1
    assert after["stores"] == before["stores"]
    # steady state keeps serving from the in-process copy
    assert np.array_equal(_dispatch(plan, params, cols), out)


def test_foreign_device_decision_is_skipped_not_quarantined(cache):
    """A valid decision recorded by another backend/chip sharing the
    fleet cache root is not ours to use — and not ours to destroy."""
    cache.record_decision({
        "format": 1, "op": "aot_foreign_op", "sig": "()",
        "kind": "backend", "choice": "x", "timings_ms": {},
        "search_ms": 1.0, "probe": "",
        "device": {"backend": "notthisbackend", "device_kind": "mythical"},
    })
    aot.set_cache(aot.ExecutableCache(cache.root))   # fresh scan
    assert autotune.get_decision("aot_foreign_op", ()) is None
    tune_root = os.path.join(cache.root, "autotune")
    assert not any(".corrupt" in n for n in os.listdir(tune_root))
    assert len(os.listdir(tune_root)) == 1           # entry survived


def test_code_fingerprint_is_transitive_over_helpers():
    """Editing a helper a kernel reaches by global name (directly or
    through a dispatch-table dict) must change the kernel's fingerprint
    — a restarted process must never load an executable built from the
    old helper."""
    from flink_ml_tpu.kernels.aot import _code_fingerprint

    src = "def top(x):\n    return helper(x)\n"
    src_tab = "def top(x):\n    return table['a'](x)\n"

    def make(source, **globs):
        g = dict(globs)
        exec(source, g)
        return g["top"]

    h1 = lambda x: x + 1      # noqa: E731
    h2 = lambda x: x + 2      # noqa: E731 — same co_code, different const
    assert _code_fingerprint(make(src, helper=h1)) \
        == _code_fingerprint(make(src, helper=h1))
    assert _code_fingerprint(make(src, helper=h1)) \
        != _code_fingerprint(make(src, helper=h2))
    assert _code_fingerprint(make(src_tab, table={"a": h1})) \
        != _code_fingerprint(make(src_tab, table={"a": h2}))


# ---------------------------------------------------------------------------
# cross-process: zero lowerings from a populated cache
# ---------------------------------------------------------------------------

_CHILD = textwrap.dedent("""\
    import json, os, sys
    import numpy as np
    from flink_ml_tpu.utils.backend import count_compiles
    from flink_ml_tpu import Table
    from flink_ml_tpu.models.classification.logisticregression import (
        LogisticRegressionModel)
    from flink_ml_tpu.serving.executor import make_servable
    from flink_ml_tpu.kernels.registry import kernel_stats

    rng = np.random.default_rng(11)
    model = LogisticRegressionModel()
    model.set_model_data(Table({
        "coefficients": rng.normal(size=(1, 12)),
        "intercept": np.array([0.4])}))
    feats = Table({"features": rng.normal(size=(64, 12))
                   .astype(np.float32)})
    servable = make_servable(model, feats.take(2), max_batch_rows=32)
    with count_compiles() as count:
        servable.warm_up()
        out = servable.predict(feats.take(5))
    print(json.dumps({
        "lowerings": count(),
        "aot": kernel_stats.snapshot()["aot"],
        "warmup": servable.warmup_report,
        "out": {n: np.asarray(out[n]).tolist()
                for n in sorted(out.column_names)},
    }))
""")


def _run_child(script_path, cache_root):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["FLINK_ML_TPU_AOT_CACHE_PATH"] = cache_root
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, script_path], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_second_process_warms_with_zero_compiles(cache, tmp_path):
    script = tmp_path / "child.py"
    script.write_text(_CHILD)

    cold = _run_child(str(script), cache.root)
    warm = _run_child(str(script), cache.root)

    # cold process compiled and stored; the warm one must not lower a
    # single program for the cached keys — the acceptance criterion
    assert cold["lowerings"] > 0
    assert cold["aot"]["misses"] > 0 and cold["aot"]["stores"] > 0
    assert warm["lowerings"] == 0, (
        f"warm process lowered {warm['lowerings']} programs — the "
        "executable cache did not cover its warm-up")
    assert warm["aot"]["hits"] >= len(warm["warmup"]["buckets"])
    assert warm["aot"]["misses"] == 0

    # served bits are identical across the two processes
    assert cold["out"] == warm["out"]

    # the warm-up report attributes every bucket to the cache
    assert all(b["source"] == "aot"
               for b in warm["warmup"]["buckets"].values())
    assert all(b["source"] == "compile"
               for b in cold["warmup"]["buckets"].values())
    # warm-up wall collapses when compiles become deserializes
    assert warm["warmup"]["wall_s"] < cold["warmup"]["wall_s"]


# ---------------------------------------------------------------------------
# autotune: measured, persisted, reloaded without re-search
# ---------------------------------------------------------------------------

def test_autotune_winner_persistence_roundtrip(cache):
    calls = {"slow": 0, "fast": 0}

    def mk(name, delay):
        def thunk():
            calls[name] += 1
            time.sleep(delay)
            return np.zeros(1)
        return thunk

    choice, decision = autotune.choose(
        "aot_test_op", (16, 4),
        {"slow": mk("slow", 0.003), "fast": mk("fast", 0.0)})
    assert choice == "fast" and decision["search_ms"] > 0
    assert calls["slow"] > 0 and calls["fast"] > 0
    key = "aot_test_op|(16, 4)"
    assert kernel_stats.tuned_ops[key]["source"] == "measured"

    # a later process (fresh cache instance): recorded winner, no search
    aot.set_cache(aot.ExecutableCache(cache.root))
    calls["slow"] = calls["fast"] = 0
    choice2, decision2 = autotune.choose(
        "aot_test_op", (16, 4),
        {"slow": mk("slow", 0.003), "fast": mk("fast", 0.0)})
    assert choice2 == "fast"
    assert calls == {"slow": 0, "fast": 0}           # zero re-search
    assert kernel_stats.tuned_ops[key]["source"] == "cache"


def test_autotune_disabled_measures_but_does_not_persist(tmp_path):
    aot.set_cache(None)
    try:
        assert not autotune.enabled()
        choice, dec = autotune.choose(
            "aot_nopersist_op", (),
            {"a": lambda: np.zeros(1),
             "b": lambda: (time.sleep(0.003), np.zeros(1))[1]})
        assert choice == "a" and dec["device"] is None
    finally:
        aot.set_cache(None)


def test_corrupt_decision_quarantines_and_researches(cache):
    autotune.choose("aot_decay_op", (),
                    {"x": lambda: np.zeros(1), "y": lambda: np.zeros(1)})
    tune_root = os.path.join(cache.root, "autotune")
    entry = [os.path.join(tune_root, n) for n in os.listdir(tune_root)][0]
    os.remove(os.path.join(entry, "manifest.json"))
    aot.set_cache(aot.ExecutableCache(cache.root))
    assert autotune.get_decision("aot_decay_op", ()) is None
    assert any(".corrupt" in n for n in os.listdir(tune_root))


def test_lookup_honors_tuned_backend(cache):
    kreg.register_kernel("aot_lookup_op", "alpha", lambda: None,
                         priority=10)
    kreg.register_kernel("aot_lookup_op", "beta", lambda: None,
                         priority=0)
    try:
        assert kreg.lookup("aot_lookup_op").backend == "alpha"
        choice, _ = autotune.choose(
            "aot_lookup_op", (),
            {"alpha": lambda: (time.sleep(0.003), np.zeros(1))[1],
             "beta": lambda: np.zeros(1)})
        assert choice == "beta"
        # the measured winner beats static priority, here and in every
        # later process that shares the cache root
        assert kreg.lookup("aot_lookup_op").backend == "beta"
        aot.set_cache(aot.ExecutableCache(cache.root))
        assert kreg.lookup("aot_lookup_op").backend == "beta"
        # forced lookups stay forced
        assert kreg.lookup("aot_lookup_op",
                           backend="alpha").backend == "alpha"
    finally:
        with kreg._REG_LOCK:
            kreg._REGISTRY.pop("aot_lookup_op", None)


def test_kmeans_block_pick_measured_and_persisted(cache):
    from flink_ml_tpu.ops import kmeans_pallas as kp

    bn = kp.pick_block_n_measured(8, 4, interpret=True,
                                  candidates=[128, 256])
    assert bn in (128, 256)
    key = "kmeans_update_stats|('block_n', 'feature_major', 8, 4)"
    assert kernel_stats.tuned_ops[key]["source"] == "measured"
    assert set(kernel_stats.tuned_ops[key]["timings_ms"]) == \
        {"128", "256"}

    aot.set_cache(aot.ExecutableCache(cache.root))   # later process
    bn2 = kp.pick_block_n_measured(8, 4, interpret=True,
                                   candidates=[128, 256])
    assert bn2 == bn
    assert kernel_stats.tuned_ops[key]["source"] == "cache"


def test_kmeans_block_pick_analytic_when_disabled():
    from flink_ml_tpu.ops import kmeans_pallas as kp

    aot.set_cache(None)
    try:
        assert kp.pick_block_n_measured(64, 256) == \
            kp.pick_block_n(None, 64, 256)
        assert kp.pick_block_n_workset_measured(64, 256) == \
            kp.pick_block_n_workset(None, 64, 256)
    finally:
        aot.set_cache(None)


# ---------------------------------------------------------------------------
# aot_jit: the training step builders' pre-warm path (GBT)
# ---------------------------------------------------------------------------

def _gbt_fixture():
    from flink_ml_tpu.models.common.gbt import GBTConfig

    rng = np.random.default_rng(23)
    X = rng.normal(size=(512, 6)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.int64)
    cfg = GBTConfig(num_trees=2, max_depth=3, max_bins=16,
                    learning_rate=0.3)
    return X, y, cfg


def test_gbt_train_forest_through_aot_cache(cache):
    """The hosted GBT trainer (the multiclass one: the binary fit is one
    fused ``iterate`` program) builds its levels, leaves and in-sample
    predictions through ``aot_jit``."""
    from flink_ml_tpu.models.common.gbt import train_forest_softmax
    from flink_ml_tpu.utils.backend import count_compiles

    X, y, cfg = _gbt_fixture()
    aot.set_cache(None)
    baseline = train_forest_softmax(X, y, 2, cfg)

    aot.set_cache(cache)
    first = train_forest_softmax(X, y, 2, cfg)   # compile + store

    aot.set_cache(aot.ExecutableCache(cache.root))    # restarted process
    with count_compiles() as count:
        second = train_forest_softmax(X, y, 2, cfg)
    assert count() == 0, (
        f"{count()} lowerings on the warm-cache GBT run — the aot_jit "
        "wrapper did not cover the training step builders")

    for a, b in ((baseline, first), (baseline, second)):
        assert np.array_equal(a.feature, b.feature)
        assert np.array_equal(a.threshold, b.threshold)
        assert np.array_equal(a.value, b.value)


def test_aot_jit_falls_back_under_tracing(cache):
    """aot_jit-wrapped fns called with tracers (inside an enclosing jit
    or scan, e.g. the out-of-core chunk paths) must inline as plain
    nested jits — an executable cannot run mid-trace."""
    import jax

    from flink_ml_tpu.kernels.aot import aot_jit

    @aot_jit
    def double(x):
        return x * 2

    @jax.jit
    def outer(x):
        return double(x) + 1

    x = jnp.arange(4, dtype=jnp.float32)
    assert np.array_equal(np.asarray(outer(x)),
                          np.asarray(x) * 2 + 1)
    assert np.array_equal(np.asarray(double(x)), np.asarray(x) * 2)


# ---------------------------------------------------------------------------
# serving warm-up readiness report + deploy summary
# ---------------------------------------------------------------------------

def _lr_model(d=8, seed=7):
    from flink_ml_tpu.models.classification.logisticregression import (
        LogisticRegressionModel)

    rng = np.random.default_rng(seed)
    model = LogisticRegressionModel()
    model.set_model_data(Table({
        "coefficients": rng.normal(size=(1, d)),
        "intercept": np.array([0.1])}))
    feats = Table({"features": rng.normal(size=(32, d))
                   .astype(np.float32)})
    return model, feats


def test_warmup_report_and_deploy_summary(cache, caplog):
    from flink_ml_tpu.serving import ModelRegistry, ServingEndpoint

    model, feats = _lr_model()
    registry = ModelRegistry()
    with caplog.at_level(logging.INFO, logger="flink_ml_tpu.robustness"):
        dep = registry.deploy("m", model, feats.take(1),
                              max_batch_rows=32)
    rep = dep.servable.warmup_report
    assert rep["wall_s"] > 0
    assert set(rep["buckets"]) == set(dep.servable.buckets)
    assert rep["compiled"] == len(dep.servable.buckets)
    assert any("warm-up of 'm'" in r.message and "compiled" in r.message
               for r in caplog.records)

    # a redeploy of the same generation: every bucket rides the compile
    # cache (or the aot loads) — zero fresh compiles, says the report
    dep2 = registry.deploy("m", model)
    rep2 = dep2.servable.warmup_report
    assert rep2["compiled"] == 0
    assert all(b["source"] in ("cache", "aot")
               for b in rep2["buckets"].values())

    endpoint = ServingEndpoint(registry, "m")
    assert endpoint.warmup_report == rep2


def test_warmup_report_without_cache():
    """The report (and the deploy summary) must not depend on the AOT
    cache being configured — sources just never say 'aot'."""
    from flink_ml_tpu.serving import ModelRegistry

    aot.set_cache(None)
    try:
        model, feats = _lr_model(seed=9)
        dep = ModelRegistry().deploy("m", model, feats.take(1),
                                     max_batch_rows=16)
        rep = dep.servable.warmup_report
        assert rep["wall_s"] > 0 and len(rep["buckets"]) > 0
        assert all(b["source"] != "aot"
                   for b in rep["buckets"].values())
    finally:
        aot.set_cache(None)


# ---------------------------------------------------------------------------
# stats surface: the kernels.* re-export carries the new gauges
# ---------------------------------------------------------------------------

def test_thread_counts_isolated_from_other_threads():
    """Warm-up source attribution diffs the deploy thread's OWN
    counters: dispatches recorded by a concurrently-serving thread (the
    hot-swap shape) must not move this thread's view."""
    import threading

    base = kernel_stats.thread_counts()
    t = threading.Thread(target=lambda: kernel_stats.record(
        "other_thread_op", compiled=True, seconds=0.0))
    t.start()
    t.join()
    assert kernel_stats.thread_counts() == base
    kernel_stats.record("this_thread_op", compiled=False, seconds=0.0)
    assert kernel_stats.thread_counts()[2] == base[2] + 1


def test_kernel_stats_publish_carries_aot_and_tuning_gauges():
    from flink_ml_tpu.utils.metrics import MetricGroup

    group = MetricGroup("t_aot")
    kernel_stats.publish(group)
    snap = group.snapshot()
    for gauge in ("aot_hits", "aot_misses", "aot_quarantined",
                  "aot_load_ms", "aot_compile_ms", "tuned_ops"):
        assert any(k.endswith(gauge) for k in snap), (gauge, snap.keys())
