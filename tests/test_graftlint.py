"""Tier-1 wiring for the graftlint framework (ISSUE 8).

Three layers, mirroring the pattern ``test_no_host_sync.py``
established for single checkers:

1. **Repo gate** — ``python -m scripts.graftlint`` semantics: every pass
   over its roots, suppressions + baseline applied AND enforced, exit 0.
2. **Can't-fail self-tests** — each pass must flag its seeded bad
   fixture (a guard that can't fail guards nothing) and, for the bug
   classes this repo actually shipped fixes for, must flag the
   HISTORICAL bug when re-seeded into today's real module (the PR 1
   ``flush_lock``-across-put deadlock, the PR 7-era read-after-donate
   resume shape, the PR 3 top_k-under-auto abort).
3. **Framework mechanics** — suppressions are line-scoped and must be
   exercised (unused ones are findings), baseline entries match by
   symbol and go stale loudly, the walker skips ``__pycache__``, the
   JSON report is machine-stable, the legacy shims delegate.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from scripts.graftlint import runner  # noqa: E402
from scripts.graftlint.core import (  # noqa: E402
    EXCLUDE_DIRS,
    ModuleInfo,
    Project,
    iter_py_files,
)
from scripts.graftlint.passes import ALL_PASSES  # noqa: E402
from scripts.graftlint.passes.atomic_writes import AtomicWritesPass  # noqa: E402,E501
from scripts.graftlint.passes.collectives import (  # noqa: E402
    _AXIS_ARG_POS,
    _COLLECTIVES,
    CollectiveConsistencyPass,
)
from scripts.graftlint.passes.donation import DonationSafetyPass  # noqa: E402,E501
from scripts.graftlint.passes.host_sync import HostSyncPass  # noqa: E402
from scripts.graftlint.passes.locks import LockDisciplinePass  # noqa: E402


def _check(pass_obj, tmp_path, source, name="mod.py", repo=None):
    """Run one AST pass over one fixture module."""
    path = tmp_path / name
    path.write_text(textwrap.dedent(source))
    repo = repo or str(tmp_path)
    project = Project(repo=repo)
    return pass_obj.check_module(ModuleInfo(str(path), repo), project)


# ---------------------------------------------------------------------------
# 1. repo gate
# ---------------------------------------------------------------------------

def test_repo_is_clean_under_all_passes():
    """THE gate: all seven passes, suppressions + baseline enforced, no
    findings — and the accepted exceptions are really being exercised
    (they'd otherwise be unused-suppression / stale-baseline findings)."""
    report = runner.run()
    assert [f.render() for f in report.findings] == []
    assert report.exit_code == 0
    assert report.files_scanned > 100       # the walk actually walked


def test_cli_entry_point_runs_all_passes(tmp_path):
    """One command, one exit code, machine-readable findings."""
    out = tmp_path / "report.json"
    proc = subprocess.run(
        [sys.executable, "-m", "scripts.graftlint", "--json", str(out)],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "graftlint clean" in proc.stdout
    payload = json.loads(out.read_text())
    assert payload["findings"] == []
    assert payload["files_scanned"] > 100


def test_in_repo_paths_restrict_not_replace_pass_roots(tmp_path):
    """Review regression: ``graftlint flink_ml_tpu`` must intersect the
    narrowing path with each pass's own roots — running the durable-
    layer-only atomic-writes rule over the whole package produced 6
    false findings.  Out-of-repo fixture paths keep the legacy
    point-at-anything behavior."""
    report = runner.run(paths=["flink_ml_tpu"])
    assert [f.render() for f in report.findings] == []
    # and the scoping really narrows: a subdir path visits only it
    report2 = runner.run(passes=[LockDisciplinePass()],
                         paths=["flink_ml_tpu/serving"],
                         enforce_suppressions=False)
    assert report2.files_scanned <= 10
    # out-of-repo path: scanned as given even though outside the roots
    bad = tmp_path / "fixture.py"
    bad.write_text(textwrap.dedent("""\
        import queue
        import threading
        q = queue.Queue()
        lock = threading.Lock()
        def f(item):
            with lock:
                q.put(item)
        """))
    report3 = runner.run(passes=[LockDisciplinePass()], paths=[str(bad)],
                         enforce_suppressions=False)
    assert len(report3.findings) == 1


def test_json_dash_emits_parseable_stdout():
    """Review regression: with ``--json -`` the human-readable render
    moves to stderr so stdout IS the machine-readable report."""
    proc = subprocess.run(
        [sys.executable, "-m", "scripts.graftlint", "--json", "-"],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0
    payload = json.loads(proc.stdout)       # parses as pure JSON
    assert payload["findings"] == []
    assert "graftlint clean" in proc.stderr


def test_nonexistent_explicit_path_fails_loudly(tmp_path):
    """Review regression: a typo'd CI path must never pass by checking
    zero files — the runner raises (legacy-checker parity) and the CLI
    exits 2."""
    with pytest.raises(FileNotFoundError, match="no such path"):
        runner.run(paths=["flink_ml_tpu/modles"])
    proc = subprocess.run(
        [sys.executable, "-m", "scripts.graftlint", "does_not_exist.py"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 2
    assert "no such path" in proc.stderr


def test_donation_flags_same_statement_read_after_call(tmp_path):
    """Review regression: Python evaluates left-to-right, so
    ``step(state, xs) + state.sum()`` reads the donated buffer in the
    SAME statement; a read textually before the call does not."""
    problems = _check(DonationSafetyPass(), tmp_path, """\
        import jax
        step = jax.jit(lambda s, x: s + x, donate_argnums=(0,))
        def bad(state, xs):
            loss = step(state, xs) + state.sum()
            return loss
        def fine(state, xs):
            loss = state.sum() + step(state, xs)
            return loss
        """)
    assert len(problems) == 1 and "'state'" in problems[0].message
    assert problems[0].symbol == "bad"


def test_collectives_nested_switch_reports_once(tmp_path):
    """Review regression: a divergent switch inside a nested def is
    reachable from both the inner and outer function walks — one
    finding, not two."""
    problems = _check(CollectiveConsistencyPass(), tmp_path, """\
        from jax import lax
        def branch_a(x):
            return lax.psum(x, "data")
        def branch_b(x):
            return lax.all_gather(x, "data").sum()
        def outer(x, idx):
            def inner(y):
                return lax.switch(idx, [branch_a, branch_b], y)
            return inner(x)
        """)
    assert len(problems) == 1
    assert "different collective sets" in problems[0].message


def test_pass_catalog_covers_the_contract():
    ids = {cls.id for cls in ALL_PASSES}
    assert ids == {"host-sync", "atomic-writes", "donation-safety",
                   "lock-discipline", "collective-consistency",
                   "kernel-registry", "unfenced-timing"}


# ---------------------------------------------------------------------------
# 2a. donation-safety
# ---------------------------------------------------------------------------

def test_donation_flags_read_after_donate(tmp_path):
    problems = _check(DonationSafetyPass(), tmp_path, """\
        import jax
        step = jax.jit(lambda s, x: s + x, donate_argnums=(0,))
        def fit(state, xs):
            out = step(state, xs)
            return state + out
        """)
    assert len(problems) == 1 and "'state' is read after" in \
        problems[0].message


def test_donation_accepts_rebind_and_copy(tmp_path):
    problems = _check(DonationSafetyPass(), tmp_path, """\
        import jax
        step = jax.jit(lambda s, x: s + x, donate_argnums=(0,))
        def fit(state, xs):
            state = step(state, xs)      # rebind consumes the donation
            return state
        def fit_copy(state, xs):
            out = step(state.copy(), xs)   # donates a private copy
            return state + out
        """)
    assert problems == []


def test_donation_covers_decorator_and_loop_back_edge(tmp_path):
    problems = _check(DonationSafetyPass(), tmp_path, """\
        import jax
        from functools import partial
        @partial(jax.jit, donate_argnums=(0,))
        def consume(buf, x):
            return buf * x
        def loop(state, chunks):
            for c in chunks:
                consume(state, c)    # donated on iter 1, read on iter 2
            return 0
        """)
    assert len(problems) == 1


def test_donation_follows_jit_factories(tmp_path):
    """The serving/executor.py shape: a helper manufactures donating
    callables; the donated positions come from the call site."""
    problems = _check(DonationSafetyPass(), tmp_path, """\
        import jax
        def serving_jit(fn, donate_argnums):
            donate = donate_argnums if True else ()
            return jax.jit(fn, donate_argnums=donate)
        def serve(X, w):
            fn = serving_jit(lambda a, b: a @ b, (0,))
            out = fn(X, w)
            return X.sum() + out         # X was donated
        """)
    assert len(problems) == 1 and "'X'" in problems[0].message


def test_donation_respects_conditional_donate_and_early_return(tmp_path):
    """Regression for the iteration/core.py false positive this PR hit:
    two mutually-exclusive arms each call the donating fn, the first
    ends in ``return`` — the second arm's call must NOT read as a
    re-read of the first arm's donation.  The conditional
    ``(0,) if cfg else ()`` form still counts as donating."""
    problems = _check(DonationSafetyPass(), tmp_path, """\
        import jax
        def build(body, cfg, initial_state, data):
            run = jax.jit(body, donate_argnums=(0,) if cfg else ())
            if cfg:
                final, outs = run(initial_state, data)
                return final, outs
            final, outs, extra = run(initial_state, data)
            return final, (outs, extra)
        """)
    assert problems == []


def test_donation_catches_reseeded_resume_hazard_in_real_core():
    """Re-seed the exact hazard ``_private_copy`` exists to prevent into
    today's ``iteration/core.py`` (read the donated state between the
    step call and the rebind): the pass must catch it, and must be
    clean on the unmodified file."""
    path = os.path.join(REPO, "flink_ml_tpu", "iteration", "core.py")
    src = open(path).read()
    marker = ("            res = step(state, jnp.asarray(epoch, jnp.int32),"
              " epoch_data)\n            state = res.feedback")
    assert marker in src, "core.py hosted-loop shape moved; update test"
    bad = src.replace(marker, marker.replace(
        "\n            state = res.feedback",
        "\n            stale = jax.tree_util.tree_leaves(state)"
        "\n            state = res.feedback"))
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        good_p = os.path.join(d, "core_good.py")
        bad_p = os.path.join(d, "core_bad.py")
        open(good_p, "w").write(src)
        open(bad_p, "w").write(bad)
        project = Project(repo=d)
        p = DonationSafetyPass()
        assert p.check_module(ModuleInfo(good_p, d), project) == []
        problems = p.check_module(ModuleInfo(bad_p, d), project)
    assert len(problems) == 1 and "'state'" in problems[0].message


# ---------------------------------------------------------------------------
# 2b. lock-discipline
# ---------------------------------------------------------------------------

def test_locks_flag_blocking_under_with_acquire_and_transitive(tmp_path):
    problems = _check(LockDisciplinePass(), tmp_path, """\
        import queue
        import threading
        import time
        q = queue.Queue(maxsize=2)
        lock = threading.Lock()
        def bad_put(item):
            with lock:
                q.put(item)
        def bad_sleep():
            lock.acquire()
            time.sleep(0.1)
            lock.release()
        def bad_transitive(item):
            with lock:
                helper(item)
        def helper(item):
            q.put(item, timeout=1.0)
        """)
    assert len(problems) == 3
    reasons = "\n".join(f.message for f in problems)
    assert "queue put()" in reasons and "time.sleep" in reasons \
        and "helper() -> queue put()" in reasons


def test_locks_accept_release_before_block_and_nonqueue_get(tmp_path):
    """The ``_flush_ready`` discipline: release, block, reacquire — in
    linear statement order the put is NOT held; and ``dict.get`` is not
    a queue get."""
    problems = _check(LockDisciplinePass(), tmp_path, """\
        import queue
        import threading
        q = queue.Queue()
        lock = threading.Lock()
        def good(items, d, k):
            lock.acquire()
            try:
                staged = list(items)
                v = d.get(k)
                lock.release()
                try:
                    for s in staged:
                        q.put(s)
                finally:
                    lock.acquire()
            finally:
                lock.release()
            return v
        """)
    assert problems == []


def test_locks_flag_device_put_join_and_wait(tmp_path):
    problems = _check(LockDisciplinePass(), tmp_path, """\
        import jax
        import threading
        lock = threading.Lock()
        def to_device(batch, sharding):
            with lock:
                return jax.device_put(batch, sharding)
        def reap(worker_thread):
            with lock:
                worker_thread.join()
        def land(manager):
            with lock:
                manager.wait()
        """)
    assert len(problems) == 3


def test_locks_catch_reseeded_flush_lock_bug_in_real_prefetch():
    """Re-seed THE PR 1 bug (blocking put moved back under flush_lock)
    into today's ``data/prefetch.py``: the pass must reconstruct the
    finding, and must be clean on the unmodified file."""
    path = os.path.join(REPO, "flink_ml_tpu", "data", "prefetch.py")
    src = open(path).read()
    marker = """\
                        flush_lock.release()
                        try:
                            for entry in ready:
                                put_or_abandon(q, entry)
                        finally:
                            flush_lock.acquire()"""
    assert marker in src, "prefetch._flush_ready shape moved; update test"
    bad = src.replace(marker, """\
                        for entry in ready:
                            put_or_abandon(q, entry)""")
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        good_p = os.path.join(d, "prefetch_good.py")
        bad_p = os.path.join(d, "prefetch_bad.py")
        open(good_p, "w").write(src)
        open(bad_p, "w").write(bad)
        project = Project(repo=d)
        p = LockDisciplinePass()
        assert p.check_module(ModuleInfo(good_p, d), project) == []
        problems = p.check_module(ModuleInfo(bad_p, d), project)
    assert len(problems) == 1
    assert "flush_lock" in problems[0].message
    assert "put_or_abandon() -> queue put()" in problems[0].message


# ---------------------------------------------------------------------------
# 2c. collective-consistency
# ---------------------------------------------------------------------------

_COLL_FIXTURE = """\
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental.shard_map import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(jax.devices(), ("data",))

    def unbound_body(x):
        return lax.psum(x, "model")

    def run_unbound(x):
        return shard_map(unbound_body, mesh, in_specs=(P("data"),),
                         out_specs=P())(x)

    def topk_body(x):
        vals, idx = lax.top_k(x, 4)
        return lax.psum(vals, "data")

    def run_topk_auto(x):
        return shard_map(topk_body, mesh, in_specs=(P("data"),),
                         out_specs=P(), auto=frozenset({"model"}))(x)

    def branch_a(x):
        return lax.psum(x, "data")

    def branch_b(x):
        return lax.all_gather(x, "data").sum()

    def diverging(x, idx):
        return lax.switch(idx, [branch_a, branch_b], x)

    def converged(x):
        n = lax.psum(jnp.ones(()), "data")
        idx = (n > 4).astype(jnp.int32)
        return lax.switch(idx, [branch_a, branch_b], x)

    def same_sets(x, flag):
        return lax.cond(flag, branch_a, branch_a, x)
    """


def test_collectives_three_subchecks_fire_and_safe_shapes_pass(tmp_path):
    problems = _check(CollectiveConsistencyPass(), tmp_path, _COLL_FIXTURE)
    msgs = sorted(f.message for f in problems)
    assert len(problems) == 3
    assert any("axis 'model'" in m for m in msgs)            # unbound axis
    assert any("top_k" in m for m in msgs)                   # topk in auto
    assert any("different collective sets" in m for m in msgs)
    # the psum-derived switch (``converged``) and the matching-set cond
    # (``same_sets``) must NOT be flagged: exactly one branch-divergence
    # finding exists and it anchors in ``diverging``
    switch_findings = [f for f in problems
                       if "different collective sets" in f.message]
    assert [f.symbol for f in switch_findings] == ["diverging"]


def test_collectives_follow_factory_built_branch_lists(tmp_path):
    """The grad_reduce adaptive-ladder shape: branches built by a
    comprehension over a factory whose inner defs carry different
    collective sets."""
    problems = _check(CollectiveConsistencyPass(), tmp_path, """\
        from jax import lax
        def make(spec):
            if spec == "exact":
                def branch(acc):
                    return lax.psum(acc, "data")
            else:
                def branch(acc):
                    return lax.all_gather(acc, "data").sum()
            return branch
        def reduce_bucketed(acc, rung, ladder):
            branches = [make(spec) for spec in ladder]
            return lax.switch(rung, branches, acc)
        """)
    assert len(problems) == 1
    assert "different collective sets" in problems[0].message


def test_collectives_follow_cross_module_references(tmp_path):
    """sgd -> grad_reduce shape: the shard_map body reaches top_k
    through a from-import into another repo module."""
    pkg = tmp_path / "pkg"
    (pkg / "sub").mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    (pkg / "sub" / "__init__.py").write_text("")
    (pkg / "sub" / "reduce.py").write_text(textwrap.dedent("""\
        from jax import lax
        def compress(g):
            vals, idx = lax.top_k(g, 8)
            return lax.psum(vals, "data")
        """))
    (pkg / "sub" / "train.py").write_text(textwrap.dedent("""\
        from ..sub import reduce as GR
        from jax.experimental.shard_map import shard_map
        def build(mesh, auto_axes):
            def body(g):
                return GR.compress(g)
            return shard_map(body, mesh, in_specs=(), out_specs=(),
                             auto=auto_axes)
        """))
    project = Project(repo=str(tmp_path))
    mod = ModuleInfo(str(pkg / "sub" / "train.py"), str(tmp_path))
    problems = CollectiveConsistencyPass().check_module(mod, project)
    assert len(problems) == 1 and "top_k" in problems[0].message
    assert "reduce.py" in problems[0].message       # names the hop


def test_collectives_resolve_axis_through_round_loop_helpers(tmp_path):
    """ISSUE 16 seeded fixture: the recursive-doubling wire protocol
    moves its ``ppermute`` out of the shard_map body into round-loop
    helpers whose perm lists are built from ``axis_size(axis)``.  A
    typo'd LITERAL axis at the helper call site used to sail past
    sub-check 1 — the collective itself only ever sees the parameter
    name ``axis``, which is not a literal — and abort at lowering.  The
    pass now computes which helper params flow into collective axis
    arguments (transitively: ``body -> rd_round -> exchange ->
    ppermute``) and checks the literals at the call site.  The
    correctly-bound twin body must stay clean."""
    problems = _check(CollectiveConsistencyPass(), tmp_path, """\
        import jax
        from jax import lax
        from jax.experimental.shard_map import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(jax.devices(), ("data",))

        def exchange(x, axis, perm):
            return lax.ppermute(x, axis, perm)

        def rd_round(x, r, axis):
            p = lax.axis_size(axis)
            half = p >> (r + 1)
            perm = [(i, i ^ half) for i in range(p)]
            return x + exchange(x, axis, perm)

        def body(x):
            for r in range(3):
                x = rd_round(x, r, "dcn")     # typo: mesh binds "data"
            return x

        def body_ok(x):
            for r in range(3):
                x = rd_round(x, r, "data")
            return x

        def run(x):
            return shard_map(body, mesh, in_specs=(P("data"),),
                             out_specs=P("data"))(x)

        def run_ok(x):
            return shard_map(body_ok, mesh, in_specs=(P("data"),),
                             out_specs=P("data"))(x)
        """)
    msgs = [f.message for f in problems]
    assert len(problems) == 1, msgs
    assert "axis 'dcn'" in msgs[0] and "['data']" in msgs[0]


def test_collectives_pass_visits_wire_protocol_module():
    """The recursive-doubling primitives live in parallel/collectives.py
    — assert the pass's walk genuinely VISITS that module (a roots
    listing that misses it guards nothing), that the new wrappers are
    known collectives with their axis positions registered (their axis
    rides AFTER the segment length, so the lax-default position 1 would
    misread a perm list as an axis), and that the module is clean raw:
    the one ``lax.cond`` in ``sparse_all_reduce_rd`` keeps equal branch
    collective sets by construction (both doubling branches are pure
    ppermute), so no new baseline entry was needed."""
    assert {"sparse_all_reduce_rd", "fixed_point_all_reduce"} \
        <= _COLLECTIVES
    assert _AXIS_ARG_POS["sparse_all_reduce_rd"] == 3
    assert _AXIS_ARG_POS["sparse_all_reduce"] == 3
    project = Project(repo=REPO)
    visited = {os.path.basename(m.path): m
               for m in project.iter_modules(
                   [os.path.join(REPO, "flink_ml_tpu", "parallel")])}
    assert {"collectives.py", "grad_reduce.py"} <= set(visited)
    coll_pass = CollectiveConsistencyPass()
    assert coll_pass.check_module(visited["collectives.py"], project) == []
    # grad_reduce's only raw finding stays the baselined rung switch —
    # the new wire-protocol plumbing added nothing
    raw = coll_pass.check_module(visited["grad_reduce.py"], project)
    assert {f.symbol for f in raw} <= {"_reduce_bucketed"}


def test_grad_reduce_adaptive_switch_is_baselined_not_silent():
    """The one accepted finding: the rung switch in _reduce_bucketed IS
    flagged by the raw pass (the taint is carried state, invisible
    statically) and the committed baseline is what accepts it — so the
    guard stays falsifiable."""
    project = Project(repo=REPO)
    mod = project.module(os.path.join(
        REPO, "flink_ml_tpu", "parallel", "grad_reduce.py"))
    problems = CollectiveConsistencyPass().check_module(mod, project)
    assert len(problems) == 1
    assert problems[0].symbol == "_reduce_bucketed"
    entries = runner.load_baseline(runner.BASELINE)
    assert any(e.fingerprint == problems[0].fingerprint for e in entries)


# ---------------------------------------------------------------------------
# 2d. absorbed passes keep their teeth
# ---------------------------------------------------------------------------

def test_host_sync_pass_flags_seeded_sync(tmp_path):
    problems = _check(HostSyncPass(), tmp_path, """\
        import numpy as np
        def batch_step(params, xb):
            return params, np.asarray(xb)
        """)
    assert len(problems) == 1 and "np.asarray" in problems[0].message


def test_atomic_writes_pass_flags_naked_write(tmp_path):
    problems = _check(AtomicWritesPass(), tmp_path, """\
        import os
        def save(path, data):
            with open(path, 'wb') as f:
                f.write(data)
        """)
    assert len(problems) == 1 and "half-written" in problems[0].message


def test_atomic_writes_pass_visits_aot_cache_modules():
    """The persistent executable/decision cache (ISSUE 12) joined the
    durable roots: the pass must actually VISIT both modules (parse
    them, see their open-for-write sites) and find every write riding
    the tmp-dir -> commit -> os.replace protocol — no suppressions, no
    blind spots."""
    import ast

    for rel in ("flink_ml_tpu/kernels/aot.py",
                "flink_ml_tpu/kernels/autotune.py"):
        assert rel in AtomicWritesPass.roots
    project = Project(repo=REPO)
    writes_seen = 0
    for rel in ("flink_ml_tpu/kernels/aot.py",
                "flink_ml_tpu/kernels/autotune.py"):
        mod = project.module(os.path.join(REPO, *rel.split("/")))
        problems = AtomicWritesPass().check_module(mod, project)
        assert problems == [], (
            f"{rel}: cache writes must use the commit protocol "
            f"(tmp -> os.replace): {[f.message for f in problems]}")
        # visits-the-module proof: the pass's subject matter — actual
        # open-for-write call sites — exists in the module it cleared
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call) \
                    and mod.call_qualname(node) == "open":
                writes_seen += 1
    assert writes_seen >= 5, (
        "the AOT cache modules lost their write sites — the durable-root "
        "listing is guarding nothing")


def test_atomic_writes_pass_visits_obs_package():
    """flink_ml_tpu/obs joined the durable roots (ISSUE 13): trace
    exports must be tmp -> os.replace (they are the files an operator
    loads after a crash), and the one sanctioned exception — the
    sampler's line-framed JSONL append (torn tail dropped by
    read_samples, the WAL-tail stance) — must be SEEN by the raw pass
    and disarmed only by its inline suppression (suppression !=
    blindness)."""
    assert "flink_ml_tpu/obs" in AtomicWritesPass.roots
    project = Project(repo=REPO)
    visited = {os.path.relpath(m.path, REPO): m
               for m in project.iter_modules(
                   [os.path.join(REPO, "flink_ml_tpu", "obs")])}
    names = {os.path.basename(p) for p in visited}
    assert {"trace.py", "tree.py", "probe.py"} <= names
    by_file = {rel: AtomicWritesPass().check_module(mod, project)
               for rel, mod in visited.items()}
    # the atomic export writes clear the pass outright
    trace_rel = os.path.join("flink_ml_tpu", "obs", "trace.py")
    assert by_file[trace_rel] == []
    # the sampler append IS flagged raw, and the flag is suppressed
    tree_rel = os.path.join("flink_ml_tpu", "obs", "tree.py")
    raw = by_file[tree_rel]
    assert {f.symbol for f in raw} == {"ObsSampler.sample"}
    mod = visited[tree_rel]
    for f in raw:
        assert "atomic-writes" in mod.suppressions.get(f.line, set())


def test_scheduler_modules_visited_by_lock_and_host_sync_passes():
    """ISSUE 14: the multi-tenant scheduler joined the scanned surfaces.
    ``lock-discipline`` roots at the whole package — assert the walk
    genuinely VISITS the new modules (a root listing that misses them
    guards nothing) and that both are clean: the scheduler's whole
    design is compute-under-the-condvar, block outside it, and the
    embedding cache's pool faults must never run under a held lock.
    ``host-sync``'s step-tree roots grew ``flink_ml_tpu/serving`` (the
    one serve loop multiplexes EVERY tenant — a host sync in a
    step-shaped helper there stalls all of them at once)."""
    from scripts.graftlint.passes.host_sync import SCAN_ROOTS

    assert "flink_ml_tpu/serving" in SCAN_ROOTS
    assert "flink_ml_tpu" in LockDisciplinePass.roots
    project = Project(repo=REPO)
    lock_visited = {
        os.path.relpath(m.path, REPO)
        for m in project.iter_modules(
            [os.path.join(REPO, r) for r in LockDisciplinePass.roots])}
    new_modules = [os.path.join("flink_ml_tpu", "serving", name)
                   for name in ("scheduler.py", "embcache.py")]
    for rel in new_modules:
        assert rel in lock_visited, f"lock-discipline never visits {rel}"
    sync_visited = {
        os.path.relpath(m.path, REPO)
        for m in project.iter_modules(
            [os.path.join(REPO, r) for r in SCAN_ROOTS])}
    for rel in new_modules:
        assert rel in sync_visited, f"host-sync never visits {rel}"
    for rel in new_modules:
        mod = project.module(os.path.join(REPO, rel))
        assert LockDisciplinePass().check_module(mod, project) == []
        assert HostSyncPass().check_module(mod, project) == []


def test_atomic_writes_pass_guards_durability_module():
    """robustness/durability.py joined the durable roots this PR; its
    two protocol-level exceptions are inline-suppressed, so the raw pass
    must still SEE them (suppression != blindness)."""
    assert "flink_ml_tpu/robustness/durability.py" in \
        AtomicWritesPass.roots
    project = Project(repo=REPO)
    mod = project.module(os.path.join(
        REPO, "flink_ml_tpu", "robustness", "durability.py"))
    problems = AtomicWritesPass().check_module(mod, project)
    assert {f.symbol for f in problems} == \
        {"write_manifest", "write_commit_marker"}
    for f in problems:
        assert "atomic-writes" in mod.suppressions.get(f.line, set())


# ---------------------------------------------------------------------------
# 3. framework mechanics
# ---------------------------------------------------------------------------

def _mini_repo(tmp_path, body, suppress=""):
    mod = tmp_path / "m.py"
    mod.write_text(textwrap.dedent(body))
    baseline = tmp_path / "baseline.txt"
    baseline.write_text(suppress)
    return str(mod), str(baseline)


def test_suppression_drops_finding_and_is_marked_used(tmp_path):
    mod, baseline = _mini_repo(tmp_path, """\
        import queue
        import threading
        q = queue.Queue()
        lock = threading.Lock()
        def f(item):
            with lock:
                q.put(item)   # graftlint: disable=lock-discipline
        """)
    report = runner.run(repo=str(tmp_path),
                        passes=[LockDisciplinePass()], paths=[mod],
                        baseline_path=baseline,
                        enforce_suppressions=True)
    assert report.findings == [] and len(report.suppressed) == 1


def test_unused_suppression_is_itself_a_finding(tmp_path):
    mod, baseline = _mini_repo(tmp_path, """\
        def fine():   # graftlint: disable=lock-discipline
            return 1
        """)
    report = runner.run(repo=str(tmp_path),
                        passes=[LockDisciplinePass()], paths=[mod],
                        baseline_path=baseline,
                        enforce_suppressions=True)
    assert len(report.findings) == 1
    assert report.findings[0].pass_id == "unused-suppression"


def test_baseline_entry_grandfathers_by_symbol_and_goes_stale(tmp_path):
    body = """\
        import queue
        import threading
        q = queue.Queue()
        lock = threading.Lock()
        def legacy(item):
            with lock:
                q.put(item)
        """
    mod, baseline = _mini_repo(
        tmp_path, body,
        suppress="lock-discipline m.py::legacy  # grandfathered\n")
    report = runner.run(repo=str(tmp_path),
                        passes=[LockDisciplinePass()], paths=[mod],
                        baseline_path=baseline,
                        enforce_suppressions=True)
    assert report.findings == [] and len(report.baselined) == 1
    # now the hazard is fixed but the entry remains: stale-baseline
    mod2, baseline2 = _mini_repo(
        tmp_path, "def legacy():\n    return 1\n",
        suppress="lock-discipline m.py::legacy  # grandfathered\n")
    report2 = runner.run(repo=str(tmp_path),
                         passes=[LockDisciplinePass()], paths=[mod2],
                         baseline_path=baseline2,
                         enforce_suppressions=True)
    assert [f.pass_id for f in report2.findings] == ["stale-baseline"]


def test_suppression_allows_trailing_justification(tmp_path):
    """Review regression: ids stop at the comma-separated list — a
    trailing justification must neither disarm the suppression nor be
    swallowed into a garbage pass id."""
    mod, baseline = _mini_repo(tmp_path, """\
        import queue
        import threading
        q = queue.Queue()
        lock = threading.Lock()
        def f(item):
            with lock:
                q.put(item)  # graftlint: disable=lock-discipline held is protocol safe
        """)
    report = runner.run(repo=str(tmp_path),
                        passes=[LockDisciplinePass()], paths=[mod],
                        baseline_path=baseline,
                        enforce_suppressions=True)
    assert report.findings == [] and len(report.suppressed) == 1


def test_suppression_syntax_quoted_in_docstring_is_not_a_suppression(
        tmp_path):
    """Review regression: documentation QUOTING the disable syntax (a
    docstring or string literal) must not register as a suppression —
    it would fail the gate as unused."""
    mod, baseline = _mini_repo(tmp_path, '''\
        """Module doc: silence a finding with
        `# graftlint: disable=lock-discipline` on the flagged line."""
        EXAMPLE = "# graftlint: disable=host-sync"
        ''')
    report = runner.run(repo=str(tmp_path),
                        passes=[LockDisciplinePass()], paths=[mod],
                        baseline_path=baseline,
                        enforce_suppressions=True)
    assert report.findings == []


def test_shim_check_file_honors_inline_suppressions():
    """Review regression: the legacy shims and the canonical gate must
    agree on what is clean — durability.py's two suppressed sites stay
    quiet through the shim surface too."""
    caw = _load_shim("check_atomic_writes")
    path = os.path.join(REPO, "flink_ml_tpu", "robustness",
                        "durability.py")
    assert caw.check_file(path) == []


def test_json_report_shape(tmp_path):
    mod, baseline = _mini_repo(tmp_path, """\
        import queue
        import threading
        q = queue.Queue()
        lock = threading.Lock()
        def f(item):
            with lock:
                q.put(item)
        """)
    report = runner.run(repo=str(tmp_path),
                        passes=[LockDisciplinePass()], paths=[mod],
                        baseline_path=baseline,
                        enforce_suppressions=True)
    payload = report.as_dict()
    assert payload["counts"] == {"lock-discipline": 1}
    f = payload["findings"][0]
    assert {"pass", "path", "line", "symbol", "message", "hint"} <= set(f)
    json.dumps(payload)       # serializable as-is


def test_walker_skips_pycache_and_gitignore_covers_artifacts(tmp_path):
    (tmp_path / "pkg" / "__pycache__").mkdir(parents=True)
    (tmp_path / "pkg" / "real.py").write_text("x = 1\n")
    (tmp_path / "pkg" / "__pycache__" / "junk.py").write_text("x = 1\n")
    found = list(iter_py_files([str(tmp_path)]))
    assert [os.path.basename(p) for p in found] == ["real.py"]
    # every generated dir the walker special-cases must be gitignored so
    # the linter (and git) agree on what is source
    gitignore = open(os.path.join(REPO, ".gitignore")).read()
    for pattern in ("__pycache__/", ".pytest_cache/", "graftlint*.json"):
        assert pattern in gitignore, f"{pattern} missing from .gitignore"
    assert "__pycache__" in EXCLUDE_DIRS


def test_alias_resolution_sees_through_import_renames(tmp_path):
    """The shared qualified-name layer: ``import numpy as onp`` and a
    local rebinding both resolve to the same host-sync finding."""
    problems = _check(HostSyncPass(), tmp_path, """\
        import numpy as onp
        def chunk_step(carry, xs):
            return carry, onp.asarray(xs)
        """)
    assert len(problems) == 1 and "np.asarray" in problems[0].message


def test_linter_is_lint_clean():
    """Run the AST passes over the linter's own tree (plus the shims):
    the gate must hold itself to its own conventions."""
    project = Project(repo=REPO)
    passes = [AtomicWritesPass(), DonationSafetyPass(),
              LockDisciplinePass(), CollectiveConsistencyPass(),
              HostSyncPass()]
    problems = []
    for mod in project.iter_modules(["scripts"]):
        for p in passes:
            problems += p.check_module(mod, project)
    assert [f.render() for f in problems] == []


# ---------------------------------------------------------------------------
# legacy shims
# ---------------------------------------------------------------------------

def _load_shim(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "scripts", f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_shims_delegate_and_warn(tmp_path, capsys):
    shim = _load_shim("check_no_host_sync")
    with pytest.warns(DeprecationWarning, match="graftlint"):
        rc = shim.main([])
    assert rc == 0
    assert "clean" in capsys.readouterr().out
    caw = _load_shim("check_atomic_writes")
    with pytest.warns(DeprecationWarning, match="graftlint"):
        rc = caw.main([])
    assert rc == 0
    # the shim surface the legacy tests import is intact
    assert shim.SCAN_ROOTS and callable(shim.check_file) \
        and callable(shim._module_paths)
    assert caw.DURABLE_MODULES and callable(caw.check_file)


# ---------------------------------------------------------------------------
# 2f. kernel-registry (ISSUE 10)
# ---------------------------------------------------------------------------

def test_kernel_registry_flags_direct_pallas_call(tmp_path):
    from scripts.graftlint.passes.kernel_registry import KernelRegistryPass

    problems = _check(KernelRegistryPass(), tmp_path, """\
        from jax.experimental import pallas as pl

        def fast_scores(x):
            return pl.pallas_call(lambda i, o: None, out_shape=x)(x)
        """)
    assert len(problems) == 1
    assert "pallas_call bypasses the kernel registry" in problems[0].message
    assert problems[0].symbol == "fast_scores"


def test_kernel_registry_flags_use_pallas_branching(tmp_path):
    """The pre-PR 10 sgd.py idiom, reconstructed from a seeded fixture:
    a use_pallas parameter AND the call-site keyword both flag."""
    from scripts.graftlint.passes.kernel_registry import KernelRegistryPass

    problems = _check(KernelRegistryPass(), tmp_path, """\
        import jax

        def _update(w, use_pallas=True):
            if use_pallas:
                return w + 1
            return w - 1

        def fit(w):
            return _update(w, use_pallas=jax.default_backend() == "tpu")

        def fit_inline(w):
            use_pallas = jax.default_backend() == "tpu"
            return w + 1 if use_pallas else w - 1
        """)
    msgs = [p.message for p in problems]
    assert any("parameter forks backend dispatch" in m for m in msgs)
    assert any("backend branching at the call site" in m for m in msgs)
    assert any("binding forks backend dispatch inline" in m for m in msgs)
    assert len(problems) == 3


def test_kernel_registry_accepts_registry_lookup(tmp_path):
    from scripts.graftlint.passes.kernel_registry import KernelRegistryPass

    problems = _check(KernelRegistryPass(), tmp_path, """\
        from flink_ml_tpu.kernels.registry import lookup

        def _update(w, backend=None):
            entry = lookup("ell_margin", sig=(w.shape[0],), backend=backend)
            return entry.fn(w)
        """)
    assert problems == []


def test_kernel_registry_scope_is_models_and_retrieval_trees():
    """scope_fixed: pointing graftlint at flink_ml_tpu must not run the
    dispatch-layer rule over ops/ (where pallas_call lives by design).
    ISSUE 19 grew the scope to ``retrieval/`` — the index layer looks
    ``retrieve`` up exactly like the model families look up their ops,
    so the bypass idioms are flagged there too, and the pass must
    genuinely VISIT the new modules (a root listing that misses them
    guards nothing)."""
    from scripts.graftlint.passes.kernel_registry import KernelRegistryPass

    p = KernelRegistryPass()
    assert p.scope_fixed
    assert p.roots == ("flink_ml_tpu/models", "flink_ml_tpu/retrieval")
    project = Project(repo=REPO)
    assert p.run(project, ["flink_ml_tpu"]) == []
    visited = {
        os.path.relpath(m.path, REPO)
        for m in project.iter_modules(
            [os.path.join(REPO, r) for r in p.roots])}
    for name in ("ivf.py", "metrics.py"):
        rel = os.path.join("flink_ml_tpu", "retrieval", name)
        assert rel in visited, f"kernel-registry never visits {rel}"


# ---------------------------------------------------------------------------
# 2g. unfenced-timing (ISSUE 13)
# ---------------------------------------------------------------------------

def test_unfenced_timing_flags_bare_bracketing(tmp_path):
    """The can't-fail seeded fixture: perf_counter brackets a jitted
    call with no fence — it times the dispatch's enqueue, not the
    work."""
    from scripts.graftlint.passes.unfenced_timing import UnfencedTimingPass

    problems = _check(UnfencedTimingPass(), tmp_path, """\
        import time
        import jax

        run = jax.jit(lambda x: x * 2)

        def measure(x):
            t0 = time.perf_counter()
            y = run(x)
            return time.perf_counter() - t0
        """)
    assert len(problems) == 1
    assert "no device fence" in problems[0].message
    assert problems[0].symbol == "measure"


def test_unfenced_timing_accepts_fenced_forms(tmp_path):
    """np.asarray probe fetch, jax.device_get, and StepTimer.stop all
    satisfy the fence; host-only timing (no jitted call inside the
    bracket) is never flagged."""
    from scripts.graftlint.passes.unfenced_timing import UnfencedTimingPass

    problems = _check(UnfencedTimingPass(), tmp_path, """\
        import time
        import jax
        import numpy as np

        from flink_ml_tpu.utils.profiler import StepTimer

        run = jax.jit(lambda x: x * 2)

        def measure_probe(x):
            t0 = time.perf_counter()
            y = run(x)
            np.asarray(y)
            return time.perf_counter() - t0

        def measure_get(x):
            t0 = time.perf_counter()
            y = run(x)
            jax.device_get(y)
            return time.perf_counter() - t0

        def measure_timer(x):
            t0 = time.perf_counter()
            timer = StepTimer().start()
            y = run(x)
            timer.stop(y)
            return time.perf_counter() - t0

        def measure_host_only(rows):
            t0 = time.perf_counter()
            total = sum(range(rows))
            return time.perf_counter() - t0
        """)
    assert problems == []


def test_unfenced_timing_covers_decorator_and_direct_jit(tmp_path):
    """@jax.jit / @partial(jax.jit, ...) defs and a direct
    jax.jit(fn)(args) invocation are all jitted calls."""
    from scripts.graftlint.passes.unfenced_timing import UnfencedTimingPass

    problems = _check(UnfencedTimingPass(), tmp_path, """\
        import time
        from functools import partial

        import jax

        @jax.jit
        def step(x):
            return x + 1

        @partial(jax.jit, donate_argnums=(0,))
        def step2(x):
            return x + 2

        def measure_decorated(x):
            t0 = time.perf_counter()
            y = step(x)
            return time.perf_counter() - t0

        def measure_partial(x):
            t0 = time.perf_counter()
            y = step2(x)
            return time.perf_counter() - t0

        def measure_direct(x):
            t0 = time.perf_counter()
            y = jax.jit(lambda v: v * 3)(x)
            return time.perf_counter() - t0
        """)
    assert len(problems) == 3
    assert {p.symbol for p in problems} == {
        "measure_decorated", "measure_partial", "measure_direct"}


def test_unfenced_timing_nested_defs_are_their_own_scope(tmp_path):
    """A nested helper's bracket reports ONCE (in its own scope), and a
    jitted call inside a never-called nested def does not poison the
    enclosing function's host-only bracket."""
    from scripts.graftlint.passes.unfenced_timing import UnfencedTimingPass

    problems = _check(UnfencedTimingPass(), tmp_path, """\
        import time
        import jax

        run = jax.jit(lambda x: x * 2)

        def outer_with_bad_helper(x):
            def measure(x):
                t0 = time.perf_counter()
                y = run(x)
                return time.perf_counter() - t0

            return measure(x)

        def outer_host_bracket(x, items):
            t0 = time.perf_counter()

            def helper(v):
                return run(v)          # defined, never called in-bracket

            total = sum(items)
            return time.perf_counter() - t0
        """)
    assert len(problems) == 1
    assert problems[0].symbol == "outer_with_bad_helper.measure"


def test_unfenced_timing_scope_and_repo_clean():
    """Scope-fixed to the trees that publish measurements (benchmarks/
    + obs/), and both are clean."""
    from scripts.graftlint.passes.unfenced_timing import UnfencedTimingPass

    p = UnfencedTimingPass()
    assert p.scope_fixed
    assert set(p.roots) == {"benchmarks", "flink_ml_tpu/obs"}
    project = Project(repo=REPO)
    assert [f.render() for f in p.run(project)] == []
    # the walk genuinely visited both roots
    scanned = {os.path.relpath(s, REPO) for s in project.scanned}
    for root in (os.path.join("benchmarks", "runners"),
                 os.path.join("flink_ml_tpu", "obs")):
        assert any(s.startswith(root) for s in scanned)


def test_elastic_module_visited_by_lock_and_host_sync_passes():
    """ISSUE 15: the elastic coordinator joined the scanned surfaces.
    ``lock-discipline`` roots at the whole package — assert the walk
    genuinely VISITS ``parallel/elastic.py`` (the lease table computes
    under an RLock and must never block there: an expire/poll holding
    the lock across a device_put or queue op would stall the training
    loop at every chunk boundary) and that ``host-sync`` — whose roots
    include ``flink_ml_tpu/parallel`` — sees it too; both must report
    it clean."""
    from scripts.graftlint.passes.host_sync import SCAN_ROOTS

    assert "flink_ml_tpu/parallel" in SCAN_ROOTS
    assert "flink_ml_tpu" in LockDisciplinePass.roots
    rel = os.path.join("flink_ml_tpu", "parallel", "elastic.py")
    project = Project(repo=REPO)
    lock_visited = {
        os.path.relpath(m.path, REPO)
        for m in project.iter_modules(
            [os.path.join(REPO, r) for r in LockDisciplinePass.roots])}
    assert rel in lock_visited, "lock-discipline never visits elastic.py"
    sync_visited = {
        os.path.relpath(m.path, REPO)
        for m in project.iter_modules(
            [os.path.join(REPO, r) for r in SCAN_ROOTS])}
    assert rel in sync_visited, "host-sync never visits elastic.py"
    mod = project.module(os.path.join(REPO, rel))
    assert LockDisciplinePass().check_module(mod, project) == []
    assert HostSyncPass().check_module(mod, project) == []


def test_autoscale_modules_visited_by_host_sync_and_atomic_writes():
    """ISSUE 17: ``flink_ml_tpu/autoscale/`` joined both scanned
    surfaces.  Assert host-sync's SCAN_ROOTS and atomic-writes'
    DURABLE_MODULES carry the root, that the walks genuinely VISIT all
    four control-plane modules (a root that matches nothing keeps a
    rule from ever firing — the visits-the-modules stance), and that
    every module is clean under host-sync, atomic-writes (the
    placement publish is tmp -> os.replace), and lock-discipline (the
    store writes its file OUTSIDE the lock with a generation re-check
    on re-acquire)."""
    from scripts.graftlint.passes.atomic_writes import DURABLE_MODULES
    from scripts.graftlint.passes.host_sync import SCAN_ROOTS

    assert "flink_ml_tpu/autoscale" in SCAN_ROOTS
    assert "flink_ml_tpu/autoscale" in DURABLE_MODULES
    modules = [os.path.join("flink_ml_tpu", "autoscale", f)
               for f in ("placement.py", "signals.py", "policy.py",
                         "controller.py")]
    project = Project(repo=REPO)
    sync_visited = {
        os.path.relpath(m.path, REPO)
        for m in project.iter_modules(
            [os.path.join(REPO, r) for r in SCAN_ROOTS])}
    durable_visited = {
        os.path.relpath(m.path, REPO)
        for m in project.iter_modules(
            [os.path.join(REPO, r) for r in AtomicWritesPass.roots])}
    for rel in modules:
        assert rel in sync_visited, f"host-sync never visits {rel}"
        assert rel in durable_visited, \
            f"atomic-writes never visits {rel}"
        mod = project.module(os.path.join(REPO, rel))
        assert HostSyncPass().check_module(mod, project) == []
        assert AtomicWritesPass().check_module(mod, project) == []
        assert LockDisciplinePass().check_module(mod, project) == []


def test_kernels_modules_visited_by_host_sync():
    """ISSUE 18: ``flink_ml_tpu/kernels/`` joined the host-sync scan —
    the quantize module's dequant helpers trace into every int8 serving
    program, so a host fetch in a step-shaped helper there would fence
    every consumer's dispatch stream.  Assert SCAN_ROOTS carries the
    root, the walk genuinely VISITS the kernel modules (quantize
    included — a root that matches nothing keeps the rule from ever
    firing), and every one is clean: calibration's host numpy lives at
    publish/bind time, never inside a step body."""
    from scripts.graftlint.passes.host_sync import SCAN_ROOTS

    assert "flink_ml_tpu/kernels" in SCAN_ROOTS
    modules = [os.path.join("flink_ml_tpu", "kernels", f)
               for f in ("quantize.py", "registry.py", "aot.py")]
    project = Project(repo=REPO)
    visited = {
        os.path.relpath(m.path, REPO)
        for m in project.iter_modules(
            [os.path.join(REPO, r) for r in SCAN_ROOTS])}
    for rel in modules:
        assert rel in visited, f"host-sync never visits {rel}"
        mod = project.module(os.path.join(REPO, rel))
        assert HostSyncPass().check_module(mod, project) == []


def test_failover_module_visited_by_lock_and_host_sync_passes():
    """ISSUE 20: ``serving/failover.py`` joined both scanned surfaces
    through the existing roots (``flink_ml_tpu/serving`` for host-sync,
    the whole package for lock-discipline).  Assert the walks genuinely
    VISIT the module (a root that matches nothing keeps a rule from
    ever firing — the visits-the-modules stance) and that it is clean
    under both: the failover driver's requeue + re-placement runs
    INLINE on the scheduler's one serve loop when a dispatch fault
    fires, so a host sync there would stall every tenant during the
    exact window the failover exists to keep short, and the lease
    table computes under its lock but fires tracer instants and
    recoveries outside it."""
    from scripts.graftlint.passes.host_sync import SCAN_ROOTS

    assert "flink_ml_tpu/serving" in SCAN_ROOTS
    assert "flink_ml_tpu" in LockDisciplinePass.roots
    rel = os.path.join("flink_ml_tpu", "serving", "failover.py")
    project = Project(repo=REPO)
    sync_visited = {
        os.path.relpath(m.path, REPO)
        for m in project.iter_modules(
            [os.path.join(REPO, r) for r in SCAN_ROOTS])}
    assert rel in sync_visited, "host-sync never visits failover.py"
    lock_visited = {
        os.path.relpath(m.path, REPO)
        for m in project.iter_modules(
            [os.path.join(REPO, r) for r in LockDisciplinePass.roots])}
    assert rel in lock_visited, \
        "lock-discipline never visits failover.py"
    mod = project.module(os.path.join(REPO, rel))
    assert HostSyncPass().check_module(mod, project) == []
    assert LockDisciplinePass().check_module(mod, project) == []


def test_retrieval_modules_visited_by_host_sync():
    """ISSUE 19: ``flink_ml_tpu/retrieval/`` joined the host-sync scan —
    the fused retrieve stage traces into every index tenant's serving
    program through the shared plan jit, so a host sync in a
    step-shaped helper there would stall the multiplexed serve loop
    exactly like one in ``serving/`` would.  Assert SCAN_ROOTS carries
    the root, the walk genuinely VISITS the retrieval modules (a root
    that matches nothing keeps the rule from ever firing), and every
    one is clean: index build/re-anchor is host numpy by design, but it
    runs at build time, never inside the dispatched search."""
    from scripts.graftlint.passes.host_sync import SCAN_ROOTS

    assert "flink_ml_tpu/retrieval" in SCAN_ROOTS
    modules = [os.path.join("flink_ml_tpu", "retrieval", f)
               for f in ("ivf.py", "metrics.py")]
    project = Project(repo=REPO)
    visited = {
        os.path.relpath(m.path, REPO)
        for m in project.iter_modules(
            [os.path.join(REPO, r) for r in SCAN_ROOTS])}
    for rel in modules:
        assert rel in visited, f"host-sync never visits {rel}"
        mod = project.module(os.path.join(REPO, rel))
        assert HostSyncPass().check_module(mod, project) == []
