"""The linear family's ingest on a mesh of one device, and its kept program.

``sgd_fit_mixed`` puts the columns flat and the device lays them out in
the epoch order (``sgd._epoch_tensors``); the ELL layout reads the
categorical rows through that order on the host and goes to the device in
rounds.  What the fit trains on has to be, bit for bit, what the host's
``prepare_epoch_tensor`` and ``ell_layout`` of the permuted tensor give.
The epoch driver states a program key, so a second fit of equal shapes
enqueues the first one's program, and a fit that differs in anything the
trace reads builds its own.
"""

import importlib.util
import json
import os

import jax
import numpy as np
import pytest

from flink_ml_tpu.models.common import sgd as S
from flink_ml_tpu.models.common.losses import LOSSES
from flink_ml_tpu.obs.trace import tracer
from flink_ml_tpu.ops import ell_scatter as E
from flink_ml_tpu.parallel import mesh as mesh_mod
from flink_ml_tpu.parallel.mesh import device_mesh

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")


def _bench_module(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"_bench_{name}", os.path.join(_BENCH, kind, f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture
def one_device():
    return device_mesh({"data": 1}, devices=jax.devices()[:1])


def _zipf_table(rng, n, nd, nc, d):
    """Zipf-skewed keys past the dense slots, the first field one key
    throughout (a batch of more than 512 rows makes it heavy)."""
    dense = rng.normal(size=(n, nd)).astype(np.float32)
    cat = np.minimum(rng.zipf(1.2, size=(n, nc)) + nd, d - 1).astype(
        np.int32)
    cat[:, 0] = nd
    y = (dense[:, 0] + 0.3 > 0).astype(np.float64)
    return dense, cat, y


@pytest.mark.parametrize("n", [1000, 1024], ids=["padded", "whole"])
def test_device_epoch_tensors_equal_the_hosts_bit_for_bit(one_device, n):
    """Dense rows, categorical rows, labels and weights gathered on the
    device by the epoch order equal ``prepare_epoch_tensor``'s, the
    zero pad of a last step that is not whole included."""
    rng = np.random.default_rng(3)
    steps, batch, perm = S.plan_epoch_layout(n, 64, 1, seed=5)
    columns = [rng.normal(size=(n, 13)).astype(np.float32),
               rng.integers(0, 1 << 20, size=(n, 26)).astype(np.int32),
               rng.integers(0, 2, size=n).astype(np.float32),
               rng.random(n).astype(np.float32)]
    order = S._epoch_order(perm, steps, batch)
    device = S._one_device(one_device)
    put = mesh_mod.put_in_rounds(
        [order.reshape(-1)] + [c.reshape(-1) for c in columns], device)
    got = S._epoch_tensors(put[0], tuple(put[1:]),
                           widths=tuple(c.shape[1:] for c in columns),
                           steps=steps)
    assert (steps * batch > n) == (n == 1000)
    for column, tensor in zip(columns, got):
        want = S.prepare_epoch_tensor(column, perm, steps, batch)
        assert tensor.dtype == want.dtype
        assert np.asarray(tensor).tobytes() == want.tobytes()


@pytest.mark.parametrize("builder", ["native", "numpy"])
@pytest.mark.parametrize("with_values", [False, True])
def test_layout_read_through_the_order_equals_the_permuted_tensors(
        builder, with_values, monkeypatch):
    """``ell_layout(table, order=...)`` gives the layout of
    ``prepare_epoch_tensor(table, perm, ...)`` array for array: grids,
    overflow lists, heavy indices and counts, the needs; the pad rows of
    the last step are index 0, as the host's pad makes them."""
    if builder == "numpy":
        monkeypatch.setattr(E, "_native_ell", lambda: None)
    elif E._native_ell() is None:
        pytest.skip("no native ELL builder on this machine")
    rng = np.random.default_rng(11)
    n, nnz, d, batch = 1000, 5, 128 * 128, 96
    table = np.minimum(rng.zipf(1.3, size=(n, nnz)), d - 1).astype(np.int32)
    values = rng.normal(size=(n, nnz)).astype(np.float32)
    steps, batch, perm = S.plan_epoch_layout(n, batch, 1, seed=2)
    host = E.ell_layout(
        S.prepare_epoch_tensor(table, perm, steps, batch), d,
        heavy_threshold=128, device=False,
        values=(S.prepare_epoch_tensor(values, perm, steps, batch)
                if with_values else None))
    got = E.ell_layout(table, d, heavy_threshold=128, device=False,
                       values=values if with_values else None,
                       order=S._epoch_order(perm, steps, batch))
    assert host.need_ovf.max() > 0 and host.need_heavy.max() > 0
    for field in ("src", "pos", "mask", "ovf_idx", "ovf_src", "heavy_idx",
                  "heavy_cnt", "val", "ovf_val", "need_ovf", "need_heavy"):
        a, b = getattr(host, field), getattr(got, field)
        assert (a is None) == (b is None), field
        if a is not None:
            assert a.dtype == b.dtype and a.shape == b.shape, field
            np.testing.assert_array_equal(a, b, err_msg=field)


@pytest.mark.parametrize("plan", ["ell", "xla"])
def test_fit_trains_on_the_hosts_epoch_arrays(plan, one_device,
                                              monkeypatch):
    """What ``sgd_fit_mixed`` on one device hands its epoch driver, put in
    rounds of a few KB, equals the host path's arrays: the epoch tensors
    and, on the ELL plan, every layout stack (overflow and heavy arrays
    among them), the last step padded."""
    rng = np.random.default_rng(7)
    n, nd, nc, d, batch = 2000, 3, 4, 128 * 128, 768
    dense, cat, y = _zipf_table(rng, n, nd, nc, d)
    weights = rng.random(n)
    cfg = S.SGDConfig(learning_rate=0.3, max_epochs=2,
                      global_batch_size=batch, tol=0, seed=4)
    monkeypatch.setattr(S, "plan_mixed_impl", lambda *a, **k: plan)
    monkeypatch.setattr(mesh_mod, "PUT_BYTES", 4096)
    seen = []
    driver = S._run_minibatch_epochs

    def spy(update, data, *args, **kwargs):
        seen.append([np.asarray(a) for a in data])
        return driver(update, data, *args, **kwargs)

    monkeypatch.setattr(S, "_run_minibatch_epochs", spy)
    state, _ = S.sgd_fit_mixed(LOSSES["logistic"], dense, cat, y, weights,
                               d, cfg, mesh=one_device)
    assert state.planned_impl == plan

    steps, batch, perm = S.plan_epoch_layout(n, batch, 1, cfg.seed)

    def epoch(a):
        return S.prepare_epoch_tensor(a, perm, steps, batch)

    if plan == "ell":
        lay = E.ell_layout(epoch(cat), d, device=False)
        cats = [lay.src, lay.pos, lay.mask, lay.ovf_idx, lay.ovf_src,
                lay.heavy_idx, lay.heavy_cnt]
        assert lay.need_ovf.max() > 0 and lay.need_heavy.max() > 0
    else:
        cats = [epoch(cat)]
    want = ([epoch(dense)] + cats
            + [epoch(y.astype(np.float32)),
               epoch(weights.astype(np.float32))])
    (got,) = seen
    assert len(got) == len(want)
    for i, (a, b) in enumerate(zip(got, want)):
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), i


def _fit_noting_reuse(fit):
    tracer.enable()
    try:
        state, log = fit()
        (span,) = tracer.find("iterate.dispatch.compile")
    finally:
        tracer.disable()
        tracer.clear()
    return state, log, span.ids["reused"]


@pytest.mark.parametrize("trainer", ["mixed", "sparse", "dense"])
def test_a_second_fit_of_equal_shapes_reuses_the_firsts_program(
        trainer, one_device):
    """``sgd_fit_mixed``, ``sgd_fit_sparse`` and ``sgd_fit`` share the
    epoch driver and its key: the second fit enqueues the kept program
    and trains the first one's weights bit for bit."""
    rng = np.random.default_rng(5)
    n, nd, nc, d = 512, 3, 4, 128 * 128
    dense, cat, y = _zipf_table(rng, n, nd, nc, d)
    cfg = S.SGDConfig(learning_rate=0.2, max_epochs=2, global_batch_size=64,
                      tol=0, seed=1)
    loss = LOSSES["logistic"]
    fits = {
        "mixed": lambda: S.sgd_fit_mixed(loss, dense, cat, y, None, d, cfg,
                                         mesh=one_device),
        "sparse": lambda: S.sgd_fit_sparse(
            loss, cat, np.ones(cat.shape, np.float32), y, None, d, cfg,
            mesh=one_device),
        "dense": lambda: S.sgd_fit(loss, dense, y, None, cfg,
                                   mesh=one_device),
    }
    first, first_log, reused_first = _fit_noting_reuse(fits[trainer])
    second, second_log, reused_second = _fit_noting_reuse(fits[trainer])
    assert (reused_first, reused_second) == (0, 1)
    assert second.coefficients.tobytes() == first.coefficients.tobytes()
    assert second_log == first_log


@pytest.mark.parametrize("what", ["learning_rate", "loss", "ell_precision",
                                  "max_epochs", "rows"])
def test_a_fit_that_differs_in_what_the_trace_reads_builds_its_own(
        what, one_device, monkeypatch):
    """One fact the trace bakes in, or one shape, changed between two fits
    of the ELL plan: the second builds its program anew, and a third
    like it reuses that one."""
    monkeypatch.setattr(S, "plan_mixed_impl", lambda *a, **k: "ell")
    rng = np.random.default_rng(6)
    n, nd, nc, d = 512, 3, 4, 128 * 128
    dense, cat, y = _zipf_table(rng, n, nd, nc, d)
    cfg = S.SGDConfig(learning_rate=0.2, max_epochs=2, global_batch_size=64,
                      tol=0, seed=1)
    loss = LOSSES["logistic"]

    def fit():
        return S.sgd_fit_mixed(loss, dense, cat, y, None, d, cfg,
                               mesh=one_device)

    assert _fit_noting_reuse(fit)[2] == 0
    assert _fit_noting_reuse(fit)[2] == 1
    if what == "learning_rate":
        cfg.learning_rate = 0.3
    elif what == "loss":
        loss = LOSSES["hinge"]
    elif what == "ell_precision":
        cfg.ell_precision = "highest"
    elif what == "max_epochs":
        cfg.max_epochs = 3
    else:
        dense, cat, y = dense[:448], cat[:448], y[:448]
    assert _fit_noting_reuse(fit)[2] == 0
    assert _fit_noting_reuse(fit)[2] == 1


@pytest.mark.parametrize("plan", ["ell", "xla"])
def test_fit_agrees_with_the_float64_reference_on_a_zipf_table(
        plan, one_device, monkeypatch):
    """``sgd_fit_mixed`` through the ingest on one device against the
    benchmark's plain float64 reference (``references/logistic_sgd.py``),
    on a seeded table of ``lr_criteo``'s generator at its rehearsal size
    (16,384 rows, batch 4096, 2^20 weights, 2 epochs), within the
    configuration's limits of ``weight_err`` and ``loss_gap``."""
    with open(os.path.join(_BENCH, "configs", "lr_criteo.json")) as f:
        config = json.load(f)
    rows, batch = 16384, 4096
    config["reference_params"]["batch"] = batch
    params = {k: v for k, v in config.items() if not isinstance(v, dict)}
    params.update(config["generator_params"], rows=rows)
    data = _bench_module("generators", "criteo_zipf").generate(
        params, 2147483999)
    monkeypatch.setattr(S, "plan_mixed_impl", lambda *a, **k: plan)
    cfg = S.SGDConfig(learning_rate=config["learning_rate"],
                      reg=config["reg"], tol=config["tol"],
                      max_epochs=config["max_iter"],
                      global_batch_size=batch,
                      seed=config["reference_params"]["shuffle_seed"])
    state, loss_log = S.sgd_fit_mixed(
        LOSSES["logistic"], data["features_dense"],
        data["features_indices"], data["label"], None,
        config["num_features"], cfg, mesh=one_device)
    assert state.planned_impl == plan
    got = _bench_module("references", "logistic_sgd").compare(
        config, data, {"coefficients": state.coefficients,
                       "intercept": np.asarray([state.intercept]),
                       "loss_log": np.asarray(loss_log)}, 0)
    for name, limit in config["limits"].items():
        assert got[name] <= limit, (name, got)
