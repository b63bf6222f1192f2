"""ELL static-routing scatter (`ops/ell_scatter.py`) — the Pallas hot
path behind the mixed-layout LR trainer.

Tier-1 (CPU) coverage: layout construction (host + device builders must
agree, overflow and heavy-hitter routing must be exact), the csum/pick
math against a plain numpy scatter, and the full `_mixed_update_ell`
step against the `_mixed_update` oracle.  The Mosaic kernel itself is
compiled and parity-checked on the chip by `tests_tpu/` and
`chip_smoke.py` (same stance as the KMeans kernel)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flink_ml_tpu.ops.ell_scatter import (
    ELL_WIDTH,
    ell_layout,
    ell_layout_device,
    ell_scatter_apply_xla,
    supported,
)


def _scatter_reference(d, layout, r_ext, lr, step=0):
    """Dense scatter the ELL + overflow routing back to a flat weight."""
    w = np.zeros(d, np.float64)
    src = np.asarray(layout.src[step])
    pos = np.asarray(layout.pos[step])
    mask = np.asarray(layout.mask[step])
    rows = src.shape[0]
    # reconstruct per-slot updates the kernel would apply
    u = -lr * r_ext[src]
    csum = np.cumsum(u, axis=1)
    G = np.take_along_axis(csum, pos, axis=1) * mask
    delta = G - np.concatenate([np.zeros((rows, 1)), G[:, :-1]], axis=1)
    w += delta.reshape(-1)
    np.add.at(w, np.asarray(layout.ovf_idx[step]),
              -lr * r_ext[np.asarray(layout.ovf_src[step])])
    np.add.at(w, np.asarray(layout.heavy_idx[step]),
              -lr * (np.asarray(layout.heavy_cnt[step], np.float64)
                     @ r_ext[:layout.batch]))
    return w


def _direct_scatter(d, cat, r, lr):
    w = np.zeros(d, np.float64)
    np.add.at(w, cat.reshape(-1),
              np.repeat(-lr * r, cat.shape[-1]))
    return w


class TestLayout:
    def test_supported(self):
        assert supported(1 << 20)
        assert supported(128 * 128)
        assert not supported(1000)       # not lane-divisible
        assert not supported(128 * 64)   # too few rows

    def test_routing_matches_direct_scatter(self):
        rng = np.random.default_rng(0)
        d, batch, nnz = 128 * 128, 64, 7
        cat = rng.integers(0, d, size=(2, batch, nnz)).astype(np.int32)
        r = rng.normal(size=batch).astype(np.float32)
        layout = ell_layout(cat, d)
        r_ext = np.concatenate([r, np.zeros(1, np.float32)])
        for step in range(2):
            got = _scatter_reference(d, layout, r_ext, 0.3, step)
            want = _direct_scatter(d, cat[step], r, 0.3)
            np.testing.assert_allclose(got, want, atol=1e-5)

    def test_heavy_hitter_overflows(self):
        # one index receives every slot: below the heavy threshold it
        # splits ELL (128) + overflow (the rest)
        d, batch, nnz = 128 * 128, 300, 2
        cat = np.full((1, batch, nnz), 777, np.int32)
        r = np.ones(batch, np.float32)
        layout = ell_layout(cat, d, heavy_threshold=10_000)
        n_ovf = int((np.asarray(layout.ovf_src[0]) != batch).sum())
        assert n_ovf == batch * nnz - ELL_WIDTH
        r_ext = np.concatenate([r, np.zeros(1, np.float32)])
        got = _scatter_reference(d, layout, r_ext, 1.0)
        want = _direct_scatter(d, cat[0], r, 1.0)
        np.testing.assert_allclose(got, want, atol=1e-5)

    def test_heavy_hitter_dense_path(self):
        # above the threshold the whole run routes to the count matrix
        rng = np.random.default_rng(8)
        d, batch, nnz = 128 * 128, 400, 4
        cat = rng.integers(0, d, size=(1, batch, nnz)).astype(np.int32)
        cat[:, :, 0] = 777          # 400 slots > threshold 300
        cat[:, ::2, 1] = 778        # 200 slots < threshold: stays per-slot
        r = rng.normal(size=batch).astype(np.float32)
        layout = ell_layout(cat, d, heavy_threshold=300)
        h_idx = np.asarray(layout.heavy_idx[0])
        assert 777 in h_idx and 778 not in h_idx
        # heavy slots left the ELL grid and the overflow list
        assert int((np.asarray(layout.ovf_src[0])
                    != batch).sum()) < batch * nnz
        r_ext = np.concatenate([r, np.zeros(1, np.float32)])
        got = _scatter_reference(d, layout, r_ext, 0.7)
        want = _direct_scatter(d, cat[0], r, 0.7)
        np.testing.assert_allclose(got, want, atol=1e-4)

    def test_device_builder_agrees_with_host(self):
        rng = np.random.default_rng(1)
        d, batch, nnz = 128 * 128, 96, 5
        cat = rng.integers(0, d, size=(3, batch, nnz)).astype(np.int32)
        # include a heavy hitter to exercise the device overflow path
        cat[:, :, 0] = 12345
        host = ell_layout(cat, d)
        dev = ell_layout_device(jnp.asarray(cat), d, ovf_cap=1024)
        r = rng.normal(size=batch).astype(np.float32)
        r_ext = np.concatenate([r, np.zeros(1, np.float32)])
        for step in range(3):
            got_h = _scatter_reference(d, host, r_ext, 0.5, step)
            got_d = _scatter_reference(d, dev, r_ext, 0.5, step)
            np.testing.assert_allclose(got_h, got_d, atol=1e-5)

    def test_device_builder_capacity_check(self):
        """ADVICE r3: the device builder drops slots beyond the static
        caps — need_ovf/need_heavy + assert_capacities make that loud."""
        rng = np.random.default_rng(2)
        d, batch, nnz = 128 * 128, 64, 4
        cat = rng.integers(0, d, size=(2, batch, nnz)).astype(np.int32)
        ok = ell_layout_device(jnp.asarray(cat), d, ovf_cap=1024)
        assert ok.need_ovf is not None and ok.need_heavy is not None
        assert ok.assert_capacities() is ok

        # force an overflow flood: all 256 slots/step land in table row 0
        # (indices < 128) with ~2 repeats each — light runs, but the row
        # keeps only ELL_WIDTH slots, so ~128 must spill per step
        cat2 = rng.integers(0, 128, size=(2, batch, nnz)).astype(np.int32)
        need = ell_layout_device(jnp.asarray(cat2), d, ovf_cap=4096)
        worst = int(jnp.max(need.need_ovf))
        assert worst >= batch * nnz - 128
        starved = ell_layout_device(jnp.asarray(cat2), d, ovf_cap=worst - 1)
        with pytest.raises(ValueError, match="raise ovf_cap"):
            starved.assert_capacities()

        # heavy starvation: two distinct heavy indices, cap of one
        cat3 = np.zeros((1, 600, 2), np.int32)
        cat3[..., 1] = 777
        starved_h = ell_layout_device(jnp.asarray(cat3), d, heavy_cap=1)
        with pytest.raises(ValueError, match="raise heavy_cap"):
            starved_h.assert_capacities()


class TestApplyXla:
    def test_matches_numpy(self):
        rng = np.random.default_rng(2)
        d, batch, nnz = 128 * 128, 128, 9
        cat = rng.integers(0, d, size=(1, batch, nnz)).astype(np.int32)
        layout = ell_layout(cat, d)
        r = rng.normal(size=batch).astype(np.float32)
        r_ext = jnp.concatenate([jnp.asarray(r), jnp.zeros(1)])
        u = -0.2 * np.asarray(r_ext)[np.asarray(layout.src[0])]
        w0 = rng.normal(size=d).astype(np.float32)
        got = np.asarray(ell_scatter_apply_xla(
            jnp.asarray(w0), jnp.asarray(u), layout.pos[0],
            layout.mask[0]))
        want = w0.astype(np.float64) + _scatter_reference(
            d, layout, np.asarray(r_ext), 0.2)
        np.testing.assert_allclose(got, want, atol=1e-4)


class TestMixedUpdateEll:
    def test_step_matches_xla_oracle(self):
        from flink_ml_tpu.models.common.losses import logistic_loss
        from flink_ml_tpu.models.common.sgd import (
            SGDConfig, _mixed_update, _mixed_update_ell)

        rng = np.random.default_rng(3)
        d, batch, nnz, nd = 128 * 128, 64, 6, 4
        dense = rng.normal(size=(batch, nd)).astype(np.float32)
        cat = rng.integers(0, d, size=(1, batch, nnz)).astype(np.int32)
        y = rng.integers(0, 2, size=batch).astype(np.float32)
        wb = np.ones(batch, np.float32)
        layout = ell_layout(cat, d)

        for cfg in (SGDConfig(learning_rate=0.4, tol=0),
                    SGDConfig(learning_rate=0.4, reg=0.05,
                              elastic_net=0.3, tol=0)):
            params = {"w": jnp.asarray(rng.normal(size=d), jnp.float32),
                      "b": jnp.asarray(0.1, jnp.float32)}
            oracle = _mixed_update(logistic_loss, cfg)
            want, want_loss = oracle(params, jnp.asarray(dense),
                                     jnp.asarray(cat[0]), jnp.asarray(y),
                                     jnp.asarray(wb))
            ell = _mixed_update_ell(logistic_loss, cfg, backend="xla")
            got, got_loss = ell(params, jnp.asarray(dense),
                                layout.src[0],
                                layout.pos[0], layout.mask[0],
                                layout.ovf_idx[0], layout.ovf_src[0],
                                layout.heavy_idx[0], layout.heavy_cnt[0],
                                jnp.asarray(y), jnp.asarray(wb))
            np.testing.assert_allclose(np.asarray(got_loss),
                                       np.asarray(want_loss), rtol=1e-6)
            np.testing.assert_allclose(np.asarray(got["w"]),
                                       np.asarray(want["w"]), atol=1e-5)
            np.testing.assert_allclose(np.asarray(got["b"]),
                                       np.asarray(want["b"]), rtol=1e-5)

    def test_sgd_fit_mixed_plans_xla_on_cpu(self):
        from flink_ml_tpu.models.common.sgd import plan_mixed_impl
        from flink_ml_tpu.parallel.mesh import default_mesh

        assert plan_mixed_impl(1 << 20, default_mesh()) == "xla"


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="Mosaic kernel needs TPU")
class TestApplyPallas:
    def test_kernel_matches_xla_twin(self):
        from flink_ml_tpu.ops.ell_scatter import ell_scatter_apply

        rng = np.random.default_rng(4)
        d = 128 * 128
        rows = d // 128
        u = rng.normal(size=(rows, 128)).astype(np.float32)
        cat = rng.integers(0, d, size=(1, 64, 8)).astype(np.int32)
        layout = ell_layout(cat, d)
        w0 = rng.normal(size=d).astype(np.float32)
        got = np.asarray(ell_scatter_apply(
            jnp.asarray(w0), jnp.asarray(u), layout.pos[0],
            layout.mask[0]))
        want = np.asarray(ell_scatter_apply_xla(
            jnp.asarray(w0), jnp.asarray(u), layout.pos[0],
            layout.mask[0]))
        np.testing.assert_allclose(got, want, atol=1e-4)


def test_heavy_threshold_floor_enforced():
    # threshold < ELL_WIDTH would silently drop kept-slot updates after a
    # heavy run (pos inflated past rank); both builders must refuse it
    cat = np.zeros((1, 8, 2), np.int32)
    with pytest.raises(ValueError, match="heavy_threshold"):
        ell_layout(cat, 128 * 128, heavy_threshold=64)
    with pytest.raises(ValueError, match="heavy_threshold"):
        ell_layout_device(jnp.asarray(cat), 128 * 128, heavy_threshold=64)


class TestSparseUpdateEll:
    def test_step_matches_xla_oracle(self):
        from flink_ml_tpu.models.common.losses import logistic_loss
        from flink_ml_tpu.models.common.sgd import (
            SGDConfig, _sparse_update, _sparse_update_ell)

        rng = np.random.default_rng(6)
        d, batch, nnz = 128 * 128, 96, 7
        idx = rng.integers(0, d, size=(1, batch, nnz)).astype(np.int32)
        idx[:, ::3, 0] = 505           # duplicate hot-ish index w/ values
        vals = rng.normal(size=(1, batch, nnz)).astype(np.float32)
        y = rng.integers(0, 2, size=batch).astype(np.float32)
        wb = np.ones(batch, np.float32)
        layout = ell_layout(idx, d, values=vals)
        assert layout.val is not None and layout.ovf_val is not None
        assert layout.heavy_cnt.dtype == jnp.float32

        for cfg in (SGDConfig(learning_rate=0.3, tol=0),
                    SGDConfig(learning_rate=0.3, reg=0.04,
                              elastic_net=0.5, tol=0)):
            params = {"w": jnp.asarray(rng.normal(size=d), jnp.float32),
                      "b": jnp.asarray(-0.2, jnp.float32)}
            want, want_loss = _sparse_update(logistic_loss, cfg)(
                params, jnp.asarray(idx[0]), jnp.asarray(vals[0]),
                jnp.asarray(y), jnp.asarray(wb))
            got, got_loss = _sparse_update_ell(
                logistic_loss, cfg, backend="xla")(
                params,
                layout.src[0], layout.pos[0], layout.mask[0],
                layout.val[0], layout.ovf_idx[0], layout.ovf_src[0],
                layout.ovf_val[0], layout.heavy_idx[0],
                layout.heavy_cnt[0], jnp.asarray(y), jnp.asarray(wb))
            np.testing.assert_allclose(np.asarray(got_loss),
                                       np.asarray(want_loss), rtol=1e-6)
            np.testing.assert_allclose(np.asarray(got["w"]),
                                       np.asarray(want["w"]), atol=1e-5)

    def test_heavy_values_route_dense(self):
        from flink_ml_tpu.models.common.losses import logistic_loss
        from flink_ml_tpu.models.common.sgd import (
            SGDConfig, _sparse_update, _sparse_update_ell)

        rng = np.random.default_rng(7)
        d, batch, nnz = 128 * 128, 300, 3
        idx = rng.integers(0, d, size=(1, batch, nnz)).astype(np.int32)
        idx[:, :, 0] = 999             # 300 slots > threshold
        vals = rng.normal(size=(1, batch, nnz)).astype(np.float32)
        y = rng.integers(0, 2, size=batch).astype(np.float32)
        wb = np.ones(batch, np.float32)
        layout = ell_layout(idx, d, values=vals, heavy_threshold=256)
        assert 999 in np.asarray(layout.heavy_idx[0])
        cfg = SGDConfig(learning_rate=0.5, tol=0)
        params = {"w": jnp.zeros(d, jnp.float32),
                  "b": jnp.zeros((), jnp.float32)}
        want, _ = _sparse_update(logistic_loss, cfg)(
            params, jnp.asarray(idx[0]), jnp.asarray(vals[0]),
            jnp.asarray(y), jnp.asarray(wb))
        got, _ = _sparse_update_ell(logistic_loss, cfg, backend="xla")(
            params,
            layout.src[0], layout.pos[0], layout.mask[0], layout.val[0],
            layout.ovf_idx[0], layout.ovf_src[0], layout.ovf_val[0],
            layout.heavy_idx[0], layout.heavy_cnt[0],
            jnp.asarray(y), jnp.asarray(wb))
        np.testing.assert_allclose(np.asarray(got["w"]),
                                   np.asarray(want["w"]), atol=1e-5)

    def test_device_builder_values_agree_with_host(self):
        from flink_ml_tpu.models.common.losses import logistic_loss
        from flink_ml_tpu.models.common.sgd import (
            SGDConfig, _sparse_update_ell)

        rng = np.random.default_rng(9)
        d, batch, nnz = 128 * 128, 400, 5
        idx = rng.integers(0, d, size=(2, batch, nnz)).astype(np.int32)
        # 400 occurrences of idx 31: > threshold 128 -> HEAVY value sums;
        # 200 of idx 33 (same table row as 31): not heavy, > ELL_WIDTH
        # entries in row 0 -> real OVERFLOW values
        idx[:, :, 0] = 31
        idx[:, ::2, 1] = 33
        vals = rng.normal(size=(2, batch, nnz)).astype(np.float32)
        host = ell_layout(idx, d, values=vals, heavy_threshold=256)
        dev = ell_layout_device(jnp.asarray(idx), d, ovf_cap=512,
                                values=jnp.asarray(vals),
                                heavy_threshold=256)
        assert 31 in np.asarray(host.heavy_idx[0])
        assert float(np.abs(np.asarray(host.ovf_val)).sum()) > 0
        # grid fields match exactly; overflow/heavy capacities differ by
        # construction, so compare the applied UPDATE instead
        for f in ("src", "pos", "mask", "val"):
            np.testing.assert_allclose(
                np.asarray(getattr(host, f)),
                np.asarray(getattr(dev, f)), atol=1e-6, err_msg=f)
        y = rng.integers(0, 2, size=batch).astype(np.float32)
        wb = np.ones(batch, np.float32)
        cfg = SGDConfig(learning_rate=0.4, tol=0)
        upd = _sparse_update_ell(logistic_loss, cfg, backend="xla")
        outs = []
        for L in (host, dev):
            params = {"w": jnp.zeros(d, jnp.float32),
                      "b": jnp.zeros((), jnp.float32)}
            got, _ = upd(params,
                         L.src[0], L.pos[0], L.mask[0], L.val[0],
                         L.ovf_idx[0], L.ovf_src[0], L.ovf_val[0],
                         L.heavy_idx[0], L.heavy_cnt[0],
                         jnp.asarray(y), jnp.asarray(wb))
            outs.append(np.asarray(got["w"]))
        np.testing.assert_allclose(outs[0], outs[1], atol=1e-6)


def test_sharded_ell_fit_matches_single_device_oracle(monkeypatch):
    """VERDICT r3 task 4: the data-axis-sharded ELL path (device-local
    grids + psum) must reproduce the single-device fit exactly (up to f32
    partial-sum order) on the virtual 8-device CPU mesh."""
    from flink_ml_tpu.models.common import sgd as S
    from flink_ml_tpu.models.common.losses import LOSSES
    from flink_ml_tpu.parallel.mesh import device_mesh

    rng = np.random.default_rng(7)
    n_dev = 8
    batch = 4 * n_dev
    n, nd, nc, d = 8 * batch, 3, 2, 128 * 128
    dense = rng.normal(size=(n, nd)).astype(np.float32)
    cat = rng.integers(nd, d, size=(n, nc)).astype(np.int32)
    y = (dense[:, 0] + 0.3 > 0).astype(np.float64)
    cfg = S.SGDConfig(learning_rate=0.3, max_epochs=3,
                      global_batch_size=batch, tol=0, seed=0,
                      reg=0.01, elastic_net=0.5)

    # force the ELL plan on CPU (the planner itself requires TPU); the
    # XLA twin of the kernel runs under shard_map
    monkeypatch.setattr(S, "plan_mixed_impl", lambda *a, **k: "ell")
    mesh8 = device_mesh({"data": n_dev})
    state_s, log_s = S.sgd_fit_mixed(LOSSES["logistic"], dense, cat, y,
                                     None, d, cfg, mesh=mesh8)
    assert state_s.planned_impl == "ell"

    monkeypatch.setattr(S, "plan_mixed_impl", lambda *a, **k: "xla")
    mesh1 = device_mesh({"data": 1}, devices=jax.devices()[:1])
    state_1, log_1 = S.sgd_fit_mixed(LOSSES["logistic"], dense, cat, y,
                                     None, d, cfg, mesh=mesh1)
    np.testing.assert_allclose(state_s.coefficients, state_1.coefficients,
                               atol=1e-5)
    np.testing.assert_allclose(log_s, log_1, atol=1e-6)
    assert log_s[-1] < log_s[0]


def test_plan_mixed_impl_admits_data_axis_meshes(monkeypatch):
    """plan_mixed_impl returns "ell" for a single-process data-axis mesh
    when the caller opts in (sgd_fit_mixed), and keeps the XLA fallback
    for single-device-shaped ELL wirings (the streaming fit)."""
    from flink_ml_tpu.models.common import sgd as S
    from flink_ml_tpu.parallel.mesh import device_mesh

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    d = 1 << 20
    mesh8 = device_mesh({"data": 8})
    assert S.plan_mixed_impl(d, mesh8, 32, allow_sharded=True) == "ell"
    assert S.plan_mixed_impl(d, mesh8, 32) == "xla"
    # model-axis meshes never take the data-sharded ELL route
    mesh_mp = device_mesh({"data": 4, "model": 2})
    assert S.plan_mixed_impl(d, mesh_mp, 32, allow_sharded=True) == "xla"
    # budget still enforced per device
    assert S.plan_mixed_impl(d, mesh8, 1 << 15, allow_sharded=True) == "xla"


def test_sharded_ell_sparse_fit_matches_single_device_oracle(monkeypatch):
    """Values-aware (indices, values) twin of the sharded-ELL oracle:
    device-local grids + psum must reproduce the single-device sparse fit
    on the 8-device CPU mesh."""
    from flink_ml_tpu.models.common import sgd as S
    from flink_ml_tpu.models.common.losses import LOSSES
    from flink_ml_tpu.parallel.mesh import device_mesh

    rng = np.random.default_rng(9)
    n_dev = 8
    batch = 4 * n_dev
    n, nnz, d = 8 * batch, 4, 128 * 128
    idx = rng.integers(0, d, size=(n, nnz)).astype(np.int32)
    vals = rng.normal(size=(n, nnz)).astype(np.float32)
    y = (vals[:, 0] > 0).astype(np.float64)
    cfg = S.SGDConfig(learning_rate=0.3, max_epochs=3,
                      global_batch_size=batch, tol=0, seed=0, reg=0.01)

    monkeypatch.setattr(S, "plan_mixed_impl", lambda *a, **k: "ell")
    mesh8 = device_mesh({"data": n_dev})
    state_s, log_s = S.sgd_fit_sparse(LOSSES["logistic"], idx, vals, y,
                                      None, d, cfg, mesh=mesh8)
    assert state_s.planned_impl == "ell"

    monkeypatch.setattr(S, "plan_mixed_impl", lambda *a, **k: "xla")
    mesh1 = device_mesh({"data": 1}, devices=jax.devices()[:1])
    state_1, log_1 = S.sgd_fit_sparse(LOSSES["logistic"], idx, vals, y,
                                      None, d, cfg, mesh=mesh1)
    np.testing.assert_allclose(state_s.coefficients, state_1.coefficients,
                               atol=1e-5)
    np.testing.assert_allclose(log_s, log_1, atol=1e-6)
    assert log_s[-1] < log_s[0]


def test_native_layout_builder_matches_numpy():
    """native/ell_layout.cpp (counting-sort, ~13x the numpy builder at
    product shape) must reproduce the numpy builder exactly: grids,
    overflow order, heavy routing, sentinel handling, forced-cap raises.
    Heavy f32 VALUE sums may differ in summation order only."""
    import flink_ml_tpu.ops.ell_scatter as E

    lib = E._native_ell()
    if lib is None:
        pytest.skip("native ell_layout unavailable (no toolchain)")

    def both(cat, d, values=None, **kw):
        nat = E.ell_layout(cat, d, values=values, device=False, **kw)
        E._ELL_NATIVE, E._ELL_NATIVE_TRIED = None, True   # force numpy
        try:
            ref = E.ell_layout(cat, d, values=values, device=False, **kw)
        finally:
            E._ELL_NATIVE_TRIED = False
        return nat, ref

    def check(cat, d, values=None, **kw):
        nat, ref = both(cat, d, values=values, **kw)
        for f in ("src", "pos", "mask", "ovf_idx", "ovf_src", "heavy_idx",
                  "need_ovf", "need_heavy"):
            np.testing.assert_array_equal(
                np.asarray(getattr(nat, f)), np.asarray(getattr(ref, f)),
                err_msg=f)
        if values is None:
            np.testing.assert_array_equal(np.asarray(nat.heavy_cnt),
                                          np.asarray(ref.heavy_cnt))
        else:
            np.testing.assert_allclose(np.asarray(nat.heavy_cnt),
                                       np.asarray(ref.heavy_cnt), atol=1e-4)
            np.testing.assert_array_equal(np.asarray(nat.val),
                                          np.asarray(ref.val))
            np.testing.assert_array_equal(np.asarray(nat.ovf_val),
                                          np.asarray(ref.ovf_val))

    rng = np.random.default_rng(0)
    d = 128 * 128
    check(rng.integers(0, d, size=(3, 96, 5)).astype(np.int32), d)

    # heavy + overflow flood + a second light index sharing the row
    cat2 = rng.integers(0, d, size=(2, 700, 4)).astype(np.int32)
    cat2[:, :, 0] = 777
    cat2[:, ::2, 1] = 778
    check(cat2, d)

    # sentinel padding rows drop out of the layout
    cat3 = rng.integers(0, d, size=(2, 64, 4)).astype(np.int32)
    cat3[:, 50:, :] = d
    check(cat3, d)

    # values variant (sgd_fit_sparse's layout)
    check(cat2, d, values=rng.normal(size=cat2.shape).astype(np.float32))

    # forced caps raise identically on both paths
    cat4 = cat2.copy()
    cat4[:, :, 1] = 9999   # two heavy indices
    for forced in ({"pad_heavy_cap": 1}, {"pad_ovf_cap": 8}):
        with pytest.raises(ValueError, match="forced cap"):
            E.ell_layout(cat2 if "pad_ovf_cap" in forced else cat4, d,
                         device=False, **forced)
        E._ELL_NATIVE, E._ELL_NATIVE_TRIED = None, True
        try:
            with pytest.raises(ValueError, match="forced cap"):
                E.ell_layout(cat2 if "pad_ovf_cap" in forced else cat4, d,
                             device=False, **forced)
        finally:
            E._ELL_NATIVE_TRIED = False

    # forced caps that fit produce exact forced shapes
    nat, ref = both(cat2, d, pad_ovf_cap=2048, pad_heavy_cap=4)
    assert nat.ovf_idx.shape == ref.ovf_idx.shape == (2, 2048)
    assert nat.heavy_idx.shape == ref.heavy_idx.shape == (2, 4)


def test_fused_gather_kernel_matches_twin_interpret():
    """ell_scatter_apply_fused (EXPERIMENTAL r4: u-gather inside the
    kernel via one-hot MXU contraction) must equal gather-then-apply in
    interpret mode, including pad slots (src == batch -> r_ext zero pad)."""
    from flink_ml_tpu.ops.ell_scatter import ell_scatter_apply_fused

    rng = np.random.default_rng(3)
    d, batch, nnz = 128 * 128, 96, 7
    cat = rng.integers(0, d, size=(1, batch, nnz)).astype(np.int32)
    lay = ell_layout(cat, d, device=False)
    r = rng.normal(size=batch).astype(np.float32)
    r_ext = np.concatenate([r, np.zeros(256 - batch % 256, np.float32)])
    w0 = rng.normal(size=d).astype(np.float32)
    lr = 0.35
    got = np.asarray(ell_scatter_apply_fused(
        jnp.asarray(w0), jnp.asarray(r_ext), jnp.asarray(lay.src[0]),
        jnp.asarray(lay.pos[0]), jnp.asarray(lay.mask[0]), lr=lr,
        interpret=True))
    u = (-lr) * r_ext[np.asarray(lay.src[0])]
    want = np.asarray(ell_scatter_apply_xla(
        jnp.asarray(w0), jnp.asarray(u), lay.pos[0], lay.mask[0]))
    np.testing.assert_allclose(got, want, atol=1e-5)

def test_margin_kernel_matches_direct_gather_interpret():
    """ell_margin_xla / ell_margin_fused (r4: forward half of the ELL
    plan) must reproduce sum_j v_j * w[idx_j] exactly when the whole
    batch fits the grid, for both the implicit-1.0 mixed layout and the
    values-aware sparse layout; the pad region (slot/ovf pads carry
    src == batch) is discarded by the [:batch] slice."""
    from flink_ml_tpu.ops.ell_scatter import ell_margin_fused, ell_margin_xla

    rng = np.random.default_rng(11)
    d, batch, nnz, m_len = 128 * 128, 96, 7, 256
    cat = rng.integers(0, d, size=(1, batch, nnz)).astype(np.int32)
    w = rng.normal(size=d).astype(np.float32)
    lay = ell_layout(cat, d, device=False)
    want = w[cat[0]].sum(axis=1)
    got = np.asarray(ell_margin_xla(
        jnp.asarray(w), jnp.asarray(lay.src[0]), jnp.asarray(lay.pos[0]),
        jnp.asarray(lay.mask[0]), m_len))
    np.testing.assert_allclose(got[:batch], want, atol=1e-4)
    got_f = np.asarray(ell_margin_fused(
        jnp.asarray(w), jnp.asarray(lay.src[0]), jnp.asarray(lay.pos[0]),
        jnp.asarray(lay.mask[0]), m_len=m_len, interpret=True))
    np.testing.assert_allclose(got_f[:batch], want, atol=1e-4)

    vals = rng.normal(size=(1, batch, nnz)).astype(np.float32)
    layv = ell_layout(cat, d, values=vals, device=False)
    wantv = (vals[0] * w[cat[0]]).sum(axis=1)
    gotv = np.asarray(ell_margin_fused(
        jnp.asarray(w), jnp.asarray(layv.src[0]), jnp.asarray(layv.pos[0]),
        jnp.asarray(layv.mask[0]), m_len=m_len,
        val=jnp.asarray(layv.val[0]), interpret=True))
    np.testing.assert_allclose(gotv[:batch], wantv, atol=1e-4)


def test_margin_decomposition_with_overflow_and_heavy():
    """The three-way margin decomposition (grid + overflow + heavy) must
    be exact when slots spill and a heavy index exists — the sgd helper's
    algebra, driven directly: a skewed batch where one index repeats past
    HEAVY_THRESHOLD and one row overflows its 128 slots."""
    from flink_ml_tpu.ops.ell_scatter import ell_margin_xla

    rng = np.random.default_rng(12)
    d, batch, nnz = 128 * 128, 1024, 8
    cat = rng.integers(0, d, size=(1, batch, nnz)).astype(np.int32)
    cat[0, :, 0] = 777            # heavy: 1024 > HEAVY_THRESHOLD slots
    cat[0, :200, 1] = 128 * 5 + np.arange(200) % 3  # row 5 overflows
    w = rng.normal(size=d).astype(np.float32)
    lay = ell_layout(cat, d, device=False)
    assert int(np.asarray(lay.need_heavy).max()) >= 1
    assert int(np.asarray(lay.need_ovf).max()) >= 1
    m_len = 1024 + 256
    mext = np.asarray(ell_margin_xla(
        jnp.asarray(w), jnp.asarray(lay.src[0]), jnp.asarray(lay.pos[0]),
        jnp.asarray(lay.mask[0]), m_len))
    ovf = np.zeros(m_len, np.float32)
    np.add.at(ovf, np.asarray(lay.ovf_src[0]),
              w[np.asarray(lay.ovf_idx[0])])
    margin = (mext + ovf)[:batch] + (
        w[np.asarray(lay.heavy_idx[0])]
        @ np.asarray(lay.heavy_cnt[0]).astype(np.float32))
    want = w[cat[0]].sum(axis=1)
    np.testing.assert_allclose(margin, want, rtol=1e-5, atol=1e-4)


def test_trim_overflow_preserves_update_exactly():
    """trim_overflow slices the overflow arrays to measured need; its
    exactness rests on every builder front-compacting real entries, so
    assert the trimmed layout yields the IDENTICAL update as the full
    one (any dropped real slot would move the overflow scatter), for
    both the host and device builders."""
    from flink_ml_tpu.models.common.losses import logistic_loss
    from flink_ml_tpu.models.common.sgd import SGDConfig, _mixed_update_ell
    from flink_ml_tpu.ops.ell_scatter import ell_layout_device

    rng = np.random.default_rng(17)
    d, batch, nnz = 128 * 128, 1024, 8
    cat = rng.integers(0, d, size=(1, batch, nnz)).astype(np.int32)
    cat[0, :300, 1] = 128 * 7 + np.arange(300) % 5   # row 7 spills
    y = rng.integers(0, 2, size=batch).astype(np.float32)
    wb = np.ones(batch, np.float32)
    dense = rng.normal(size=(batch, 3)).astype(np.float32)
    upd = _mixed_update_ell(logistic_loss,
                            SGDConfig(learning_rate=0.4, tol=0),
                            backend="xla")
    for builder in ("host", "device"):
        lay = (ell_layout(cat, d, pad_ovf_cap=2048)
               if builder == "host"
               else ell_layout_device(jnp.asarray(cat), d, ovf_cap=2048))
        trimmed = lay.assert_capacities().trim_overflow()
        need = int(np.asarray(lay.need_ovf).max())
        assert need > 0, "test data must actually spill"
        assert trimmed.ovf_idx.shape[1] < lay.ovf_idx.shape[1]
        assert trimmed.ovf_idx.shape[1] >= need
        outs = []
        for L in (lay, trimmed):
            params = {"w": jnp.zeros((d,), jnp.float32),
                      "b": jnp.zeros((), jnp.float32)}
            got, _ = upd(params, jnp.asarray(dense),
                         jnp.asarray(L.src[0]), jnp.asarray(L.pos[0]),
                         jnp.asarray(L.mask[0]), jnp.asarray(L.ovf_idx[0]),
                         jnp.asarray(L.ovf_src[0]),
                         jnp.asarray(L.heavy_idx[0]),
                         jnp.asarray(L.heavy_cnt[0]),
                         jnp.asarray(y), jnp.asarray(wb))
            outs.append(np.asarray(got["w"]))
        np.testing.assert_array_equal(outs[0], outs[1], err_msg=builder)
