"""Worker process for the two-process jax.distributed test tier (the
MiniCluster analog — see tests/test_distributed_multiprocess.py).

Run as: python tests/_distributed_worker.py <coordinator> <nprocs> <pid> <outdir>

Exercises the real multi-process branches of parallel/distributed.py
(initialize, global_mesh, host_local_to_global, barrier,
broadcast_from_host0, global_to_host_local) plus a data-parallel iterate fit
with the multi-host checkpoint path (process-0 writes + cross-host barrier),
then writes a result JSON the parent compares across processes.
"""

import json
import os
import sys


def main() -> None:
    coord, nprocs, pid, outdir = (sys.argv[1], int(sys.argv[2]),
                                  int(sys.argv[3]), sys.argv[4])
    # Pin a 2-device CPU platform and leave the backend uninitialized, so
    # the distributed runtime owns backend creation (shared helper: handles
    # the teardown-before-config ordering).
    from flink_ml_tpu.utils.backend import force_virtual_cpu

    force_virtual_cpu(2, verify=False)  # jax.distributed owns backend init

    import jax

    from flink_ml_tpu.parallel import distributed as dist

    dist.initialize(coordinator_address=coord, num_processes=nprocs,
                    process_id=pid)
    info = dist.process_info()
    assert info.process_count == nprocs, info
    assert info.global_device_count == 2 * nprocs, info  # 2 cpu devs/host
    assert info.is_coordinator == (pid == 0)

    import jax.numpy as jnp
    import numpy as np

    mesh = dist.global_mesh()
    assert int(mesh.shape["data"]) == 2 * nprocs

    # host-local -> global: host p contributes rows [4p, 4p+4)
    local = np.arange(pid * 4, pid * 4 + 4, dtype=np.float32)
    global_arr = dist.host_local_to_global(local, mesh, axis="data")
    assert not global_arr.is_fully_addressable
    total = float(np.asarray(jax.jit(jnp.sum)(global_arr)))
    assert total == sum(range(4 * nprocs)), total

    # global -> host-local round trip returns this host's own rows
    back = dist.global_to_host_local(global_arr, mesh, axis="data")
    np.testing.assert_array_equal(np.asarray(back), local)

    dist.barrier("after-ingest")
    v = dist.broadcast_from_host0(np.asarray([123.0 + pid]))
    assert float(np.asarray(v)[0]) == 123.0, v  # host 0's value everywhere

    # data-parallel iterate + the multi-host checkpoint path: every epoch
    # all processes enter save_pytree (collective assembly + barrier),
    # process 0 writes, everyone restores the same bytes on resume
    from flink_ml_tpu.iteration import (
        IterationBodyResult,
        IterationConfig,
        iterate,
    )
    from flink_ml_tpu.iteration.checkpoint import CheckpointConfig

    def body(w, epoch, d):
        return IterationBodyResult(w + jnp.sum(d))

    ck = os.path.join(outdir, "ck")  # same dir: the shared-filesystem setup
    res = iterate(body, jnp.asarray(0.0, jnp.float32), global_arr,
                  max_epochs=3, config=IterationConfig(mode="hosted"),
                  checkpoint=CheckpointConfig(ck))
    resumed = iterate(body, jnp.asarray(0.0, jnp.float32), global_arr,
                      max_epochs=5, config=IterationConfig(mode="hosted"),
                      checkpoint=CheckpointConfig(ck), resume=True)

    # multi-host trainer: sgd_fit_mixed over the process-spanning mesh.
    # Every process passes ITS shard; the result must equal a manual
    # single-program update loop over the concatenated global batches
    # (both shards are deterministic functions of pid, so every process
    # can compute the oracle locally).
    from flink_ml_tpu.models.common.losses import LOSSES
    from flink_ml_tpu.models.common.sgd import (
        SGDConfig,
        _mixed_update,
        sgd_fit_mixed,
    )

    def shard(p):
        srng = np.random.default_rng(100 + p)
        nloc, nd, nc, dim = 64, 3, 2, 256
        dense = srng.normal(size=(nloc, nd)).astype(np.float32)
        cat = srng.integers(nd, dim, size=(nloc, nc)).astype(np.int32)
        y = (dense[:, 0] > 0).astype(np.float64)
        return dense, cat, y

    cfg = SGDConfig(learning_rate=0.3, max_epochs=3, tol=0, seed=0,
                    global_batch_size=16)
    dense_l, cat_l, y_l = shard(pid)
    state, log = sgd_fit_mixed(LOSSES["logistic"], dense_l, cat_l, y_l,
                               None, 256, cfg, mesh=mesh)

    # tol > 0 works across hosts: the termination vote is a replicated
    # scalar inside the fused while_loop and num_epochs reads back from
    # the local replica (no cross-host round-trip per epoch)
    state_t, log_t = sgd_fit_mixed(
        LOSSES["logistic"], dense_l, cat_l, y_l, None, 256,
        SGDConfig(learning_rate=0.3, max_epochs=4, tol=1e-6,
                  global_batch_size=16), mesh=mesh)
    assert 1 <= len(log_t) <= 4
    assert np.isfinite(state_t.coefficients).all()

    # oracle: global batch = [proc0 local batch | proc1 local batch] per
    # step, each locally shuffled by the same seed (the layout
    # _plan_epoch_layout_for_mesh produces)
    from flink_ml_tpu.models.common.sgd import prepare_epoch_tensor

    local_batch = 16 // nprocs
    steps = 64 // local_batch
    parts = []
    for p in range(nprocs):
        dp, cp, yp = shard(p)
        perm = np.random.default_rng(cfg.seed).permutation(64)
        parts.append((
            prepare_epoch_tensor(dp, perm, steps, local_batch),
            prepare_epoch_tensor(cp, perm, steps, local_batch),
            prepare_epoch_tensor(yp.astype(np.float32), perm, steps,
                                 local_batch)))
    g_dense = np.concatenate([q[0] for q in parts], axis=1)
    g_cat = np.concatenate([q[1] for q in parts], axis=1)
    g_y = np.concatenate([q[2] for q in parts], axis=1)

    update = jax.jit(_mixed_update(LOSSES["logistic"], cfg))
    params = {"w": jnp.zeros((256,), jnp.float32),
              "b": jnp.zeros((), jnp.float32)}
    ones = np.ones((16,), np.float32)
    oracle_log = []
    for _ in range(cfg.max_epochs):
        losses = []
        for s in range(steps):
            params, value = update(params, g_dense[s], g_cat[s], g_y[s],
                                   ones)
            losses.append(float(value))
        oracle_log.append(float(np.mean(losses)))
    np.testing.assert_allclose(state.coefficients,
                               np.asarray(params["w"], np.float64),
                               atol=1e-5)
    np.testing.assert_allclose(log, oracle_log, atol=1e-5)
    assert log[-1] < log[0]

    # multi-host STREAMING fit (r4): each process feeds its own reader
    # (its own data shard, the parallelism-P source posture); the global
    # batch is the per-step concatenation over processes, assembled by
    # make_array_from_process_local_data inside the prefetch pipeline.
    # Must equal a manual single-program loop over the concatenated
    # batches (deterministic shards => every process computes the oracle).
    from flink_ml_tpu.models.common.sgd import sgd_fit_outofcore

    def stream_shard(p):
        srng = np.random.default_rng(300 + p)
        nloc, nd2, nc2 = 96, 3, 2
        return (srng.normal(size=(nloc, nd2)).astype(np.float32),
                srng.integers(nd2, 256, size=(nloc, nc2)).astype(np.int32),
                (srng.normal(size=nloc) > 0).astype(np.float32))

    def make_stream_reader():
        d_l, c_l, y_loc = stream_shard(pid)
        return iter([{"fd": d_l[i:i + 32], "fi": c_l[i:i + 32],
                      "label": y_loc[i:i + 32]} for i in range(0, 96, 32)])

    scfg = SGDConfig(learning_rate=0.4, max_epochs=2, tol=0)
    st_state, st_log = sgd_fit_outofcore(
        LOSSES["logistic"], make_stream_reader, num_features=256,
        config=scfg, mesh=mesh, dense_key="fd", indices_key="fi")
    assert st_state.planned_impl == "xla-stream"

    # cross-process SHARDED ELL streaming (r4): per-host decode builds
    # its own devices' layout stacks; forced plan (the planner is
    # TPU-gated) runs the kernel's XLA twin through the full multi-host
    # wiring.  Must equal the xla-stream fit above... on the same data
    # but the ELL layout needs an ELL-supported hash space, so rerun
    # both paths at d=128*128 and compare against each other.
    from flink_ml_tpu.models.common import sgd as S

    d_ell = 128 * 128

    def make_stream_reader_ell():
        d_l, c_l, y_loc = stream_shard(pid)
        c_big = (c_l.astype(np.int64) * 131) % (d_ell - 3) + 3
        return iter([{"fd": d_l[i:i + 32],
                      "fi": c_big[i:i + 32].astype(np.int32),
                      "label": y_loc[i:i + 32]} for i in range(0, 96, 32)])

    real_plan = S.plan_mixed_impl
    S.plan_mixed_impl = lambda *a, **k: "ell"
    try:
        ell_state, ell_log = sgd_fit_outofcore(
            LOSSES["logistic"], make_stream_reader_ell, num_features=d_ell,
            config=scfg, mesh=mesh, dense_key="fd", indices_key="fi")
    finally:
        S.plan_mixed_impl = real_plan
    assert ell_state.planned_impl == "ell-stream"
    xla_state, xla_log = sgd_fit_outofcore(
        LOSSES["logistic"], make_stream_reader_ell, num_features=d_ell,
        config=scfg, mesh=mesh, dense_key="fd", indices_key="fi")
    np.testing.assert_allclose(ell_state.coefficients,
                               xla_state.coefficients, atol=1e-5)
    np.testing.assert_allclose(ell_log, xla_log, atol=1e-6)

    st_update = jax.jit(_mixed_update(LOSSES["logistic"], scfg))
    sp = {"w": jnp.zeros((256,), jnp.float32),
          "b": jnp.zeros((), jnp.float32)}
    shards = [stream_shard(p) for p in range(nprocs)]
    s_log = []
    for _ in range(scfg.max_epochs):
        losses = []
        for i in range(0, 96, 32):
            gd = np.concatenate([sh[0][i:i + 32] for sh in shards])
            gc = np.concatenate([sh[1][i:i + 32] for sh in shards])
            gy = np.concatenate([sh[2][i:i + 32] for sh in shards])
            sp, v = st_update(sp, gd, gc, gy,
                              np.ones(len(gy), np.float32))
            losses.append(float(v))
        s_log.append(float(np.mean(losses)))
    np.testing.assert_allclose(st_state.coefficients,
                               np.asarray(sp["w"], np.float64), atol=1e-5)
    np.testing.assert_allclose(st_log, s_log, atol=1e-5)

    # dp x model over 2 OS processes (VERDICT r3 task 5): the weight
    # itself sharded over the 'model' axis with the same shards living on
    # BOTH hosts' devices — the final fetch is a cross-process allgather
    # of the model axis (mesh.fetch_replicated).  Must equal the
    # data-parallel fit above exactly.
    from flink_ml_tpu.parallel.mesh import device_mesh

    dpmp_mesh = device_mesh({"data": nprocs, "model": 2},
                            devices=jax.devices())
    state_mp, log_mp = sgd_fit_mixed(LOSSES["logistic"], dense_l, cat_l,
                                     y_l, None, 256, cfg, mesh=dpmp_mesh)
    assert state_mp.planned_impl == "sharded"
    np.testing.assert_allclose(state_mp.coefficients, state.coefficients,
                               atol=1e-5)
    np.testing.assert_allclose(log_mp, log, atol=1e-5)

    # multi-host STREAMING Wide&Deep (r4): each process streams its own
    # shard through fit_outofcore over the process-spanning mesh; the
    # fitted params must equal a manual single-program Adam loop over
    # the concatenated per-step batches with the same init.
    from flink_ml_tpu.models.recommendation.widedeep import (
        WideDeep,
        _make_train_ops,
        _validate_cat_ids,
        init_params,
    )

    wd_vocab = [9, 5]

    def wd_shard(p):
        srng = np.random.default_rng(500 + p)
        nloc = 64
        return (srng.normal(size=(nloc, 3)).astype(np.float32),
                np.stack([srng.integers(0, v, size=nloc)
                          for v in wd_vocab], 1).astype(np.int32),
                srng.integers(0, 2, size=nloc).astype(np.float32))

    def wd_reader():
        wdn, wcn, wyn = wd_shard(pid)
        return iter([{"denseFeatures": wdn[i:i + 16],
                      "catFeatures": wcn[i:i + 16],
                      "label": wyn[i:i + 16]} for i in range(0, 64, 16)])

    wd_est = (WideDeep().set_vocab_sizes(wd_vocab).set_max_iter(2)
              .set_seed(0))
    wd_model = wd_est.fit_outofcore(wd_reader, mesh=mesh)

    wd_oracle = init_params(np.random.default_rng(1), 3, wd_vocab, 8,
                            (64, 32))
    wd_step, wd_opt = _make_train_ops(wd_oracle, 1e-2, False)
    wd_step = jax.jit(wd_step)
    wd_shards = [wd_shard(p) for p in range(nprocs)]
    import jax.numpy as _jnp
    wd_oracle = jax.tree_util.tree_map(_jnp.asarray, wd_oracle)
    for _ in range(2):
        for i in range(0, 64, 16):
            gdn = np.concatenate([s[0][i:i + 16] for s in wd_shards])
            gcn = np.concatenate(
                [_validate_cat_ids(s[1][i:i + 16], wd_vocab)
                 for s in wd_shards])
            gyn = np.concatenate([s[2][i:i + 16] for s in wd_shards])
            wd_oracle, wd_opt, _ = wd_step(
                wd_oracle, wd_opt, gdn, gcn, gyn,
                np.ones(len(gyn), np.float32))
    for a, b in zip(jax.tree_util.tree_leaves(wd_model._params),
                    jax.tree_util.tree_leaves(jax.device_get(wd_oracle))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)

    # multi-host KMeans: each host holds a different half of 4 separated
    # clusters; the replicated centroids must recover all 4 means on BOTH
    # hosts (host 0's local selection seeds the global init).
    from flink_ml_tpu import Table
    from flink_ml_tpu.models.clustering import KMeans
    from flink_ml_tpu.parallel.mesh import use_mesh

    # The distributed-correctness assert: multi-host KMeans must equal a
    # manual single-program Lloyd's run on the concatenated shards with
    # the same init (clustering QUALITY is a property of Lloyd's, not of
    # the distribution — only exact equivalence catches sharding bugs).
    centers = np.asarray([[10.0, 0.0], [-10.0, 0.0],
                          [0.0, 10.0], [0.0, -10.0]], np.float32)

    def kshard(p):
        srng = np.random.default_rng(7 + p)
        return np.concatenate([
            c + srng.normal(scale=0.3, size=(16, 2)).astype(np.float32)
            for c in centers])

    pts = kshard(pid)
    with use_mesh(mesh):
        km_model = (KMeans().set_k(4).set_max_iter(20).set_seed(3)
                    .fit(Table({"features": pts})))
    got = np.asarray(km_model.get_model_data()[0]["centroids"][0])

    from flink_ml_tpu.distance import DistanceMeasure
    from flink_ml_tpu.models.clustering.kmeans import (
        kmeans_epoch_step,
        select_random_centroids,
    )

    all_pts = np.concatenate([kshard(p) for p in range(nprocs)])
    oracle_c = jnp.asarray(select_random_centroids(kshard(0), 4, 3))
    body = kmeans_epoch_step(DistanceMeasure.get_instance("euclidean"), 4)
    omask = jnp.ones((len(all_pts),), jnp.float32)
    opts = jnp.asarray(all_pts)
    for _ in range(20):
        oracle_c = body(oracle_c, 0, (opts, omask)).feedback
    np.testing.assert_allclose(got, np.asarray(oracle_c), atol=1e-4)

    # hybrid dcn x data mesh over the 2 REAL processes: hierarchical
    # gradient reduction — exact reduce_scatter over each host's local
    # 'data' axis, the (compressed) all-reduce over the cross-host 'dcn'
    # axis, gather back — asserted against the single-program oracle
    # (inputs are deterministic in pid, so every process computes it).
    from jax.sharding import PartitionSpec as P

    from flink_ml_tpu.parallel import grad_reduce as GR
    from flink_ml_tpu.parallel.collectives import shard_map_fn
    from flink_ml_tpu.parallel.grad_reduce import GradReduceConfig
    from flink_ml_tpu.parallel.mesh import fetch_replicated, put_sharded

    hmesh = dist.hybrid_mesh({"data": 2})     # (dcn=2 hosts, data=2 devs)
    assert dict(hmesh.shape) == {"dcn": 2, "data": 2}
    d_red, n_red = 32, 4
    g_all = np.random.default_rng(900).normal(
        size=(n_red, d_red)).astype(np.float32)
    dev_spec = P(("dcn", "data"), None)
    g_stack = put_sharded(g_all[pid * 2:(pid + 1) * 2], hmesh, dev_spec)

    def run_reduce(cfg_gr):
        state = GR.init_state(cfg_gr, {"g": np.zeros((d_red,), np.float32)},
                              n_red)
        state = jax.tree_util.tree_map(
            lambda a: put_sharded(np.asarray(a)[pid * 2:(pid + 1) * 2],
                                  hmesh, dev_spec), state)

        def body(g, st):
            red, new_st = GR.reduce_gradients(
                {"g": g[0]}, GR.squeeze_state(st), cfg_gr)
            return red["g"][None], GR.unsqueeze_state(new_st)

        fn = shard_map_fn(body, hmesh, in_specs=(dev_spec, dev_spec),
                          out_specs=(dev_spec, dev_spec))
        red, _ = jax.jit(fn)(g_stack, state)
        red = fetch_replicated(red)          # (n_red, d) — rows identical
        np.testing.assert_array_equal(red, np.broadcast_to(red[:1],
                                                           red.shape))
        return red[0]

    # exact hierarchical == plain global sum (up to f32 order)
    np.testing.assert_allclose(
        run_reduce(GradReduceConfig(mode="exact", axis="data",
                                    dcn_axis="dcn")),
        g_all.sum(0), atol=1e-5)

    # topk hierarchical == the shard-domain EF oracle: each dcn member
    # reduces its host's 2-device group exactly, then sends its per-shard
    # top-k over the dcn hop
    density = 0.25
    shard_len = d_red // 2
    k = max(1, int(shard_len * density))
    expected = np.zeros((d_red,), np.float32)
    for m in range(2):                        # dcn members
        ici_sum = g_all[m * 2:(m + 1) * 2].sum(0)
        for i in range(2):                    # data positions -> shards
            sl = slice(i * shard_len, (i + 1) * shard_len)
            acc = ici_sum[sl]
            order = np.argsort(-np.abs(acc), kind="stable")[:k]
            sent = np.zeros_like(acc)
            sent[order] = acc[order]
            expected[sl] += sent
    np.testing.assert_allclose(
        run_reduce(GradReduceConfig(mode="topk", density=density,
                                    axis="data", dcn_axis="dcn")),
        expected, atol=1e-5)

    out = {
        "pid": pid,
        "grad_reduce_dcn_ok": True,
        "global_devices": info.global_device_count,
        "total": total,
        "final": float(np.asarray(jax.device_get(res.state))),
        "resumed": float(np.asarray(jax.device_get(resumed.state))),
        "mixed_lr_final_loss": float(log[-1]),
        "mixed_lr_w0": float(state.coefficients[0]),
        "kmeans_c00": float(got[0, 0]),
    }
    with open(os.path.join(outdir, f"result_{pid}.json"), "w") as f:
        json.dump(out, f)
    dist.barrier("done")


if __name__ == "__main__":
    main()
