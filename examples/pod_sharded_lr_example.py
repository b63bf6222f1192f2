"""Pod-posture LogisticRegression: the same Criteo-shaped fit under the
three mesh layouts the trainer plans (run on any 8-device setting — a
v5e-8 pod, or this script's virtual CPU mesh):

- pure data axis: batch sharded, weight replicated; on TPU the
  categorical scatter runs the data-sharded ELL kernel (device-local
  grids + one psum — ``sgd._mixed_update_ell_sharded``).
- dp x model: the weight ITSELF shards over 'model' (the 2^24+
  hash-space posture — hash spaces that must never replicate).
- single device: the classic layout every result must match.

All three produce the same coefficients (the oracle stance the test
suite enforces); what changes is where HBM and the scatter work live.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def main() -> None:
    import jax

    # On a real 8-chip pod set FLINK_ML_TPU_POD=1 to keep the TPU
    # backend; default is the 8-device virtual CPU mesh.
    if not os.environ.get("FLINK_ML_TPU_POD"):
        from flink_ml_tpu.utils.backend import force_virtual_cpu

        force_virtual_cpu(8)

    import numpy as np

    from flink_ml_tpu.models.common.losses import LOSSES
    from flink_ml_tpu.models.common.sgd import SGDConfig, sgd_fit_mixed
    from flink_ml_tpu.parallel.mesh import device_mesh

    rng = np.random.default_rng(0)
    n, nd, nc, d = 4096, 13, 26, 1 << 18
    dense = rng.normal(size=(n, nd)).astype(np.float32)
    cat = rng.integers(nd, d, size=(n, nc)).astype(np.int32)
    y = (dense[:, 0] + 0.2 > 0).astype(np.float64)
    cfg = SGDConfig(learning_rate=0.5, max_epochs=4, tol=0,
                    global_batch_size=512)

    results = {}
    for name, axes in [
        ("data x8", {"data": 8}),
        ("dp4 x model2", {"data": 4, "model": 2}),
        ("single device", {"data": 1}),
    ]:
        devs = jax.devices()[: int(np.prod(list(axes.values())))]
        mesh = device_mesh(axes, devices=devs)
        state, log = sgd_fit_mixed(LOSSES["logistic"], dense, cat, y, None,
                                   d, cfg, mesh=mesh)
        results[name] = state
        print(f"{name:15s} planned={state.planned_impl:8s} "
              f"loss {log[0]:.4f} -> {log[-1]:.4f}")

    ref = results["single device"].coefficients
    for name, state in results.items():
        np.testing.assert_allclose(state.coefficients, ref, atol=1e-5)
    print("all three layouts agree to 1e-5")


if __name__ == "__main__":
    main()
