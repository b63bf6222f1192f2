"""``KMeans.fit`` on the chip, through the route the benchmark's mesh
takes: the float32 rows are put as they are and the device gives them their
layout, the mask and the Pallas plan's zero rows."""

import numpy as np
import pytest

pytestmark = pytest.mark.tpu


def test_fit_pads_on_device_bitexact_vs_host_pad_and_refit_compiles_nothing(
        tpu, rng):
    """2^20 + 5 rows, d 20, k 10: the Pallas plan with a padded tail.
    The centroids equal, bit for bit, those of the parent commit's path
    (its three host copies kept here, then the same fused program), and a
    second fit of the same shapes compiles nothing: its one request, the
    fused program that ``iterate`` jits anew every call, is served by the
    persistent cache."""
    import jax.monitoring
    from jax.sharding import PartitionSpec as P

    from flink_ml_tpu import Table
    from flink_ml_tpu.distance import DistanceMeasure
    from flink_ml_tpu.iteration import IterationConfig, iterate
    from flink_ml_tpu.linalg import stack_vectors
    from flink_ml_tpu.models.clustering.kmeans import (
        KMeans, _fit_plan, kmeans_epoch_step_pallas, select_random_centroids)
    from flink_ml_tpu.parallel.mesh import (
        device_mesh, fetch_replicated, put_sharded, replicate, use_mesh)
    from flink_ml_tpu.utils.backend import count_compiles
    from flink_ml_tpu.utils.padding import pad_rows_with_mask

    n, d, k, seed, max_iter = (1 << 20) + 5, 20, 10, 28, 5
    centers = 4.0 * rng.normal(size=(5, d))
    column = (centers[rng.integers(0, 5, size=n)]
              + rng.normal(size=(n, d))).astype(np.float32)
    before = column.tobytes()
    mesh = device_mesh(devices=[tpu])

    def fit():
        model = (KMeans().set_k(k).set_seed(seed).set_max_iter(max_iter)
                 .fit(Table({"features": column})))
        (data,) = model.get_model_data()
        return np.asarray(data["centroids"][0])

    with use_mesh(mesh):
        plan = _fit_plan(n, d, k, DistanceMeasure.get_instance("euclidean"),
                         mesh)
        assert plan.impl == "pallas" and n % plan.row_multiple
        got = fit()
        hits = []
        jax.monitoring.register_event_listener(
            lambda event, **_: hits.append(event)
            if event == "/jax/compilation_cache/cache_hits" else None)
        with count_compiles() as requests:
            again = fit()
        assert requests() - len(hits) == 0

        host_points = stack_vectors(column)
        host_points = host_points.astype(np.float32)
        padded, mask = pad_rows_with_mask(host_points, plan.row_multiple,
                                          fill=plan.fill)
        result = iterate(
            kmeans_epoch_step_pallas(k, mesh, block_n=plan.block_n,
                                     tie_policy="first"),
            replicate(select_random_centroids(host_points, k, seed), mesh),
            (put_sharded(padded, mesh, P("data")),
             put_sharded(mask, mesh, P("data"))),
            max_epochs=max_iter, config=IterationConfig(mode="fused"))
        expected = np.asarray(fetch_replicated(result.state))

    assert got.tobytes() == expected.tobytes()
    assert again.tobytes() == got.tobytes()
    assert column.tobytes() == before


def test_fit_program_keeps_no_copy_of_the_points(tpu):
    """The fused program of a fit at 2^22 x 20, k 10 (the scan ``iterate``
    builds over the Pallas step), compiled from shapes: the kernel takes
    the rows as the chip lays them out, so the program's temporaries stay
    under the points' own bytes (a lane-padded copy would be 6.4 times
    them)."""
    import jax
    import jax.numpy as jnp

    from flink_ml_tpu.distance import DistanceMeasure
    from flink_ml_tpu.models.clustering.kmeans import (
        _fit_plan, kmeans_epoch_step_pallas)
    from flink_ml_tpu.parallel.mesh import device_mesh

    n, d, k = 1 << 22, 20, 10
    plan = _fit_plan(n, d, k, DistanceMeasure.get_instance("euclidean"),
                     device_mesh(devices=[tpu]))
    assert plan.impl == "pallas" and n % plan.block_n == 0
    body = kmeans_epoch_step_pallas(k, block_n=plan.block_n)

    def run(centroids, data):
        return jax.lax.scan(
            lambda c, epoch: (body(c, epoch, data).feedback, None),
            centroids, jnp.arange(5, dtype=jnp.int32))[0]

    f32 = jnp.float32
    compiled = jax.jit(run).lower(
        jax.ShapeDtypeStruct((k, d), f32),
        (jax.ShapeDtypeStruct((n, d), f32),
         jax.ShapeDtypeStruct((n,), f32))).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < n * d * 4


@pytest.mark.parametrize("fill", ["zero", "first_row"])
def test_rows_on_device_equals_the_host_pad_on_chip(tpu, rng, fill):
    """The layout program at 2^20 + 5 rows (seventeen pieces, the last
    one overlapping): rows, fill rows and mask as the host pad makes
    them, for the Pallas plan's fill and the workset plan's."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from flink_ml_tpu.models.clustering.kmeans import _rows_on_device
    from flink_ml_tpu.parallel.mesh import device_mesh, put_sharded
    from flink_ml_tpu.utils.padding import pad_rows_with_mask

    shape = ((1 << 20) + 5, 20)
    mesh = device_mesh(devices=[tpu])
    pts = rng.normal(size=shape).astype(np.float32)
    got = _rows_on_device(shape, -shape[0] % 8192, fill,
                          NamedSharding(mesh, P("data")))(
        put_sharded(pts.reshape(-1), mesh, P("data")))
    for have, want in zip(got, pad_rows_with_mask(pts, 8192, fill=fill)):
        assert have.shape == want.shape
        assert np.asarray(have).tobytes() == want.tobytes()
