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


# -- four chips: the rows divided over a ``data`` mesh (PR 39) --------------

@pytest.fixture(scope="module")
def four_chips(tpu):
    import jax

    devices = jax.devices()
    if len(devices) < 4:
        pytest.skip(f"needs four chips on one host (chiprun --chips 4); "
                    f"JAX found {len(devices)}")
    return devices[:4]


def _grey_levels(rows: int, seed: int) -> np.ndarray:
    """Whole grey levels 0-255 of 784 pixels: the benchmark's generator
    under ``kmeans_mnist8m``'s own parameters."""
    import json
    import os
    import sys

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from generators import digit_images

    with open(os.path.join(bench, "configs", "kmeans_mnist8m.json")) as f:
        config = json.load(f)
    return digit_images.generate(
        {**config, **config["generator_params"], "rows": rows},
        seed)["features"]


def test_sharded_fit_is_the_one_chip_fit_bit_for_bit(four_chips):
    """4 x 262,144 + 5 rows of 784 grey levels, k 4096, the Pallas plan
    tiled over k on both meshes with the same tiles: every chip's run of
    rows put flat in pieces and laid out on it, the kernel a shard, one
    all-reduce a step.  The sums are of whole levels, so the four partial
    sums are exact in any order and the centroids are the one-chip fit's
    bit for bit; the fit notes its four shards and a second fit reuses the
    program."""
    from flink_ml_tpu import Table
    from flink_ml_tpu.distance import DistanceMeasure
    from flink_ml_tpu.models.clustering.kmeans import KMeans, _fit_plan
    from flink_ml_tpu.obs.trace import tracer
    from flink_ml_tpu.parallel.mesh import device_mesh, use_mesh

    n, d, k = 4 * 262_144 + 5, 784, 4096
    column = _grey_levels(n, 2147483693)
    before = column[::4099].tobytes()
    table = Table({"features": column})
    euclid = DistanceMeasure.get_instance("euclidean")

    def fit(mesh):
        plan = _fit_plan(n, d, k, euclid, mesh)
        tracer.enable()
        try:
            with use_mesh(mesh):
                model = (KMeans().set_k(k).set_seed(5).set_max_iter(4)
                         .fit(table))
            notes = {s.name: s.ids for s in tracer.spans()
                     if s.name in ("fit.arrange", "fit.upload",
                                   "iterate.dispatch.compile")
                     and {"shards", "pieces", "reused"} & set(s.ids)}
        finally:
            tracer.disable()
            tracer.clear()
        (data,) = model.get_model_data()
        return np.asarray(data["centroids"][0]), plan, notes

    one, plan_one, notes_one = fit(device_mesh(devices=four_chips[:1]))
    four, plan_four, notes_four = fit(device_mesh(devices=four_chips))
    assert plan_one.impl == plan_four.impl == "pallas"
    assert (plan_one.block_n, plan_one.k_tile) == (plan_four.block_n,
                                                   plan_four.k_tile)
    assert plan_four.k_tile and n % (4 * plan_four.block_n)
    assert notes_one["fit.arrange"]["shards"] == 1
    assert notes_four["fit.arrange"]["shards"] == 4
    assert notes_four["fit.arrange"]["stats_plan"] == "k_tiled"
    assert np.isfinite(four).all() and four.tobytes() == one.tobytes()
    again, _, notes_again = fit(device_mesh(devices=four_chips))
    assert notes_again["iterate.dispatch.compile"]["reused"] == 1
    assert again.tobytes() == four.tobytes()
    assert column[::4099].tobytes() == before


def test_sharded_put_in_pieces_is_the_host_route_at_10_gb(four_chips):
    """4 x 800,000 + 7 rows of 784 floats (2.5 GB a chip, in seven rounds
    of 4 x 411 MB: a round is 2 GiB at most): the piecewise sharded put
    and the on-device layout against the host pad and one 2-D
    ``put_sharded``, compared where they lie; every chip then holds its
    2.5 GB."""
    import time

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from flink_ml_tpu.models.clustering.kmeans import (
        FitPlan, _pad_points, _put_and_lay_out_sharded)
    from flink_ml_tpu.parallel.mesh import device_mesh, put_sharded

    n, d = 4 * 800_000 + 7, 784
    column = _grey_levels(n, 2147483701)
    mesh = device_mesh(devices=four_chips)
    plan = FitPlan("pallas", 512, 512, "zero", 4096, d, k_tile=512)
    t = time.perf_counter()
    points, mask = _put_and_lay_out_sharded(column, plan, mesh, P("data"))
    jax.block_until_ready((points, mask))
    pieces_s = time.perf_counter() - t
    t = time.perf_counter()
    padded, host_mask = _pad_points(column, mesh, row_multiple=512,
                                    fill="zero")
    want = put_sharded(padded, mesh, P("data"))
    want.block_until_ready()
    host_s = time.perf_counter() - t
    print(f"\nsharded put in pieces {pieces_s:.2f} s, host pad and one 2-D "
          f"put {host_s:.2f} s for {column.nbytes / 1e9:.2f} GB")
    assert points.shape == want.shape and points.sharding == want.sharding
    assert bool(jax.jit(jnp.array_equal)(points, want))
    assert np.asarray(mask).tobytes() == host_mask.tobytes()
    held = [s.data.nbytes for s in points.addressable_shards]
    assert len(held) == 4 and min(held) == points.nbytes // 4
