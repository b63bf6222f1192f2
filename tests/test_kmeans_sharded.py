"""``KMeans.fit`` on a one-process mesh whose ``data`` axis has several
devices (PR 39): every device gets its run of the rows flat, in pieces
that are views of the column, and lays it out, pads and masks it itself
(``kmeans.py: _put_and_lay_out_sharded``, ``parallel/mesh.py:
put_sharded_in_pieces``); the step is the kernel a shard and one ``psum``
under the scope ``kmeans.reduce``; the fused program is kept per mesh."""

import os
import re
import sys

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from flink_ml_tpu import Table
from flink_ml_tpu.models.clustering import kmeans as km
from flink_ml_tpu.models.clustering.kmeans import KMeans
from flink_ml_tpu.parallel import mesh as pm
from flink_ml_tpu.parallel.mesh import device_mesh, put_sharded, use_mesh


def _mesh(devices: int):
    return device_mesh(devices=jax.devices()[:devices])


@pytest.fixture
def small_pieces(monkeypatch):
    """Relayout steps of 8 rows and puts of at most 16 rows of 5 floats:
    a few hundred rows take every loop the chip takes at 8.1 M."""
    km._rows_from_pieces.cache_clear()
    monkeypatch.setattr(km, "_RELAYOUT_ROWS", 8)
    monkeypatch.setattr(pm, "PUT_BYTES", 16 * 5 * 4)
    yield
    km._rows_from_pieces.cache_clear()


@pytest.fixture
def handed_over(monkeypatch):
    """Every host array a ``jax.device_put`` is given while the test runs."""
    handed = []
    real_put = jax.device_put

    def recording(x, *args, **kwargs):
        if isinstance(x, np.ndarray):
            handed.append(x)
        return real_put(x, *args, **kwargs)

    monkeypatch.setattr(jax, "device_put", recording)
    return handed


# rows a shard of 2, 4, 8 devices x 16 keeps whole / cuts with a remainder
# / ends in a piece shorter than the rest (and, at 8, in an empty shard)
_ROWS = {"no_remainder": 512, "remainder": 500, "short_last_piece": 299}


@pytest.mark.parametrize("fill", ["zero", "first_row"])
@pytest.mark.parametrize("case", list(_ROWS))
@pytest.mark.parametrize("devices", [2, 4, 8])
def test_sharded_ingest_is_the_host_pad_put_sharded_bit_for_bit(
        small_pieces, handed_over, devices, case, fill):
    n, mesh = _ROWS[case], _mesh(devices)
    column = np.random.default_rng(n).normal(size=(n, 5)).astype(np.float32)
    before = column.tobytes()
    plan = km.FitPlan("xla", None, 16, fill, 4, 5)
    got = km._put_and_lay_out_sharded(column, plan, mesh, P("data"))
    # (past a run's end a piece is empty: nothing to share)
    pieces = [a for a in handed_over if a.ndim == 1 and a.size]
    assert pieces and all(np.shares_memory(a, column) for a in handed_over
                          if a.size)
    assert sum(a.size for a in pieces) == column.size   # each row once
    # the cap is on a round, and a put hands over a relayout step at least
    assert max(a.size for a in pieces) == 8 * 5

    want = km._pad_points(column, mesh, row_multiple=16, fill=fill)
    assert (want[0] is column) == (case == "no_remainder")
    for have, expected in zip(got, want):
        there = put_sharded(expected, mesh, P("data"))
        assert have.sharding == there.sharding
        assert have.shape == expected.shape and have.dtype == expected.dtype
        assert np.asarray(have).tobytes() == expected.tobytes()
        for mine, theirs in zip(have.addressable_shards,
                                there.addressable_shards):
            assert mine.device == theirs.device and mine.index == theirs.index
    assert column.tobytes() == before


def test_sharded_ingest_replicates_a_shard_over_the_meshs_other_axis(
        small_pieces):
    """A ``data`` x ``model`` mesh: every device along ``model`` gets the
    shard's pieces, and the arrays are ``put_sharded``'s."""
    mesh = device_mesh({"data": 4, "model": 2})
    column = np.random.default_rng(3).normal(size=(250, 5)).astype(np.float32)
    plan = km.FitPlan("xla", None, 16, "first_row", 4, 5)
    got = km._put_and_lay_out_sharded(column, plan, mesh, P("data"))
    want = km._pad_points(column, mesh, row_multiple=16, fill="first_row")
    for have, expected in zip(got, want):
        assert have.sharding == put_sharded(expected, mesh, P("data")).sharding
        assert np.asarray(have).tobytes() == expected.tobytes()
        assert len(have.addressable_shards) == 8


def test_put_sharded_in_pieces_rounds_and_views():
    """300 rows in runs of 128 over four devices, 50 rows a put: three
    rounds, the third device's run ends after 44 rows, the fourth's is
    empty; every piece a flat view, a device's pieces its run in order."""
    mesh = _mesh(4)
    rows = np.arange(300 * 3, dtype=np.float32).reshape(300, 3)
    rounds = list(pm.put_sharded_in_pieces(rows, mesh, 128, 50))
    assert [first for first, _ in rounds] == [0, 50, 100]
    for i, device in enumerate(jax.devices()[:4]):
        mine = [pieces[i][0] for _, pieces in rounds]
        assert all(a.devices() == {device} and a.ndim == 1 for a in mine)
        run = rows[i * 128:(i + 1) * 128].reshape(-1)
        assert np.concatenate([np.asarray(a) for a in mine]).tobytes() \
            == run.tobytes()
    assert pm.shard_devices(mesh) == [[d] for d in jax.devices()[:4]]


def test_put_in_rounds_waits_between_rounds_under_the_cap(monkeypatch):
    """Arrays of 40, 40, 40 and 100 bytes under a cap of 100: rounds of
    two, one and one, each waited for before the next is put; every
    array arrives whole on the device as an array of its own."""
    waited = []

    class Put:
        def __init__(self, arr, device):
            self.arr, self.device = arr, device

        def block_until_ready(self):
            waited.append(int(self.arr[0]))

    monkeypatch.setattr(pm, "PUT_BYTES", 100)
    monkeypatch.setattr(pm.jax, "device_put", Put)
    arrays = [np.full(10, i, np.float32) for i in range(3)] + [
        np.full(25, 3, np.float32)]
    out = pm.put_in_rounds(arrays, "dev")
    assert [p.arr is a for p, a in zip(out, arrays)] == [True] * 4
    assert waited == [0, 1, 2] and {p.device for p in out} == {"dev"}
    monkeypatch.undo()
    (got,) = pm.put_in_rounds([arrays[3]], jax.devices()[1])
    assert got.devices() == {jax.devices()[1]}
    np.testing.assert_array_equal(np.asarray(got), arrays[3])


def _grey_levels(rows: int, seed: int = 2147483659) -> np.ndarray:
    """Whole grey levels 0-255 of 784 pixels: the benchmark's generator
    under ``kmeans_mnist8m``'s own parameters."""
    import json

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from generators import digit_images

    with open(os.path.join(bench, "configs", "kmeans_mnist8m.json")) as f:
        config = json.load(f)
    return digit_images.generate(
        {**config, **config["generator_params"], "prototypes": 96, "rows": rows},
        seed)["features"]


def _centroids(model) -> np.ndarray:
    return np.asarray(model.get_model_data()[0]["centroids"][0])


def _steer_to_the_ktiled_kernel(monkeypatch):
    """The plan and the body a TPU takes at k 4096, the interpreter
    standing in for the chip, the tiles pinned (a shard's rows would let
    the plan pick another block, and the scores of a row do not depend on
    which chip holds it only where the tiles are the same)."""
    step = km.kmeans_epoch_step_pallas
    monkeypatch.setattr(
        km, "_fit_plan", lambda n, d, k, measure, mesh, **how:
        km.FitPlan("pallas", 128, 128, "zero", k, d, k_tile=16))
    monkeypatch.setattr(
        km, "kmeans_epoch_step_pallas",
        lambda *a, **kw: step(*a, **{**kw, "interpret": True}))


@pytest.mark.parametrize("body", ["xla", "k_tiled"])
def test_four_device_fit_is_the_one_device_fit_bit_for_bit_on_grey_levels(
        monkeypatch, body):
    """The sums are of whole levels and stay under 2^24, so four partial
    sums added in any order are exact; a row's scores are its own: the
    centroids do not depend on how many chips shared the rows."""
    if body == "k_tiled":
        _steer_to_the_ktiled_kernel(monkeypatch)
    column = _grey_levels(4096 + 5)
    before = column.tobytes()

    def fit(devices):
        with use_mesh(_mesh(devices)):
            return _centroids(KMeans().set_k(64).set_max_iter(5).set_seed(11)
                              .fit(Table({"features": column})))

    one, four = fit(1), fit(4)
    assert four.tobytes() == one.tobytes()
    assert not np.array_equal(one, column[np.random.default_rng(11)
                                          .permutation(len(column))[:64]])
    assert column.tobytes() == before


@pytest.mark.parametrize("body", ["xla", "pallas"])
def test_second_sharded_fit_reuses_the_program_and_one_device_fit_does_not(
        monkeypatch, fit_noting_reuse, body):
    """Equal shapes on both meshes (1024 rows divide by 4 x 128): the
    mesh is in the program's key, through the arrays' shardings and, for
    the kernel's body, by name."""
    if body == "pallas":
        _steer_to_the_ktiled_kernel(monkeypatch)
    table = Table({"features": np.random.default_rng(2).normal(
        size=(1024, 8)).astype(np.float32)})

    def fit(devices, seed=7):
        with use_mesh(_mesh(devices)):
            return fit_noting_reuse(
                KMeans().set_k(5).set_max_iter(3).set_seed(seed), table)

    first, reused = fit(4)
    assert reused == 0
    second, reused = fit(4)
    assert reused == 1
    assert _centroids(second).tobytes() == _centroids(first).tobytes()
    _, reused = fit(4, seed=8)          # another start is data
    assert reused == 1
    _, reused = fit(1)
    assert reused == 0
    _, reused = fit(1)
    assert reused == 1
    _, reused = fit(4)                  # and the sharded one is still kept
    assert reused == 1


@pytest.mark.parametrize("devices,rows", [(1, 300), (4, 300), (4, 1000),
                                          (1, 1000)])
def test_fit_notes_shards_on_arrange_and_pieces_on_upload(
        small_pieces, devices, rows):
    """``shards``: the devices on ``data`` the rows were divided over;
    ``pieces``: the puts the fullest device received, noted once a fit,
    on its first ``fit.upload``.  The fixture's cap holds 16 rows, and it
    is a cap on a ROUND: one device gets 16 rows a put (1000 rows: 63
    puts), each of four a relayout step of 8, the least a put hands over
    (a shard of 250 rows: 32 puts)."""
    from flink_ml_tpu.obs.trace import tracer

    column = np.random.default_rng(1).normal(size=(rows, 5)).astype(
        np.float32)
    tracer.enable()
    try:
        with use_mesh(_mesh(devices)):
            KMeans().set_k(4).set_max_iter(2).set_seed(3).fit(
                Table({"features": column}))
        arrange = [s for s in tracer.find("fit.arrange") if "shards" in s.ids]
        upload = [s for s in tracer.find("fit.upload") if "pieces" in s.ids]
    finally:
        tracer.disable()
        tracer.clear()
    assert [s.ids["shards"] for s in arrange] == [devices]
    assert len(upload) == 1
    shard_rows = -(-rows // devices)
    assert upload[0].ids["pieces"] == -(-shard_rows // (16 if devices == 1
                                                         else 8))
    assert arrange[0].ids["stats_plan"] == "xla"


@pytest.mark.parametrize("devices", [1, 4])
def test_kmeans_reduce_scope_is_in_the_sharded_program_only(devices):
    """The ``psum`` of the shards' sums and counts, and nothing else, lies
    under ``kmeans.reduce``; a one-device step has no such scope."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    mesh = _mesh(devices)
    body = km.kmeans_epoch_step_pallas(5, mesh, block_n=128, k_tile=None,
                                       interpret=True)
    rows, whole = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    lowered = jax.jit(lambda c, data: body(c, 0, data).feedback).lower(
        jax.ShapeDtypeStruct((5, 8), jnp.float32, sharding=whole),
        (jax.ShapeDtypeStruct((1024, 8), jnp.float32, sharding=rows),
         jax.ShapeDtypeStruct((1024,), jnp.float32, sharding=rows)))
    text = lowered.as_text(debug_info=True)
    assert "kmeans.stats" in text and "kmeans.update" in text
    under = set(re.findall(r'loc\("kmeans\.reduce/(\w+)"', text))
    # the all-reduce and the addition it reduces by, nothing else
    assert under == ({"psum", "add"} if devices > 1 else set())
