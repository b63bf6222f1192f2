"""Cross-lowering for TPU, from the CPU sandbox, in seconds.

``jax.export.export(jax.jit(fn), platforms=["tpu"])`` runs Pallas' TPU
lowering rules without a chip, which is where the block-shape class of
error lives (a ``(1, d)`` block, a batched dot, a 3-D gather).  Every
``pallas`` entry the registry selects by itself on a TPU lowers
here at its smallest supported shape and at the full width
``chip_smoke.py`` runs (or, off the smoke's path, the Criteo shape).

Lowering is not compiling: Mosaic inside libtpu can still refuse what
lowers (VMEM limit, unaligned slices).  That is ``tests_tpu``'s job, on
the chip.  The last tests of this file go one step further for the
KMeans fit, the benchmark's kernel: they COMPILE it for a described v5e
(the TPU's compiler is installed here; nothing runs), which is where a
block over the VMEM limit and a lane-padded copy of the points show.
"""

from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax import ShapeDtypeStruct as Shape

from flink_ml_tpu.kernels import registry

F32, I32, I8 = jnp.float32, jnp.int32, jnp.int8


def _ell(rows, batch):
    """(w, r_ext, src, pos, mask/val grids, m_len) of an ELL step over a
    ``rows x 128`` table and a ``batch``-row minibatch."""
    from flink_ml_tpu.models.common.sgd import _ext_len

    m_len = _ext_len(batch)
    grid_i, grid_f = Shape((rows, 128), I32), Shape((rows, 128), F32)
    return (Shape((rows * 128,), F32), Shape((m_len,), F32), grid_i, grid_f,
            m_len)


def _ell_margin(rows, batch, with_val):
    from flink_ml_tpu.ops.ell_scatter import ell_margin_fused

    w, _, gi, gf, m_len = _ell(rows, batch)
    if with_val:
        return (lambda w, s, p, m, v: ell_margin_fused(
            w, s, p, m, m_len=m_len, val=v)), (w, gi, gi, gf, gf)
    return partial(ell_margin_fused, m_len=m_len), (w, gi, gi, gf)


def _ell_scatter(fn_name, rows, batch, precision="default"):
    from flink_ml_tpu.ops import ell_scatter

    w, r_ext, gi, gf, _ = _ell(rows, batch)
    fn = partial(getattr(ell_scatter, fn_name), lr=0.5, precision=precision)
    return fn, (w, r_ext, gi, gi, gf)


def _lr_step(d, batch, n_dense):
    """The whole ``_mixed_update_ell`` step with the Pallas entries
    forced — what ``LogisticRegression.fit`` scans on the chip."""
    from flink_ml_tpu.models.common.losses import LOSSES
    from flink_ml_tpu.models.common.sgd import SGDConfig, _mixed_update_ell

    rows = d // 128
    gi, gf = Shape((rows, 128), I32), Shape((rows, 128), F32)
    update = _mixed_update_ell(
        LOSSES["logistic"], SGDConfig(global_batch_size=batch),
        backend="pallas")
    params = {"w": Shape((d,), F32), "b": Shape((), F32)}
    return update, (params, Shape((batch, n_dense), F32), gi, gi, gf,
                    Shape((1024,), I32), Shape((1024,), I32),
                    Shape((16,), I32), Shape((16, batch), jnp.int16),
                    Shape((batch,), F32), Shape((batch,), F32))


def _kmeans_stats(n, d, k, block_n):
    """``block_n`` None: the tiles the fit's plan picks at (d, k)."""
    from flink_ml_tpu.ops.kmeans_pallas import (kmeans_update_stats,
                                                stats_tiles)

    k_tile = None
    if block_n is None:
        block_n, k_tile = stats_tiles(d, k)
    return (partial(kmeans_update_stats, block_n=block_n, k_tile=k_tile),
            (Shape((n, d), F32), Shape((k, d), F32)))


def _kmeans_workset(n, d, k, block_n):
    from flink_ml_tpu.ops.kmeans_pallas import kmeans_workset_update

    row = Shape((n,), F32)
    return (partial(kmeans_workset_update, block_n=block_n),
            (Shape((n, d), F32), Shape((k, d), F32), Shape((n,), I32), row,
             row))


def _retrieve(b, dim, nlist, block, m=0, ksub=16, nprobe=4, k=10):
    from flink_ml_tpu.ops import retrieve_pallas as rp

    common = dict(nprobe=nprobe, k=k, nlist=nlist, block=block)
    q, cents = Shape((b, dim), F32), Shape((nlist, dim), F32)
    ids = Shape((nlist, block), I32)
    if not m:
        return (partial(rp.retrieve_flat_fused, **common),
                (q, cents, ids, Shape((nlist * block, dim), F32)))
    return (partial(rp.retrieve_pq_fused, m=m, **common),
            (q, cents, ids, Shape((nlist * block, m), I8),
             Shape((m, ksub, dim // m), I8), Shape((m, ksub), F32)))


def _routed_adam(n, u, e, block_n=None):
    """``e`` 0: the wide table's vector of scalars."""
    from flink_ml_tpu.ops.adam_table_pallas import routed_adam_update_fused

    table = Shape((n, e) if e else (n,), F32)
    return (partial(routed_adam_update_fused, lr=1e-3, b1=0.9, b2=0.999,
                    eps=1e-8, block_n=block_n),
            (table, table, table, Shape((u, e) if e else (u,), F32),
             Shape((u,), I32), Shape((), I32)))


def _als_solve(rank, groups):
    from flink_ml_tpu.ops.als_solve_pallas import cholesky_solve_vmem

    return cholesky_solve_vmem, (Shape((rank, rank, groups), F32),
                                 Shape((rank, groups), F32))


def _gbt_hist(n, d, bins, n_nodes):
    from flink_ml_tpu.models.common.gbt import _level_histograms_pallas

    return (partial(_level_histograms_pallas, n_nodes=n_nodes, d=d,
                    bins=bins),
            ([Shape((n,), I32)] * d, Shape((n,), I32), Shape((n,), F32),
             Shape((n,), F32)))


# the groups of a users' and of an items' block of ``als_netflix.fit``
# (four blocks of 120,047 users, three of 17,770 items, each class
# rounded up; PERF.md section 4), solved at the cell's rank 100
_NETFLIX_BLOCKS, _NETFLIX_RANK = (30_020, 5_891), 100


# Criteo's 26 cardinalities in all, and the most table rows one batch of
# 32768 touches there (benchmarks/configs/widedeep_criteo.json; PERF.md)
_CRITEO_ROWS, _CRITEO_UNIQUE = 33_762_577, 126_629


# (op, backend) -> {case id: thunk returning (fn, abstract args)}.  The
# smallest ELL table is 128 rows; full width is the Criteo step
# (d = 2^20, batch 32768) and the KMeans fit (n = 2^20, d = 64, k = 256)
# of chip_smoke.py; "hibench" is the benchmark cell's d 20, k 10 at the
# block of 32768 lanes its plan picks, rows contracted on lanes.
# "airline" is ``gbt_airline.fit``'s 115,069,017 rows (padded to the
# kernel's blocks) of 13 features and 32 bins at a tree's first and last
# level.  (routed_table_grad/pallas lowers too, but Mosaic refuses it on
# the chip: forced-lookup only.)
CASES = {
    ("ell_margin", "pallas"): {
        "smallest": lambda: _ell_margin(128, 64, False),
        "smallest-values": lambda: _ell_margin(128, 64, True),
        "full-width": lambda: _ell_margin(8192, 1 << 15, False),
    },
    ("ell_scatter_apply", "pallas"): {
        "smallest": lambda: _ell_scatter("ell_scatter_apply_fused", 128, 64),
        "smallest-highest": lambda: _ell_scatter(
            "ell_scatter_apply_fused", 128, 64, "highest"),
        "full-width": lambda: _ell_scatter(
            "ell_scatter_apply_fused", 8192, 1 << 15),
        "full-width-step": lambda: _lr_step(1 << 20, 1 << 15, 13),
    },
    ("ell_scatter_apply", "pallas-pair"): {
        "smallest": lambda: _ell_scatter("ell_scatter_apply_pair", 128, 64),
        "full-width": lambda: _ell_scatter(
            "ell_scatter_apply_pair", 8192, 1 << 15),
    },
    ("kmeans_update_stats", "pallas"): {
        "smallest-first": lambda: _kmeans_stats(128, 8, 4, 128),
        "full-width": lambda: _kmeans_stats(1 << 20, 64, 256, 8192),
        "hibench": lambda: _kmeans_stats(1 << 20, 20, 10, None),
        "mnist8m": lambda: _kmeans_stats(1 << 17, 784, 4096, None),
        "tiled-over-k-row-major": lambda: _kmeans_stats(
            1 << 17, 128, 16384, None),
    },
    ("kmeans_workset_update", "pallas"): {
        "smallest": lambda: _kmeans_workset(128, 8, 4, 128),
        "full-width": lambda: _kmeans_workset(1 << 20, 64, 256, 4096),
    },
    ("routed_adam_update", "pallas"): {
        "smallest": lambda: _routed_adam(100, 8, 16),
        "smallest-scalars": lambda: _routed_adam(100, 8, 0),
        "criteo": lambda: _routed_adam(_CRITEO_ROWS, _CRITEO_UNIQUE, 16),
    },
    ("als_cholesky_solve", "pallas"): {
        "smallest": lambda: _als_solve(8, 128),
        "netflix-users": lambda: _als_solve(_NETFLIX_RANK,
                                            _NETFLIX_BLOCKS[0]),
        "netflix-items": lambda: _als_solve(_NETFLIX_RANK,
                                            _NETFLIX_BLOCKS[1]),
    },
    ("gbt_level_histograms", "pallas"): {
        "smallest": lambda: _gbt_hist(2048, 1, 2, 1),
        "ragged": lambda: _gbt_hist(5000, 3, 8, 4),
        "airline-root": lambda: _gbt_hist(115_081_216, 13, 32, 1),
        "airline-depth-4": lambda: _gbt_hist(115_081_216, 13, 32, 16),
    },
    ("retrieve", "pallas"): {
        **{f"flat-rows{b}": partial(_retrieve, b, 128, 16, 128)
           for b in (1, 8, 16)},
        "flat-full-width": lambda: _retrieve(256, 128, 1024, 1024,
                                             nprobe=16),
    },
}

# The kernel the registry no longer selects by itself because Pallas' TPU
# lowering refuses it (the registration holds the refusal).  xfail is
# strict, so the day it lowers the test fails and the entry can be planned
# again (ops/retrieve_pallas.py::_register).
PARKED = {
    "retrieve-pq-rows1": lambda: _retrieve(1, 32, 16, 64, m=4),
    "retrieve-pq-rows8": lambda: _retrieve(8, 32, 16, 64, m=4),
}


def _lower_for_tpu(case):
    fn, args = case()
    jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)


def test_every_auto_selectable_entry_has_lowering_cases():
    auto = set()
    for op in registry.ops():
        for backend in registry.backends(op):
            entry = registry.lookup(op, backend=backend)
            if entry.available is registry.tpu_only:
                auto.add((op, backend))
    assert auto == set(CASES), (
        f"no lowering case: {sorted(auto - set(CASES))}; "
        f"stale: {sorted(set(CASES) - auto)}")


@pytest.mark.parametrize("case", [
    pytest.param(case, id=f"{op}/{backend}:{name}")
    for (op, backend), cases in CASES.items()
    for name, case in cases.items()])
def test_lowers_for_tpu(case):
    _lower_for_tpu(case)


def test_sharded_lr_step_lowers_for_tpu():
    """``_mixed_update_ell_sharded`` at full width on a four-device
    ``data`` mesh — Pallas inside ``shard_map``, chip_smoke.py's mesh4
    leg — lowered here because a four-chip run costs four times the chip
    time of finding the same error on one."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from flink_ml_tpu.models.common.losses import LOSSES
    from flink_ml_tpu.models.common.sgd import (SGDConfig,
                                                _mixed_update_ell_sharded)
    from flink_ml_tpu.parallel.mesh import device_mesh

    d, batch, n_dev = 1 << 20, 1 << 15, 4
    local = batch // n_dev
    mesh = device_mesh({"data": n_dev}, devices=jax.devices()[:n_dev])

    def arg(shape, dtype, *spec):
        return Shape(shape, dtype, sharding=NamedSharding(mesh, P(*spec)))

    grid = (n_dev, d // 128, 128)
    update = _mixed_update_ell_sharded(
        LOSSES["logistic"], SGDConfig(global_batch_size=batch), mesh, d,
        backend="pallas")
    _lower_for_tpu(lambda: (update, (
        {"w": arg((d,), F32), "b": arg((), F32)},
        arg((batch, 13), F32, "data"),
        arg(grid, I32, "data"), arg(grid, I32, "data"),
        arg(grid, F32, "data"),
        arg((n_dev, 1024), I32, "data"), arg((n_dev, 1024), I32, "data"),
        arg((n_dev, 16), I32, "data"),
        arg((n_dev, 16, local), jnp.int16, "data"),
        arg((batch,), F32, "data"), arg((batch,), F32, "data"))))


@pytest.mark.parametrize("case", [
    pytest.param(case, id=name) for name, case in PARKED.items()])
@pytest.mark.xfail(
    strict=True, raises=NotImplementedError,
    reason=registry.lookup("retrieve", backend="pallas-pq").forced_only)
def test_parked_kernel_still_does_not_lower(case):
    _lower_for_tpu(case)


# -- compiled for a described v5e (no chip; nothing runs) -------------------

@pytest.fixture(scope="module")
def v5e_2x2():
    """A described host of four v5e chips, or a skip where none can be
    described.  Made in a fixture: only the worker that runs this file may
    load the TPU's library."""
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs in /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever the plugin raises
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_v5e(v5e_2x2):
    """A single-device sharding on the described host's first chip."""
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e_2x2.devices[0])


@pytest.mark.parametrize("d,k", [(20, 10), (64, 256), (784, 256)],
                         ids=["hibench", "chip-smoke", "mnist-width"])
def test_kmeans_stats_compiles_at_the_block_the_model_picks(one_v5e, d, k):
    """``_stats_tile_bytes`` against the compiler: the largest block it
    admits fits the 16 MiB of scoped VMEM Mosaic may take."""
    from flink_ml_tpu.ops.kmeans_pallas import (kmeans_update_stats,
                                                pick_block_n)

    block_n = pick_block_n(None, d, k)
    jax.jit(partial(kmeans_update_stats, block_n=block_n)).lower(
        Shape((2 * block_n, d), F32, sharding=one_v5e),
        Shape((k, d), F32, sharding=one_v5e)).compile()


@pytest.mark.parametrize("d,k", [(784, 4096), (128, 16384), (200, 5000),
                                 (1536, 512)],
                         ids=["mnist8m", "row-major-16k", "ragged",
                              "wide-512"])
def test_kmeans_stats_tiled_over_k_compile_alone(one_v5e, d, k,
                                                 monkeypatch):
    """The kernel tiled over k, ALONE, at the tiles its VMEM model picks:
    ``kmeans_mnist8m``'s shapes (rows on lanes: ``points.T`` is the
    column-major array the chip keeps a row of 784 floats as), 16 K
    centroids of whole lane tiles (row-major blocks), a k and a d that
    divide by neither tile, and one tile of 512 centroids 1536 wide (too
    wide for the feature-major tile).  Mosaic must take the resident
    ``(k, d)`` blocks and the two ``(k, d + 1)`` sums under the VMEM limit
    its model sets, here with no headroom added, the dynamic tile slices,
    the contraction over 784, the block's transposition on the XLU, the
    indices' DMA to SMEM and the rows' dynamic sublane reads and writes;
    and no operand is copied on the way in."""
    from flink_ml_tpu.ops import kmeans_pallas
    from flink_ml_tpu.ops.kmeans_pallas import (kmeans_update_stats,
                                                stats_tiles)

    monkeypatch.setattr(kmeans_pallas, "_KTILED_VMEM_HEADROOM", 0)
    block_n, k_tile = stats_tiles(d, k)
    assert k_tile is not None
    compiled = jax.jit(partial(kmeans_update_stats, block_n=block_n,
                               k_tile=k_tile)).lower(
        Shape((8 * block_n, d), F32, sharding=one_v5e),
        Shape((k, d), F32, sharding=one_v5e)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < block_n * d * 4


def test_kmeans_fit_program_tiled_over_k_keeps_no_copy_of_the_points(one_v5e):
    """The fused program of ``kmeans_mnist8m.fit`` (2,025,472 x 784 with
    the fill rows, k 4096, 20 iterations): 6.35 GB of points go into the
    kernel as they lie; a kernel that wanted rows of 784 floats on 896
    lanes made XLA copy them (7.27 GB of temporaries, compiled here)."""
    from flink_ml_tpu.models.clustering.kmeans import kmeans_epoch_step_pallas
    from flink_ml_tpu.ops.kmeans_pallas import stats_tiles

    n, d, k = 2_025_472, 784, 4096
    block_n, k_tile = stats_tiles(d, k)
    body = kmeans_epoch_step_pallas(k, block_n=block_n, k_tile=k_tile)

    def run(centroids, data):
        return jax.lax.scan(
            lambda c, epoch: (body(c, epoch, data).feedback, None),
            centroids, jnp.arange(20, dtype=jnp.int32))[0]

    compiled = jax.jit(run).lower(
        Shape((k, d), F32, sharding=one_v5e),
        (Shape((n, d), F32, sharding=one_v5e),
         Shape((n,), F32, sharding=one_v5e))).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_sharded_kmeans_fit_program_compiles_for_four_chips(v5e_2x2):
    """The fused program of ``kmeans_mnist8m_full.fit``: 8,100,000 x 784
    with the fill rows (8,101,888), divided over the ``data`` axis of the
    described host's four chips, k 4096, 20 iterations, the kernel tiled
    over k inside ``shard_map`` and the ``psum`` of the sums and counts
    in the loop.  A chip keeps no copy of its 6.35 GB of points (the
    program's temporaries stay under 64 MB a device), the kernel is there
    with its 57 MiB of VMEM under the raised limit, and the sums and counts
    meet in ONE all-reduce under the scope ``kmeans.reduce``."""
    import re

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from flink_ml_tpu.models.clustering.kmeans import kmeans_epoch_step_pallas
    from flink_ml_tpu.ops.kmeans_pallas import _stats_tile_bytes, stats_tiles

    n, d, k = 8_101_888, 784, 4096
    mesh = Mesh(v5e_2x2.devices, ("data",))
    rows, whole = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    block_n, k_tile = stats_tiles(d, k)
    body = kmeans_epoch_step_pallas(k, mesh, block_n=block_n, k_tile=k_tile)

    def run(centroids, data):
        return jax.lax.scan(
            lambda c, epoch: (body(c, epoch, data).feedback, None),
            centroids, jnp.arange(20, dtype=jnp.int32))[0]

    compiled = jax.jit(run, out_shardings=whole).lower(
        Shape((k, d), F32, sharding=whole),
        (Shape((n, d), F32, sharding=rows),
         Shape((n,), F32, sharding=rows))).compile()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 64 << 20
    assert memory.argument_size_in_bytes < (n // 4) * (d + 1) * 4 + (32 << 20)
    # the kernel's blocks as its VMEM model counts them (Mosaic took them
    # under the limit the call raises: a compile over it is refused)
    assert round(_stats_tile_bytes(d, k, block_n, k_tile) / 2 ** 20) == 57
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    reduces = [line for line in text.splitlines()
               if re.search(r" all-reduce(-start)?\(", line)]
    # the sums and counts in one all-reduce under its scope; the only
    # other collective is the scalar count of the fill rows
    big = [line for line in reduces if "f32[4096,784]" in line]
    assert len(big) == 1 and len(reduces) == 2, reduces
    assert "kmeans.reduce/psum" in text
    assert "all-gather" not in text and "all-to-all" not in text


def test_kmeans_fit_program_keeps_no_copy_of_the_points(one_v5e):
    """The fused program of a fit at 2^22 x 20, k 10 (``iterate``'s scan
    over the Pallas step): the kernel takes the rows as the chip lays
    them out (column-major: ``points.T`` is a bitcast), so the program's
    temporaries stay under the points' own bytes.  A kernel that wants
    rows on sublanes makes XLA copy them into rows of 128 lanes, 6.4
    times the points (``tests_tpu`` asserts the same on the chip)."""
    from flink_ml_tpu.models.clustering.kmeans import kmeans_epoch_step_pallas
    from flink_ml_tpu.ops.kmeans_pallas import pick_block_n

    n, d, k = 1 << 22, 20, 10
    body = kmeans_epoch_step_pallas(k, block_n=pick_block_n(n, d, k))

    def run(centroids, data):
        return jax.lax.scan(
            lambda c, epoch: (body(c, epoch, data).feedback, None),
            centroids, jnp.arange(5, dtype=jnp.int32))[0]

    compiled = jax.jit(run).lower(
        Shape((k, d), F32, sharding=one_v5e),
        (Shape((n, d), F32, sharding=one_v5e),
         Shape((n,), F32, sharding=one_v5e))).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < n * d * 4


@pytest.mark.parametrize("n,e", [(_CRITEO_ROWS, 16), ((1 << 22) + 1, 64),
                                 (5300, 16)],
                         ids=["criteo", "width-64", "table-of-one-block"])
def test_routed_adam_update_compiles_in_place(one_v5e, n, e):
    """The fused Adam pass at the block its VMEM model picks (8192 and
    2048 rows, and the 5376 of a table smaller than a block, whose id
    windows still have to be multiples of 1024): inside the 16 MiB of
    scoped VMEM, ``p``, ``m`` and ``v`` aliased in -> out, no temporary
    to speak of: the transposed views are the arrays the chip holds.  (Not so at width 128, which the chip keeps
    row-major, nor for the scalar table's ``(1, N)`` view: the same
    compile leaves table-sized temporaries there, which is why the
    registry's ``supports`` keeps those on the XLA backend.)"""
    fn, args = _routed_adam(n, _CRITEO_UNIQUE, e)
    compiled = jax.jit(fn, donate_argnums=(0, 1, 2)).lower(*(
        Shape(a.shape, a.dtype, sharding=one_v5e) for a in args)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 3 * n * e * 4
    assert mem.temp_size_in_bytes < (64 << 20)


def test_widedeep_fit_program_forms_no_table_shaped_gradient(
        one_v5e, monkeypatch):
    """The fused program of ``WideDeep.fit`` at the benchmark cell's
    shapes (5 epochs of 32 routed steps, ``scatter`` placement, the
    tables on one device) compiled for a described v5e: the step updates
    embedding table through the fused ``routed_adam_update``, so the
    program keeps no temporary of that table's size (with the dense
    gradient, 2.16 GB, it kept 2.75 GB, PERF.md section 4; without it
    1.10 GB, half a table: the folds' slot arrays and the wide table's
    gradient), donates p, m and v into the loop, and neither copies nor
    transposes an array of the table's shape."""
    import re

    import numpy as np
    import optax

    from flink_ml_tpu.models.recommendation import widedeep
    from flink_ml_tpu.ops.emb_grad import EmbGradRoute

    n, u, e, batch, fields, steps, epochs = (
        _CRITEO_ROWS, _CRITEO_UNIQUE, 16, 32768, 26, 32, 5)
    vocab = [n - fields + 1] + [1] * (fields - 1)
    slots = batch * fields

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: Shape(a.shape, a.dtype, sharding=one_v5e), tree)

    route = EmbGradRoute(
        order=Shape((steps, slots), I32),
        sorted_ids=Shape((steps, slots), I32),
        out_pos=Shape((steps, u), I32), out_ids=Shape((steps, u), I32),
        fold_passes=15, num_rows=n, placement="scatter")
    # the step is built as on the chip: the registry's own pick there
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    small = widedeep.init_params(np.random.default_rng(0), 13, [3, 2], e,
                                 (1024, 512, 256))
    step, _ = widedeep._make_train_ops(small, 1e-3, False, route=route)
    assert step.table_update == "fused"
    monkeypatch.undo()

    params = jax.eval_shape(lambda: widedeep.init_params(
        np.random.default_rng(0), 13, vocab, e, (1024, 512, 256)))
    opt_state = jax.eval_shape(optax.adam(1e-3).init, params)

    def run(state, data):
        def epoch(state, _):
            def batch_step(carry, i):
                *carry, loss = step(*carry, *(a[i] for a in data))
                return tuple(carry), loss
            return jax.lax.scan(batch_step, state,
                                jnp.arange(steps, dtype=jnp.int32))
        return jax.lax.scan(epoch, state, None, length=epochs)

    compiled = jax.jit(run, donate_argnums=0).lower(
        on_chip((params, opt_state)),
        on_chip((Shape((steps, batch, 13), F32),
                 Shape((steps, batch, fields), I32),
                 Shape((steps, batch), F32), Shape((steps, batch), F32))
                + route.stacked_arrays())).compile()
    mem = compiled.memory_analysis()
    table = n * e * 4
    assert mem.temp_size_in_bytes < 0.6 * table, mem
    assert mem.alias_size_in_bytes >= 3 * (table + n * 4), mem
    moved = [line for line in compiled.as_text().splitlines()
             if re.search(r"= f32\[(%d,%d|%d,%d)\]\S* (copy|transpose)\("
                          % (n, e, e, n), line)]
    assert not moved, moved[:3]


@pytest.mark.parametrize("rank,groups", [
    (_NETFLIX_RANK, _NETFLIX_BLOCKS[0]), (_NETFLIX_RANK, _NETFLIX_BLOCKS[1]),
    (32, 4_001), (10, 262_144)],
    ids=["netflix-users", "netflix-items", "chip-smoke-rank", "default-rank"])
def test_als_cholesky_solve_compiles_with_its_tile_in_vmem(one_v5e, rank,
                                                           groups):
    """The solve kernel at the benchmark cell's two block shapes (a last
    tile of 68 and of 3 groups), at ``chip_smoke.py``'s rank and at the
    estimator's default rank with the block of 2^18 groups a v5e gets
    there: Mosaic takes the dynamic row reads, the ragged last tile and
    the raised VMEM limit (two buffers of ``At``'s tile and the factor,
    16 MB at rank 100), and the call keeps no temporary in HBM: the
    factor is the kernel's own scratch."""
    fn, args = _als_solve(rank, groups)
    compiled = jax.jit(fn).lower(*(
        Shape(a.shape, a.dtype, sharding=one_v5e) for a in args)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20)


def test_als_fit_program_holds_one_block_of_normal_equations(
        one_v5e, monkeypatch):
    """The fused program of ``ALS.fit`` at the benchmark cell
    ``als_netflix.fit``'s shapes (120,047 users x 17,770 items, 24.8 M
    ratings with the generator's degrees, rank 100, 5 epochs) compiled
    for a described v5e: the grouped form forms and solves the normal
    equations a block of groups at a time, so the program fits the chip
    with room (the users' dense ``(120047, 100, 100)`` alone is 6.4 GB as
    the chip pads it, and Cholesky wants as much again), every ``A`` it
    holds is block-shaped, and the factorisation works with the groups
    on the lanes.  Compiled twice: with the registry's pick on a TPU (op
    ``als_cholesky_solve``'s kernel: a tile's factor in VMEM, no factor
    in HBM, one transposing copy a block in front of the call) and with
    the XLA loop the program gets elsewhere."""
    import importlib
    import json
    import os
    import re
    import sys

    import numpy as np

    from flink_ml_tpu.models.recommendation import als

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    generator = importlib.import_module("generators.netflix_ratings")
    with open(os.path.join(bench, "configs", "als_netflix.json")) as f:
        config = json.load(f)
    params = {**config, **config["generator_params"]}
    users, items, rank = config["users"], config["items"], config["rank"]
    rng = np.random.default_rng(0)
    popularity = np.clip(generator._lognormal(
        rng, items, params["item_count_median"], params["item_count_mean"]),
        params["item_count_min"], params["item_count_max"])
    plans = (
        als.GroupedPlan.of_counts(generator.user_degrees(rng, params), rank),
        als.GroupedPlan.of_counts(np.maximum(1, rng.multinomial(
            config["rows"], popularity / popularity.sum())), rank))

    def on_chip(shape, dtype):
        return Shape(shape, dtype, sharding=one_v5e)

    def arrays(plan):
        whole = tuple(
            (on_chip((plan.blocks, c.groups * c.length), I32),
             on_chip((plan.blocks, c.groups * c.length), F32),
             on_chip((plan.blocks, c.groups * c.length), F32),
             on_chip((plan.blocks, c.groups), I32)) for c in plan.classes)
        assert plan.parts == 0          # no group outgrows a block here
        return whole, ()

    body = als.als_epoch_step(users, items, config["reg_param"], False, 1.0,
                              plans=tuple(p.shape for p in plans))

    args = ((on_chip((users, rank), F32), on_chip((items, rank), F32)),
            (arrays(plans[0]), arrays(plans[1])))
    blocks = {p.block_groups for p in plans}

    def compiled_program():
        def run(state, data):            # traced anew for either backend
            return jax.lax.scan(
                lambda s, epoch: (body(s, epoch, data).feedback, None),
                state, jnp.arange(config["max_iter"], dtype=jnp.int32))[0]

        compiled = jax.jit(run).lower(*args).compile()
        mem = compiled.memory_analysis()
        total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes)
        assert (2 << 30) < total < (12 << 30), mem
        text = compiled.as_text()
        batches = {int(n) for n in re.findall(
            r"f32\[(\d+),%d,%d\]" % (rank, rank), text)}
        on_lanes = {int(n) for n in re.findall(
            r"f32\[%d,%d,(\d+)\]" % (rank, rank), text)}
        # the kernel takes the ragged last tile of a block as it is: the
        # lanes are the block's own groups, not rounded to a tile
        assert blocks <= batches and on_lanes == blocks, (batches, on_lanes)
        assert max(batches) <= als._block_sizes(rank)[0] + 64 < users
        return text, mem.temp_size_in_bytes

    # as on the chip: the registry's own pick there, a block's systems
    # solved a tile at a time inside VMEM
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert als._solve_plan(plans, rank) == "vmem"
    text, temporaries = compiled_program()
    monkeypatch.undo()
    assert als._solve_plan(plans, rank) == "xla"
    twin_text, twin_temporaries = compiled_program()

    def users_block(text, shape):
        """The distinct arrays of ``shape`` (the users' block) that the
        program's operations produce."""
        return {line.split(" = ")[0].strip() for line in text.splitlines()
                if re.search(r" = f32\[%s\]" % shape, line)}

    lanes = "%d,%d,%d" % (rank, rank, max(blocks))
    # the twin's column loop carries the block's factor beside At (1.2 GB
    # at 30,020 groups, and the loop's copy of it); the kernel's factor is
    # a tile in VMEM, so the program holds At alone
    assert text.count("tpu_custom_call") >= 2 and (
        "tpu_custom_call" not in twin_text)
    assert len(users_block(text, lanes)) < len(users_block(twin_text, lanes))
    # At is made by ONE transposing copy of the block, as in the twin's
    # program: the kernel pins its operand group-major, else XLA pushes
    # the lane-major layout back to every class's contraction (a hundred
    # small copies, 5 s more of compilation, and factors that the gathers
    # read from HBM where memory-space assignment had them in VMEM)
    copied = [int(n) for n in re.findall(
        r" = f32\[(\d+),%d,%d\]\S* copy\(" % (rank, rank), text)]
    assert sorted(copied) == sorted(blocks), copied
    assert temporaries <= 1.02 * twin_temporaries, (
        temporaries, twin_temporaries)


def test_gbt_fit_program_keeps_the_table_as_it_was_put(one_v5e, monkeypatch):
    """The fused program of ``GBTClassifier.fit`` at the benchmark cell
    ``gbt_airline.fit``'s shapes (115,069,017 rows padded to the
    histogram kernel's blocks, 13 int32 bin columns, 20 trees of depth 5
    over 32 bins) compiled for a described v5e, the histograms as the
    registry picks them on a TPU: one Pallas call a level, no copy of a
    row-sized array, and temporaries of a few row vectors (no (rows x
    features) array: ``segment_sum``'s three would take 18 GB), so that
    the program and its arguments fit the chip with room."""
    import re

    from flink_ml_tpu.iteration import IterationConfig
    from flink_ml_tpu.iteration.core import _scan_loop
    from flink_ml_tpu.models.common import gbt
    from flink_ml_tpu.ops.gbt_hist_pallas import padded_rows

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n, d, trees = 115_069_017, 13, 20
    rows = padded_rows(n)
    config = gbt.GBTConfig(num_trees=trees, max_depth=5, max_bins=32,
                           learning_rate=0.1, reg_lambda=1.0)
    run = _scan_loop(gbt.boost_round(n, d, config, "logistic", "pallas"),
                     IterationConfig(mode="fused", max_epochs=trees))

    def arg(shape, dtype):
        return Shape(shape, dtype, sharding=one_v5e)

    nodes = (trees, 63)
    compiled = run.lower(
        (arg((rows,), F32), arg(nodes, I32), arg(nodes, I32),
         arg(nodes, F32)),
        (tuple(arg((rows,), I32) for _ in range(d)), arg((rows,), F32)),
    ).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert text.count('custom_call_target="tpu_custom_call"') == 5
    assert not re.search(r"\[%d\]\S* copy\(" % rows, text)
    assert mem.temp_size_in_bytes < 5 * 4 * rows, mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 10e9


@pytest.mark.parametrize("d,bins,n_nodes", [(13, 32, 256), (13, 256, 128),
                                            (145, 32, 16)],
                         ids=["depth-9", "bins-256-depth-8", "145-features"])
def test_gbt_hist_compiles_at_the_widest_level_its_vmem_model_admits(
        one_v5e, d, bins, n_nodes):
    """``ops/gbt_hist_pallas.py: supported`` against the compiler: at 13
    features the deepest level it admits over 32 and over 256 bins, and
    near the most features it admits at a depth-5 tree's last level,
    compile for a v5e; a level of twice the nodes is refused (the
    registry then plans segment_sum)."""
    from flink_ml_tpu.ops.gbt_hist_pallas import level_histograms, supported

    assert supported((d, bins, n_nodes))
    assert not supported((d, bins, 2 * n_nodes))
    n = 1 << 16

    def arg(dtype):
        return Shape((n,), dtype, sharding=one_v5e)

    compiled = jax.jit(partial(level_histograms, n_nodes=n_nodes, d=d,
                               bins=bins)).lower(
        [arg(I32)] * d, arg(I32), arg(F32), arg(F32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_gbt_fit_deeper_than_the_kernel_holds_compiles_on_segsum(
        one_v5e, monkeypatch):
    """``maxDepth`` 12 over ``maxBins`` 256: the fit's widest level (2048
    nodes) would need about 490 MB of VMEM, so on a TPU the registry
    plans segment_sum for the fused fit, whose program compiles for a v5e;
    the hosted trainers' levels take the kernel up to 128 nodes."""
    from flink_ml_tpu.iteration import IterationConfig
    from flink_ml_tpu.iteration.core import _scan_loop
    from flink_ml_tpu.models.common import gbt

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n, d, depth, bins, trees = 4096, 13, 12, 256, 2
    impl = gbt.resolve_hist_impl("auto", (d, bins, 2 ** (depth - 1)))
    assert impl == "segsum"
    assert gbt.resolve_hist_impl("auto", (d, bins, 128)) == "pallas"
    assert gbt.resolve_hist_impl("auto", (d, bins, 256)) == "segsum"
    config = gbt.GBTConfig(num_trees=trees, max_depth=depth, max_bins=bins)
    run = _scan_loop(gbt.boost_round(n, d, config, "logistic", impl),
                     IterationConfig(mode="fused", max_epochs=trees))

    def arg(shape, dtype):
        return Shape(shape, dtype, sharding=one_v5e)

    nodes = (trees, 2 ** (depth + 1) - 1)
    compiled = run.lower(
        (arg((n,), F32), arg(nodes, I32), arg(nodes, I32), arg(nodes, F32)),
        (tuple(arg((n,), I32) for _ in range(d)), arg((n,), F32)),
    ).compile()
    assert "tpu_custom_call" not in compiled.as_text()


def test_linear_epoch_gather_keeps_the_rows_compact(one_v5e):
    """``sgd._epoch_tensors`` at ``lr_criteo.fit``'s size (5,242,880 rows
    of 13 dense floats, the labels and the weights, put flat, into 160
    steps of 32,768): the epoch tensors it returns hold the rows' own
    bytes, 13 floats a row on the chip and not a row of 128 lanes
    (2.68 GB), so the fit's program reads them as a put would lay them
    out."""
    from flink_ml_tpu.models.common.sgd import _epoch_tensors

    n, steps = 5 << 20, 160
    compiled = _epoch_tensors.lower(
        Shape((n,), I32, sharding=one_v5e),
        (Shape((13 * n,), F32, sharding=one_v5e),
         Shape((n,), F32, sharding=one_v5e),
         Shape((n,), F32, sharding=one_v5e)),
        widths=((13,), (), ()), steps=steps).compile()
    # 15 floats a row over the three, and a few hundred bytes of tiles
    assert compiled.memory_analysis().output_size_in_bytes < 16 * n * 4


def test_lr_fit_step_compiles_at_the_cell_with_its_kernels_named(
        one_v5e, monkeypatch):
    """Two epochs of ``lr_criteo.fit``'s ELL step (160 steps of batch
    32,768 over 2^20 weights, 216 heavy indices and 67,600 overflow slots
    a step), compiled for a v5e with the kernels the registry plans
    there: the Pallas calls carry the names the benchmark's roofline
    readers look for, the scopes reach the program, and the step keeps
    its temporaries under 1 GB beside 4.7 GB of arguments."""
    import re

    from flink_ml_tpu.models.common import sgd as S
    from flink_ml_tpu.models.common.losses import LOSSES

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    update = S._mixed_update_ell(
        LOSSES["logistic"], S.SGDConfig(learning_rate=0.1, tol=0.0))
    steps, b, f, h, o = 160, 32768, 1 << 20, 216, 67600

    def run(params, data):
        def epoch(p, _):
            p, losses = jax.lax.scan(
                lambda q, i: update(q, *(a[i] for a in data)), p,
                jnp.arange(steps))
            return p, jnp.mean(losses)

        return jax.lax.scan(epoch, params, jnp.arange(2))

    def arg(shape, dtype):
        return Shape(shape, dtype, sharding=one_v5e)

    grid = (steps, f // 128, 128)
    compiled = jax.jit(run).lower(
        {"w": arg((f,), F32), "b": arg((), F32)},
        (arg((steps, b, 13), F32), arg(grid, I32), arg(grid, I32),
         arg(grid, F32), arg((steps, o), I32), arg((steps, o), I32),
         arg((steps, h), I32), arg((steps, h, b), jnp.int16),
         arg((steps, b), F32), arg((steps, b), F32))).compile()
    text = compiled.as_text()
    assert set(re.findall(r"%(ell_\w+)\.\d+ = \S+ custom-call\(", text)) == {
        "ell_margin", "ell_scatter_apply"}
    assert "linear.margin" in text and "linear.apply" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
