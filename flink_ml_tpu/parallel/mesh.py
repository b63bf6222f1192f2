"""Device mesh + sharding helpers — the framework's parallelism substrate.

The reference's only parallelism is data parallelism over Flink subtasks with
hash/rebalance network shuffles (SURVEY §2.10).  Here the equivalent is a
``jax.sharding.Mesh`` with named axes and ``NamedSharding`` annotations; XLA
inserts the collectives (psum/all-gather/reduce-scatter) that replace the
reference's shuffles, and they ride ICI instead of the datacenter network.

Axis convention used across the framework:
- ``"data"``  — batch-dim sharding (the reference's subtask parallelism)
- ``"model"`` — tensor/feature-dim sharding (absent in the reference;
  reserved so TP can be layered on without API change, SURVEY §7)
"""

from __future__ import annotations

import math

from contextlib import contextmanager
from typing import Any, Iterator, List, Mapping, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils.padding import pad_rows_with_mask

__all__ = [
    "DATA_AXIS",
    "MODEL_AXIS",
    "device_mesh",
    "axis_process_count",
    "data_sharding",
    "fetch_replicated",
    "local_axis_multiple",
    "mesh_process_count",
    "put_sharded",
    "put_in_rounds",
    "put_sharded_in_pieces",
    "replicated",
    "rows_a_put",
    "shard_devices",
    "shard_batch",
    "replicate",
    "default_mesh",
    "use_mesh",
    "local_device_count",
    "pad_rows_with_mask",
]

DATA_AXIS = "data"
MODEL_AXIS = "model"

_DEFAULT_MESH: Optional[Mesh] = None


def local_device_count() -> int:
    """Devices attached to THIS host (on a multi-host pod this differs from
    the global count — size per-host batches with this)."""
    return len(jax.local_devices())


def device_mesh(axis_sizes: Optional[Mapping[str, int]] = None,
                devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a named mesh.

    Default: all devices on one ``"data"`` axis (pure DP, the reference's
    model).  Pass e.g. ``{"data": 4, "model": 2}`` for a DP x TP mesh; a
    ``-1`` size is inferred from the device count.
    """
    devices = list(devices if devices is not None else jax.devices())
    if not axis_sizes:
        axis_sizes = {DATA_AXIS: len(devices)}
    names = list(axis_sizes.keys())
    sizes = list(axis_sizes.values())
    if sizes.count(-1) > 1:
        raise ValueError("At most one mesh axis may be -1")
    if -1 in sizes:
        known = math.prod(s for s in sizes if s != -1)
        if len(devices) % known:
            raise ValueError(
                f"Cannot infer -1 axis: {len(devices)} devices not divisible "
                f"by {known}")
        sizes[sizes.index(-1)] = len(devices) // known
    if math.prod(sizes) != len(devices):
        raise ValueError(
            f"Mesh {dict(zip(names, sizes))} needs {math.prod(sizes)} devices, "
            f"have {len(devices)}")
    dev_array = np.asarray(devices).reshape(sizes)
    return Mesh(dev_array, axis_names=tuple(names))


def default_mesh() -> Mesh:
    """The process-wide default mesh (all devices, one data axis), created
    lazily; override scoped-ly with :func:`use_mesh`."""
    global _DEFAULT_MESH
    if _DEFAULT_MESH is None:
        _DEFAULT_MESH = device_mesh()
    return _DEFAULT_MESH


@contextmanager
def use_mesh(mesh: Mesh):
    global _DEFAULT_MESH
    prev = _DEFAULT_MESH
    _DEFAULT_MESH = mesh
    try:
        yield mesh
    finally:
        _DEFAULT_MESH = prev


def data_sharding(mesh: Optional[Mesh] = None, *,
                  axis: str = DATA_AXIS) -> NamedSharding:
    """Batch-dim sharding: leading dim split over the data axis (the analog
    of the reference's keyBy/rebalance partitioning, ``KMeans.java:181``)."""
    mesh = mesh or default_mesh()
    return NamedSharding(mesh, P(axis))


def replicated(mesh: Optional[Mesh] = None) -> NamedSharding:
    """Fully-replicated sharding (the analog of ``.broadcast()`` model/
    centroid streams, ``KMeans.java:152``)."""
    mesh = mesh or default_mesh()
    return NamedSharding(mesh, P())


def _pad_rows(arr: np.ndarray, multiple: int) -> np.ndarray:
    return pad_rows_with_mask(arr, multiple)[0]


def shard_batch(tree: Any, mesh: Optional[Mesh] = None, *,
                axis: str = DATA_AXIS, pad: bool = True) -> Any:
    """Place a pytree of host arrays with the leading dim sharded over
    ``axis``.  With ``pad=True`` rows are padded (repeating row 0) to a
    multiple of the PER-PROCESS axis size — callers carrying a mask should
    use ``Table.pad_to_multiple`` instead to keep the mask.  On a
    process-spanning mesh each process passes its own rows (the
    :func:`put_sharded` contract)."""
    mesh = mesh or default_mesh()
    if axis not in mesh.shape:
        raise ValueError(f"Mesh has no axis {axis!r}; axes: {list(mesh.shape)}")
    n = local_axis_multiple(mesh, axis)
    spec = P(axis)

    def put(x):
        arr = np.asarray(x)
        if pad and arr.shape and arr.shape[0] % n:
            arr = _pad_rows(arr, n)
        return put_sharded(arr, mesh, spec)

    if axis_process_count(mesh, axis) > 1:
        # unequal per-process shards would infer different global shapes
        # on each host and deadlock the first collective; check up front
        from jax.experimental import multihost_utils

        first = next(iter(jax.tree_util.tree_leaves(tree)), None)
        if first is not None:
            rows = np.asarray(first).shape[0]
            rows += (-rows) % n if pad else 0
            gathered = np.asarray(multihost_utils.process_allgather(
                np.asarray([rows], np.int64))).reshape(-1)
            if not np.all(gathered == gathered[0]):
                raise ValueError(
                    "shard_batch on a process-spanning axis requires equal "
                    f"padded row counts per process; got {gathered.tolist()}")

    return jax.tree_util.tree_map(put, tree)


def mesh_process_count(mesh: Mesh) -> int:
    """Distinct processes owning the mesh's devices (1 = single-host)."""
    return len({d.process_index for d in mesh.devices.flat})


def axis_process_count(mesh: Mesh, axis: str) -> int:
    """Distinct processes along ONE mesh axis (an axis laid out entirely
    within each host counts 1 even on a multi-host mesh).

    Every line along the axis must cross the same number of processes —
    sampling one line on an irregular layout would mis-size per-process
    padding and surface later as an opaque collective/shape error, so
    irregularity raises here instead."""
    ax = list(mesh.axis_names).index(axis)
    devs = np.moveaxis(np.asarray(mesh.devices), ax, 0)
    lines = devs.reshape(devs.shape[0], -1)
    counts = {len({d.process_index for d in lines[:, i]})
              for i in range(lines.shape[1])}
    if len(counts) > 1:
        raise ValueError(
            f"irregular process layout along mesh axis {axis!r}: lines "
            f"cross {sorted(counts)} distinct processes; lay the mesh out "
            "so every line along the axis spans the same process count")
    return counts.pop()


def local_axis_multiple(mesh: Mesh, axis: str = DATA_AXIS,
                        row_multiple: int = 1) -> int:
    """Per-process row-padding multiple for arrays sharded over ``axis``,
    with a clear error for axes that do not divide over their processes."""
    n_axis = int(mesh.shape[axis])
    procs = axis_process_count(mesh, axis)
    if procs > 1 and (n_axis % procs or n_axis < procs):
        raise ValueError(
            f"axis {axis!r} of size {n_axis} does not divide over the "
            f"{procs} processes it spans; shape the mesh with the axis as "
            "a multiple of the process count")
    return (n_axis // procs) * row_multiple


def put_sharded(arr: np.ndarray, mesh: Mesh, spec: P):
    """Place a host array on the mesh under ``spec``: plain device_put on a
    single-host mesh; on a process-spanning mesh each process contributes
    its LOCAL slice along the sharded dims
    (``jax.make_array_from_process_local_data``) and the global array is
    the assembly over processes."""
    sharding = NamedSharding(mesh, spec)
    if mesh_process_count(mesh) > 1:
        return jax.make_array_from_process_local_data(sharding, arr)
    return jax.device_put(arr, sharding)


#: The most a process hands its devices at a time.  The runtime moves a
#: buffer of 0.8 to 3.2 GB to one chip at 6.3-6.6 GB/s and one of 6.35 GB at
#: 0.25 (24.5-26.5 s; the same rows as a 2-D array 33 s); sixteen pieces of
#: 0.4 GB all in flight at once 1.0 GB/s, four of 1.6 GB 0.7: what it has in
#: flight at its fast pace is bounded somewhere between 3.2 and 6.35 GB
#: (chip runs of PR 35, one v5e, seed 2147483501).  The bound is the
#: PROCESS's, not a device's: 25.4 GB to the four chips of a host in rounds
#: of 4 x 2.06 GB took 21-25 s, of 4 x 3.1 GB 23.7 s, of 4 x 1.03 GB 3.03 s
#: and of 4 x 0.41 GB 3.14 s, 8.1 GB/s over the four links together (chip
#: runs of PR 39, four v5e, seed 2147483777).  So a larger table goes up in
#: pieces, each waited for before the next is put, and on a sharded axis a
#: ROUND of all the devices' pieces is at most this size
#: (:func:`put_sharded_in_pieces`).
PUT_BYTES = 1 << 31


def rows_a_put(n: int, row_bytes: int, whole: int = 1) -> int:
    """Rows of an ``n``-row table of ``row_bytes`` a row that one put
    hands over: all of them up to :data:`PUT_BYTES`, else as many whole
    multiples of ``whole`` rows as the cap holds (at least one).  For a
    round of puts to several devices ``row_bytes`` is a row's bytes times
    the devices that get one each."""
    if n * row_bytes <= PUT_BYTES:
        return n
    return max(1, PUT_BYTES // row_bytes // whole) * whole


def shard_devices(mesh: Mesh, axis: str = DATA_AXIS) -> List[list]:
    """The devices that hold each shard of an array split over ``axis``
    alone, in the shards' order: one device a shard on a mesh of that one
    axis, every device along the mesh's other axes otherwise (they hold
    the shard replicated)."""
    ax = list(mesh.axis_names).index(axis)
    devs = np.moveaxis(np.asarray(mesh.devices), ax, 0)
    return [list(line) for line in devs.reshape(devs.shape[0], -1)]


def put_sharded_in_pieces(rows: np.ndarray, mesh: Mesh, shard_rows: int,
                          piece_rows: int, *,
                          axis: str = DATA_AXIS) -> Iterator[tuple]:
    """The host half of a sharded put that copies nothing: the C-contiguous
    ``rows`` divided over ``axis`` in contiguous runs of ``shard_rows``
    rows (the last runs are shorter, or empty, where the table ends), each
    run handed to its shard's devices FLAT (one dimension: the runtime
    lays nothing out on the host) in pieces of ``piece_rows`` rows.

    A generator of rounds ``(first, pieces)``: ``pieces[i]`` holds, one
    per device of shard ``i`` (:func:`shard_devices`), the device array of
    rows ``[first, first + piece_rows)`` of that shard's run, cut at the
    run's end (length 0 past it).  Every piece is a view of ``rows``; all
    the devices' pieces of a round are handed over together, and a round
    is waited for before the next one is put, so that the process has no
    more than a round in flight: the caller sizes ``piece_rows`` so that a
    round stays under :data:`PUT_BYTES` (``rows_a_put(shard_rows,
    row_bytes * mesh.size)``).  The last round is not waited for: the
    caller's next program is.  One process only: every device of the mesh
    must be addressable."""
    if mesh_process_count(mesh) > 1:
        raise ValueError("put_sharded_in_pieces puts from one process; a "
                         "process-spanning mesh takes put_sharded")
    n = len(rows)
    devices = shard_devices(mesh, axis)
    flying: list = []
    for first in range(0, max(min(shard_rows, n), 1), piece_rows):
        for piece in flying:
            piece.block_until_ready()
        pieces = []
        for i, line in enumerate(devices):
            lo = min(n, i * shard_rows + first)
            hi = min(n, i * shard_rows + min(first + piece_rows, shard_rows))
            flat = rows[lo:hi].reshape(-1)
            pieces.append([jax.device_put(flat, device) for device in line])
        flying = [piece for line in pieces for piece in line]
        yield first, pieces


def put_in_rounds(arrays: Sequence[np.ndarray], device) -> List[jax.Array]:
    """Each host array to ``device`` as a device array of its own (no
    copy on the device: what is put is what the program reads), in rounds
    of at most :data:`PUT_BYTES` in flight, each round waited for before
    the next is put.  The last round is not waited for: the caller's next
    program is.  An array larger than the cap is a round of its own."""
    out: List[jax.Array] = []
    flying: list = []
    held = 0
    for arr in arrays:
        if flying and held + arr.nbytes > PUT_BYTES:
            for piece in flying:
                piece.block_until_ready()
            flying, held = [], 0
        out.append(jax.device_put(arr, device))
        flying.append(out[-1])
        held += arr.nbytes
    return out


def assemble_process_local(batch: Any, shardings: Any) -> tuple:
    """Multi-host prefetch transfer: assemble each process's LOCAL batch
    arrays into the global (non-fully-addressable) arrays in process
    order — the ``put_fn`` the streaming trainers hand to
    ``prefetch_to_device`` on process-spanning meshes."""
    return tuple(
        jax.make_array_from_process_local_data(sh, np.asarray(a))
        for a, sh in zip(batch, shardings))


def fetch_replicated(tree: Any) -> Any:
    """device_get that also handles non-fully-addressable arrays
    (multi-host).  A replicated array's local replica IS the global
    value; a sharded one (e.g. the model-axis LR weight of
    ``sgd._mixed_update_sharded``) is assembled with one cross-process
    allgather of its shards — every process gets the full array, the
    same collective-fetch stance as ``iteration/checkpoint.py``."""
    def get(x):
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            if x.sharding.is_fully_replicated:
                return np.asarray(x.addressable_data(0))
            from jax.experimental import multihost_utils

            return np.asarray(
                multihost_utils.process_allgather(x, tiled=True))
        return np.asarray(jax.device_get(x))

    return jax.tree_util.tree_map(get, tree)


def replicate(tree: Any, mesh: Optional[Mesh] = None) -> Any:
    """device_put a pytree fully replicated over the mesh (multi-host-safe:
    on a process-spanning mesh every process must pass identical values)."""
    mesh = mesh or default_mesh()
    sharding = replicated(mesh)
    if mesh_process_count(mesh) > 1:
        return jax.tree_util.tree_map(
            lambda x: jax.make_array_from_process_local_data(
                sharding, np.asarray(x)), tree)
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sharding), tree)
