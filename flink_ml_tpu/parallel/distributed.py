"""Multi-host distributed runtime.

The reference scales out via Flink's cluster runtime: TaskManagers connect
over netty, the JobManager coordinates (SURVEY §2.10 control plane).  The
TPU-native equivalent is the JAX distributed runtime: one process per host,
ICI collectives inside a pod slice, DCN across slices, and a tiny control
plane (this module) for initialization, meshes spanning hosts, host-local ->
global array assembly, and barriers.

Usage on a pod (one process per host):

    from flink_ml_tpu.parallel import distributed as dist
    dist.initialize()                      # env-driven on TPU pods
    mesh = dist.global_mesh({"data": -1})  # all devices on all hosts
    batch = dist.host_local_to_global(local_batch, mesh, axis="data")
    ... iterate(...) exactly as single-host — the jitted step is SPMD ...

Everything degrades gracefully to single-process (the default environment
here and in tests).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Mapping, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import device_mesh

__all__ = [
    "initialize",
    "is_initialized",
    "ProcessInfo",
    "process_info",
    "global_mesh",
    "hybrid_mesh",
    "host_local_to_global",
    "global_to_host_local",
    "barrier",
    "broadcast_from_host0",
]

_INITIALIZED = False


_POD_ENV_VARS = ("JAX_COORDINATOR_ADDRESS", "COORDINATOR_ADDRESS",
                 "MEGASCALE_COORDINATOR_ADDRESS", "CLOUD_TPU_TASK_ID")


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> None:
    """Join the JAX distributed runtime (the analog of TaskManagers
    registering with the JobManager).

    MUST run before any other JAX call on multi-host — jax.distributed
    requires an uninitialized backend.  With explicit args the call is
    mandatory and errors propagate; with no args it auto-initializes when a
    pod launcher environment is detected (coordinator env vars) and is a
    no-op single-process.
    """
    global _INITIALIZED
    if _INITIALIZED:
        return
    explicit = (coordinator_address is not None
                or num_processes not in (None, 1)
                or process_id is not None)
    import os

    pod_env = any(v in os.environ for v in _POD_ENV_VARS)
    if explicit or pod_env:
        # Explicit multi-process request (or launcher env): never silently
        # degrade — failures here mean the job would run single-host.
        jax.distributed.initialize(coordinator_address=coordinator_address,
                                   num_processes=num_processes,
                                   process_id=process_id)
    _INITIALIZED = True


def is_initialized() -> bool:
    # Deliberately does NOT probe jax.process_count(): that would initialize
    # the backend, breaking a later initialize() on multi-host.
    return _INITIALIZED


@dataclass
class ProcessInfo:
    process_index: int
    process_count: int
    local_device_count: int
    global_device_count: int

    @property
    def is_coordinator(self) -> bool:
        return self.process_index == 0


def process_info() -> ProcessInfo:
    return ProcessInfo(
        process_index=jax.process_index(),
        process_count=jax.process_count(),
        local_device_count=len(jax.local_devices()),
        global_device_count=len(jax.devices()),
    )


def global_mesh(axis_sizes: Optional[Mapping[str, int]] = None) -> Mesh:
    """A mesh over ALL devices across ALL hosts (jax.devices() is global)."""
    return device_mesh(axis_sizes, devices=jax.devices())


def hybrid_mesh(ici_axes: Mapping[str, int], dcn_axis: str = "dcn") -> Mesh:
    """Two-tier mesh: the leading axis spans hosts over DCN, the remaining
    axes span each host's chips over ICI.  Shard batch over ``dcn_axis`` x
    'data' and keep model axes inside a host so heavy collectives ride ICI
    (the scaling-book layout rule)."""
    ici_sizes = list(ici_axes.values())
    n_proc = jax.process_count()
    expected = n_proc * int(np.prod(ici_sizes))
    if expected != len(jax.devices()):
        raise ValueError(
            f"hybrid mesh {n_proc} hosts x {dict(ici_axes)} needs {expected} "
            f"devices, have {len(jax.devices())}")
    if n_proc == 1:
        devices = np.asarray(jax.devices()).reshape((1, *ici_sizes))
        return Mesh(devices, axis_names=(dcn_axis, *ici_axes.keys()))
    from jax.experimental import mesh_utils

    # create_hybrid_device_mesh takes same-rank per-granule and DCN shapes;
    # the total mesh is their elementwise product.  The DCN granule is the
    # process (one host's chips), which is what the leading axis is sized by.
    dev_array = mesh_utils.create_hybrid_device_mesh(
        mesh_shape=[1] + ici_sizes,
        dcn_mesh_shape=[n_proc] + [1] * len(ici_sizes),
        process_is_granule=True,
    )
    return Mesh(dev_array.reshape((n_proc, *ici_sizes)),
                axis_names=(dcn_axis, *ici_axes.keys()))


def host_local_to_global(tree: Any, mesh: Mesh, axis: str = "data") -> Any:
    """Assemble per-host local batches into one global sharded array (each
    host contributes its shard — the multi-host input pipeline step; wraps
    ``multihost_utils.host_local_array_to_global_array``)."""
    if jax.process_count() == 1:
        sharding = NamedSharding(mesh, P(axis))
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, sharding), tree)
    from jax.experimental import multihost_utils

    return multihost_utils.host_local_array_to_global_array(
        tree, mesh, P(axis))


def global_to_host_local(tree: Any, mesh: Mesh, axis: str = "data") -> Any:
    """Inverse of :func:`host_local_to_global`."""
    if jax.process_count() == 1:
        return jax.tree_util.tree_map(np.asarray, jax.device_get(tree))
    from jax.experimental import multihost_utils

    return multihost_utils.global_array_to_host_local_array(
        tree, mesh, P(axis))


def barrier(tag: str = "flink_ml_tpu") -> None:
    """Cross-host barrier (the control-plane alignment point; no-op
    single-process)."""
    if jax.process_count() == 1:
        return
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(tag)


def broadcast_from_host0(tree: Any) -> Any:
    """Make host 0's value visible on every process (the analog of the
    coordinator fanning out a GloballyAlignedEvent payload)."""
    if jax.process_count() == 1:
        return tree
    from jax.experimental import multihost_utils

    return multihost_utils.broadcast_one_to_all(tree)
