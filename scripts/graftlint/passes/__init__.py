"""The pass catalog.  Order is the report order; ids are the names
``# graftlint: disable=<id>`` and the baseline file key on."""

from .atomic_writes import AtomicWritesPass
from .collectives import CollectiveConsistencyPass
from .donation import DonationSafetyPass
from .host_sync import HostSyncPass
from .kernel_registry import KernelRegistryPass
from .locks import LockDisciplinePass
from .unfenced_timing import UnfencedTimingPass

ALL_PASSES = (
    HostSyncPass,
    AtomicWritesPass,
    DonationSafetyPass,
    LockDisciplinePass,
    CollectiveConsistencyPass,
    KernelRegistryPass,
    UnfencedTimingPass,
)

__all__ = ["ALL_PASSES", "AtomicWritesPass", "CollectiveConsistencyPass",
           "DonationSafetyPass", "HostSyncPass", "KernelRegistryPass",
           "LockDisciplinePass", "UnfencedTimingPass"]
