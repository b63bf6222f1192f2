"""graftlint — the repo's unified static-analysis framework (ISSUE 8).

PRs 1-7 each found a *convention* violation by hand: the
``flush_lock``-across-``put`` deadlock (PR 1), the top_k-inside-manual-
region XLA abort (PR 3), the zombie-reader race (PR 7).  This package
turns those conventions into enforced passes over ONE shared
infrastructure — qualified-name resolution through import aliases and
local rebinding, follow-functions-passed-by-reference, per-line
``# graftlint: disable=<pass>`` suppressions with unused-suppression
enforcement, and a committed baseline for grandfathered findings
(``scripts/graftlint/baseline.txt``).

Run everything::

    python -m scripts.graftlint            # all passes, exit 0 = clean
    python -m scripts.graftlint --json -   # machine-readable findings

Passes (see ``scripts/graftlint/passes/``):

- ``host-sync``               no host synchronization inside step/scan
                              bodies (absorbed from check_no_host_sync)
- ``atomic-writes``           durable-layer writes are tmp -> os.replace
                              (absorbed from check_atomic_writes)
- ``donation-safety``         a value passed at a ``donate_argnums``
                              position is never read again
- ``lock-discipline``         no Lock held across a blocking call
- ``collective-consistency``  collectives inside manual regions stay
                              well-formed across branches

Wired into tier-1 via ``tests/test_graftlint.py``.
"""

from .core import Finding, ModuleInfo, Project, iter_py_files  # noqa: F401
from .runner import Report, all_passes, run  # noqa: F401

__all__ = ["Finding", "ModuleInfo", "Project", "Report", "all_passes",
           "iter_py_files", "run"]
