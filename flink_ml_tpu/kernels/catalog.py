"""The registration catalog: importing this module imports every module
that registers kernels, so ``registry.lookup``/``ops()`` see the full
table no matter which consumer asked first.

Registrations live NEXT TO their implementations (an op's shape contract
is the kernel's own business, an op's planning policy the model's):

- ``ops/ell_scatter.py``      — ``ell_margin``, ``ell_scatter_apply``
- ``ops/emb_grad.py`` / ``ops/emb_grad_pallas.py`` — ``routed_table_grad``
- ``ops/adam_table_pallas.py`` — ``routed_adam_update``
- ``ops/als_solve_pallas.py`` — ``als_cholesky_solve``
- ``models/common/gbt.py``    — ``gbt_level_histograms``
- ``models/common/linear.py`` — ``linear_margins`` (stage convention)
- ``models/clustering/kmeans.py`` — ``kmeans_assign`` (stage),
  ``kmeans_update_stats``, ``kmeans_workset_update``
- ``models/recommendation/widedeep.py`` — ``widedeep_scores`` (stage)
- ``ops/int8_serving.py``      — "int8" backends of ``linear_margins``,
  ``kmeans_assign``, ``widedeep_scores`` (forced-lookup only; the
  servable bind path quantizes the params they consume)
- ``retrieval/ivf.py`` / ``ops/retrieve_pallas.py`` — ``retrieve``
  (stage convention; the IVF / IVF-PQ fused scan+top-k, first
  non-model op family)

This module is imported lazily by ``registry._ensure_catalog`` (first
lookup), never at ``flink_ml_tpu.kernels`` import — that keeps the
registry itself dependency-free and cycle-safe.
"""

from .. import ops  # noqa: F401  (ell + kmeans + emb_grad + adam + als + retrieve)
from ..models.clustering import kmeans  # noqa: F401
from ..models.common import gbt, linear  # noqa: F401
from ..models.recommendation import widedeep  # noqa: F401
from ..retrieval import ivf  # noqa: F401  (the "xla" retrieve backend)
