from . import adam_table_pallas  # noqa: F401  (registers routed_adam_update)
from . import als_solve_pallas  # noqa: F401  (registers als_cholesky_solve)
from .emb_grad import (  # noqa: F401
    EmbGradRoute,
    emb_grad_route,
    routed_run_sums,
    routed_table_grad,
    routed_table_grad_gather,
)
from .emb_grad_pallas import (  # noqa: F401
    fold_runs_fused,
    routed_table_grad_gather_fused,
)
from . import int8_serving  # noqa: F401  (registers the "int8" backends)
from .ell_scatter import (  # noqa: F401
    EllLayout,
    ell_layout,
    ell_layout_device,
    ell_scatter_apply,
)
from . import retrieve_pallas  # noqa: F401  (the "pallas" retrieve backend)
from .kmeans_pallas import (  # noqa: F401
    kmeans_assign_reduce,
    kmeans_update_stats,
    kmeans_workset_update,
    pad_correction,
    pick_block_n,
    pick_block_n_workset,
    supported,
    update_stats_sharded,
)
