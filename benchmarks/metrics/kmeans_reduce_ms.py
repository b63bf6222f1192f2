"""Device self time a step (one Lloyd iteration) of the operations under
the program's scope ``kmeans.reduce``, on the fullest chip: the all-reduce
of the chips' ``(k, d)`` sums and ``k`` counts (``ops/kmeans_pallas.py:
update_stats_sharded`` puts the ``psum`` and nothing else under it), the
wait for the slowest chip included.  ``None`` where no operation carries
the scope (a fit on one chip, the parent commit's).  A time and not a
share: ``harness/peaks.json`` has no interconnect peak."""

from harness import program_scopes


def read(ctx):
    return program_scopes.scope_ms(ctx, "kmeans.reduce")
