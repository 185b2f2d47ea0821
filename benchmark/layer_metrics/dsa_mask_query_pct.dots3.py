"""``dsa_mask_query_pct``: the growth of ``dsa.mask_queries`` over that of
``dsa.queries`` across the window's steps (the rows carry both counters): the
share of the full layers' queries that kept the indexer's selection as a
mask."""

from benchmark.step_log_stats import dsa_mask_query_pct as read  # noqa: F401
