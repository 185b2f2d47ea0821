"""``dsa_selected_pct``: ``dsa.keys_selected / dsa.keys_visible`` over the window:
the share of the cached keys the indexer scored that its top-k kept."""

from benchmark.dots3_stats import dsa_selected_pct as read  # noqa: F401
