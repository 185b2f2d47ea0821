"""``moe_load_max_over_mean``: the busiest expert's token-choices over the
experts' mean, over the window."""

from benchmark.sdar_stats import moe_load_max_over_mean as read  # noqa: F401
