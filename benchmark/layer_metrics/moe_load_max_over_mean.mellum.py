"""``moe_load_max_over_mean``: the busiest held expert's token-choices
(``moe.held_load.<e>``) over the held experts' mean, over the window."""

from benchmark.mellum_stats import moe_load_max_over_mean as read  # noqa: F401
