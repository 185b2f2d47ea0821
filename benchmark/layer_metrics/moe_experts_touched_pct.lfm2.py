"""``moe_experts_touched_pct``: mean over the window's decode ticks of the
experts their rows touched, of the 32 of each of the 12 expert layers."""

from benchmark.lfm2_stats import moe_experts_touched_pct as read  # noqa: F401
