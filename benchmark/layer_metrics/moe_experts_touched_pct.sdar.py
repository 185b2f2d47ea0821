"""``moe_experts_touched_pct``: mean over the window's block ticks of the
experts their rows touched, of the 128 of each of the 6 layers."""

from benchmark.sdar_stats import moe_experts_touched_pct as read  # noqa: F401
