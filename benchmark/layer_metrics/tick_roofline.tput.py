"""``tick_roofline``: the decode tick's share of its memory roofline."""

from benchmark.serve_stats import tick_roofline_pct as read  # noqa: F401
