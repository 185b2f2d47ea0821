"""``tick_roofline``: the traced block ticks' share of their memory roofline: the
weights outside the experts once, the experts the rows touched (9.44 MB each),
the committed keys and values the rows attend to, the block's own keys and
values, and the block's logits written."""

from benchmark.sdar_stats import tick_roofline_pct as read  # noqa: F401
