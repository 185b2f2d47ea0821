"""``tick_roofline``: the traced decode ticks' share of their memory roofline: the
weights outside the routed experts, the experts the rows touched, the index
keys of each row's context, the latents selected and the window's."""

from benchmark.dots3_stats import tick_roofline_pct as read  # noqa: F401
