"""``tick_roofline``: the traced decode ticks' share of their memory roofline: the
weights outside the experts once, the experts the rows touched (22.0 MB each),
the live keys and values of the attention layers, the rows' convolution state."""

from benchmark.lfm2_stats import tick_roofline_pct as read  # noqa: F401
