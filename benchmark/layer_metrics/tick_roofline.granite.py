"""``tick_roofline``: the traced decode ticks' share of their memory roofline: the
weights outside the experts once (the tied head among them), the held experts
the rows touched (18.9 MB each), the state of the rows that advance read and
written (38.2 MB a row, each way), and the attention layer's keys and values of
every live position (4,096 B)."""

from benchmark.granite_stats import tick_roofline_pct as read  # noqa: F401
