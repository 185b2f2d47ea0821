"""``tick_roofline``: the traced decode ticks' share of their memory roofline: the
weights outside the experts once, the held experts the rows touched (75.5 MB
each), the full layers' keys and values of every live position (8,192 B), and
of each row's ring the positions within the window (24,576 B each)."""

from benchmark.kexaone_stats import tick_roofline_pct as read  # noqa: F401
