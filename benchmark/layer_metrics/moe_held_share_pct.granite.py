"""``moe_held_share_pct``: ``moe.choices_held / moe.choices_total`` over the
window: 50 when routing over the 72 experts is even and 36 are held."""

from benchmark.granite_stats import moe_held_share_pct as read  # noqa: F401
