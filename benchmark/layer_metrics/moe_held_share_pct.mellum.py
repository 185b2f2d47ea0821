"""``moe_held_share_pct``: ``moe.choices_held / moe.choices_total`` over the
window: 25 when routing over the 64 experts is even and 16 are held."""

from benchmark.mellum_stats import moe_held_share_pct as read  # noqa: F401
