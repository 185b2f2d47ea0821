"""``device_idle_pct``: the share of the traced window in which no operation
ran on the device."""

from benchmark.lib import device_idle_pct as read  # noqa: F401
