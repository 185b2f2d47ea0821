"""``step_longest_sync_pct``: the share of the window's longest step inside
``device_sync``: whether the wait or the host held it."""

from benchmark.step_log_stats import step_longest_sync_pct as read  # noqa: F401
