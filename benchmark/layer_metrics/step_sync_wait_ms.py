"""``step_sync_wait_ms``: mean ``device_sync`` of the step rows of the window's
ticking steps: how long the host waited for the device."""

from benchmark.step_log_stats import step_sync_wait_ms as read  # noqa: F401
