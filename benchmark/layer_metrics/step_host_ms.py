"""``step_host_ms``: mean over the window's ticking engine steps of the host's
own time a step (every phase of the step row but ``device_sync``), from inside
the program."""

from benchmark.step_log_stats import step_host_ms as read  # noqa: F401
