"""``sched_host_ms``: engine step wall time less the device time of the
programs it ran, per step of the traced window."""

from benchmark.serve_stats import sched_host_ms as read  # noqa: F401
