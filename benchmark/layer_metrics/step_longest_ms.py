"""``step_longest_ms``: the longest engine step of the window, by the step
rows: a stalled run names itself."""

from benchmark.step_log_stats import step_longest_ms as read  # noqa: F401
