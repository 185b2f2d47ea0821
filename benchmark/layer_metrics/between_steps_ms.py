"""``between_steps_ms``: mean time from one ticking step's end to the next
one's beginning, by the step rows: the replica pump's sections between
steps."""

from benchmark.step_log_stats import between_steps_ms as read  # noqa: F401
