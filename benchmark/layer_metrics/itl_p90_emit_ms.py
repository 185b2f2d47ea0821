"""``itl_p90_emit_ms``: p90 over the window's tokens (first tokens left out)
of the gap between consecutive emitting steps' emit moments over the tokens a
row, by the step rows."""

from benchmark.step_log_stats import itl_p90_emit_ms as read  # noqa: F401
