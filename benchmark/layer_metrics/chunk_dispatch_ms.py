"""``chunk_dispatch_ms``: the window's ``admit.prefill_dispatch`` seconds over
its chunk programs, by the step rows: host time to hand one chunk over."""

from benchmark.step_log_stats import chunk_dispatch_ms as read  # noqa: F401
