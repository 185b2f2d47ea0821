"""``rows_per_tick``: mean of the engine's ``serve.decoding`` gauge over the
window's decode ticks (of 64 slots, fully backed: how many decode together is
the queue's and the prefill's doing)."""

from benchmark.serve_stats import rows_per_tick as read  # noqa: F401
