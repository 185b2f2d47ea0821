"""``rows_per_tick``: mean of the engine's ``serve.decoding`` gauge over the
window's block ticks (of 128 slots)."""

from benchmark.serve_stats import rows_per_tick as read  # noqa: F401
