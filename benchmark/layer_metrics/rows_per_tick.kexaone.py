"""``rows_per_tick``: mean of the engine's ``serve.decoding`` gauge over the
window's decode ticks (of 64 slots; how many decode together is the block
allocator's doing: the pool is overcommitted)."""

from benchmark.serve_stats import rows_per_tick as read  # noqa: F401
