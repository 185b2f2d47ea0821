"""``attn_walk_over_live``: the growth of ``attn.blocks_visited`` over that of
``attn.blocks_live`` across the window's steps (the rows carry both counters);
1.0 is a walk that reads what the live rows hold and nothing else, and a slot
that does not decode counts one tile above it."""

from benchmark.step_log_stats import attn_walk_over_live as read  # noqa: F401
