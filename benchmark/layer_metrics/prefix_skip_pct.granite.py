"""``prefix_skip_pct``: prompt tokens the prefix cache spared
(``Trace.prefix_tokens_skipped``) over prompt tokens, window's requests: each
such admission restored its recurrent state from a snapshot entry."""

from benchmark.serve_stats import prefix_skip_pct as read  # noqa: F401
