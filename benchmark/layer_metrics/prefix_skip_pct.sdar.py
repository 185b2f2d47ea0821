"""``prefix_skip_pct``: prompt tokens the prefix cache spared
(``Trace.prefix_tokens_skipped``) over prompt tokens, window's requests: whole
pages inside the prompt's whole blocks."""

from benchmark.serve_stats import prefix_skip_pct as read  # noqa: F401
