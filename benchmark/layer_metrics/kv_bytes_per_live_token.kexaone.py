"""``kv_bytes_per_live_token``: pool, ring and snapshot bytes the live rows hold
(``kv.full_bytes_live + kv.window_bytes_live``) over the positions they hold
(``kv.tokens_live``), over the window's ticking steps; 32,768 would be every
layer paged."""

from benchmark.kexaone_stats import kv_bytes_per_live_token as read  # noqa: F401
