"""``ttft_mean_ms``: mean over every request due in the window of first token
minus due time.  A mean, not a percentile: a hundred requests hold it (PERF.md,
section 2).  Failed or lost requests count in ``failed``.  Host clock."""

from benchmark import lib, serve_stats


def read(rec: dict):
    values = serve_stats.ttft_ms(rec)
    return lib.mean(values) if values else None
