"""``gen_late_p99_ms``: how late the load generator sent (sent minus due), 99th
percentile: a starved generator must not read as a fast server."""

from benchmark import lib, serve_stats


def read(rec: dict):
    v = serve_stats.gen_late_ms(rec)
    return lib.quantile(v, 0.99) if v else None
