"""``route_ms_p50``: ``route()`` received -> the engine enqueued, median; mostly
the wait for the pump to come round between engine steps."""

from benchmark import lib, serve_stats


def read(rec: dict):
    v = serve_stats.route_ms(rec)
    return lib.quantile(v, 0.5) if v else None
