"""``itl_p50_ms``: median gap between tokens (see ``itl_p90_ms``)."""

from benchmark import lib, serve_stats


def read(rec: dict):
    v = serve_stats.token_gaps_ms(rec)
    return lib.quantile(v, 0.5) if v else None
