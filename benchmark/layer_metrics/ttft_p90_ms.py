"""``ttft_p90_ms``: as ``ttft_mean_ms``, the 90th percentile over the window's
requests; recorded, decides nothing (a hundred requests do not hold it)."""

from benchmark import lib, serve_stats


def read(rec: dict):
    v = serve_stats.ttft_ms(rec)
    return lib.quantile(v, 0.9) if v else None
