"""``itl_tail10_ms``: mean of the slowest tenth of the gaps between tokens, the
steadier neighbour of ``itl_p90_ms``."""

from benchmark import serve_stats


def read(rec: dict):
    v = serve_stats.token_gaps_ms(rec)
    return serve_stats.tail_mean(v, 0.1) if v else None
