"""``itl_p90_ms``: 90th percentile of the gap between tokens, over every
engine step of the window that starts with a row decoding (some hundreds of
steps).  The tail users feel: a step that carries other requests' prefill
chunks.  Host clock, stamped by the benchmark's wrapper on the engine's step."""

from benchmark import lib, serve_stats


def read(rec: dict):
    gaps = serve_stats.token_gaps_ms(rec)
    return lib.quantile(gaps, 0.9) if gaps else None
