"""``serve_tput``: prompt tokens plus output tokens of the fixed offline batch
over the time from the first submit to the last completion.  Host clock."""

from benchmark import serve_stats


def read(rec: dict):
    return serve_stats.tokens_per_s(rec)
