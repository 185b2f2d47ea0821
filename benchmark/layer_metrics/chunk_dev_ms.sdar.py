"""``chunk_dev_ms``: device time of one run of the prefill chunk program."""

from benchmark import serve_stats


def read(rec: dict):
    return serve_stats.program_ms(rec, "_chunk")
