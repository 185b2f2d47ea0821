"""``chunk_dev_ms``: device time of one run of the prefill chunk program (512
tokens of one row: the chunked form of the recurrence, one read and one write
of the row's state)."""

from benchmark import serve_stats


def read(rec: dict):
    return serve_stats.program_ms(rec, "_chunk")
