"""``tick_dev_ms``: device time of one run of the decode tick program (64 slots:
the rows' recurrent state read and written in place, whatever the context)."""

from benchmark import serve_stats


def read(rec: dict):
    return serve_stats.program_ms(rec, "_tick")
