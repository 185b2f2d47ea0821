"""``tick_dev_ms``: device time of one run of the block tick program (a block
of 4 positions a decoding row, under the block-causal mask)."""

from benchmark import serve_stats


def read(rec: dict):
    return serve_stats.program_ms(rec, "_tick")
