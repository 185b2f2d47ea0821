"""``chunk_mfu_pct``: the traced prefill chunks' operations (products, indexer,
selected attention, the routed work for the choices that were held) over their
device time, as a share of the chip's peak."""

from benchmark.dots3_stats import chunk_mfu_pct as read  # noqa: F401
