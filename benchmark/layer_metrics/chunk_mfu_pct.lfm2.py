"""``chunk_mfu_pct``: the traced prefill chunks' operations (products, taps,
attention over the visible keys, the routed work for the choices made) over
their device time, as a share of the chip's peak."""

from benchmark.lfm2_stats import chunk_mfu_pct as read  # noqa: F401
