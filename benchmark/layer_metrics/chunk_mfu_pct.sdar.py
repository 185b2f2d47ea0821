"""``chunk_mfu_pct``: the traced prefill chunks' operations (products, attention
over the visible keys under the block-causal mask, the routed work for the
choices made) over their device time, as a share of the chip's peak."""

from benchmark.sdar_stats import chunk_mfu_pct as read  # noqa: F401
