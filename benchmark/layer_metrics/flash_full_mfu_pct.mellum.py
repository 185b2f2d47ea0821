"""``flash_full_mfu_pct``: the operations of the three flash kernels of the
full layers (forward, dQ, dK/dV; counted under the causal mask) over their
traced device time, as a share of the chip's bf16 peak."""

from benchmark.mellum_stats import flash_full_mfu_pct as read  # noqa: F401
