"""``flash_band_mfu_pct``: the operations of the three flash kernels of the
sliding layers (forward, dQ, dK/dV; counted under the band's mask) over
their traced device time, as a share of the chip's bf16 peak."""

from benchmark.mellum_stats import flash_band_mfu_pct as read  # noqa: F401
