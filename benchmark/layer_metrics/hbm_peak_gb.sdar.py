"""``hbm_peak_gb``: the peak on the chip, read when the window closes and before
the reference runs: the weights (8.72 GB), the pool (3.24 GB), the block's
logits, the programs' scratch."""

from benchmark.lib import hbm_peak_gb as read  # noqa: F401
