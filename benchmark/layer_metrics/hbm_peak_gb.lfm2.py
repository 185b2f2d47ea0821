"""``hbm_peak_gb``: the peak on the chip, read when the window closes and before
the reference runs: the weights (9.33 GB), the pools and the convolution's
states and snapshots (1.74 GB), the programs' scratch."""

from benchmark.lib import hbm_peak_gb as read  # noqa: F401
