"""``hbm_peak_gb``: the peak on the chip, read when the window closes and before
the reference runs: the weights (9.51 GB), the slots' states (2.44 GB), the
pools (2.45 GB), the snapshot entries (0.92 GB), the programs' scratch."""

from benchmark.lib import hbm_peak_gb as read  # noqa: F401
