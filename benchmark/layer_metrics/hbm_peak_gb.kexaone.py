"""``hbm_peak_gb``: the peak on the chip, read when the window closes and before
the reference runs: the weights (11.96 GB), the pools with their snapshots,
the rings, the programs' scratch."""

from benchmark.lib import hbm_peak_gb as read  # noqa: F401
