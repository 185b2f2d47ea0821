"""``hbm_peak_gb``: the peak on the fullest chip, read when the window closes
and before the reference runs (what it counts is each family's to say:
PERF.md, section 4)."""

from benchmark.lib import hbm_peak_gb as read  # noqa: F401
