"""``mfu_pct``: operations the forward and backward passes need (counted
from the shapes by the family, not by XLA) times images per second per chip,
over the chip's bf16 peak."""

from benchmark import lib


def read(rec: dict):
    rate = rec["items"] / rec["window_s"] / rec["chips"]
    peak = lib.peaks(rec["device_kind"])["bf16_flops_per_s"]
    return lib.share_of_peak(rec["flops_per_item"] * rate, peak, "mfu_pct")
