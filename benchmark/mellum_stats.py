"""Readers of the ``mellum_train`` family's per-layer metrics: the step's own
counters (``rec["counters"]``, the window's totals) and the traced kernels'
device time (``rec["kernels"]``) against the operations the family counts
from the shapes (``rec["kernel_ops"]``; ``rec["kernel_names"]`` groups the
kernels by what they are part of, the family's ``KERNELS``).  A record
without them (another family's, or a program that has no such counter)
gives ``None``."""

from __future__ import annotations

from benchmark import lib


def _counter(rec: dict, name: str, of: str = "counters"):
    return (rec.get(of) or {}).get(name)


def _ratio_pct(rec: dict, part: str, whole: str):
    a, b = _counter(rec, part), _counter(rec, whole)
    if a is None or not b:
        return None
    return 100.0 * a / b


def attn_key_blocks_pct(rec: dict):
    """Key blocks the attention kernels' grids visited, over what causal
    layers throughout would visit."""
    return _ratio_pct(rec, "attn.key_blocks_visited",
                      "attn.key_blocks_causal")


def moe_held_share_pct(rec: dict):
    return _ratio_pct(rec, "moe.choices_held", "moe.choices_total")


def moe_load_max_over_mean(rec: dict):
    load = [v for k, v in (rec.get("counters") or {}).items()
            if k.startswith("moe.held_load.")]
    if not load or not sum(load):
        return None
    return max(load) / (sum(load) / len(load))


def _kernel_share(rec: dict, part: str, rows_a_call: float = 1.0):
    """The operations of the kernels of ``part`` (a key of the family's
    ``KERNELS``) over their traced seconds, as a share of the chip's peak:
    a call computes ``rows_a_call`` times its entry of ``kernel_ops``."""
    kernels, names = rec.get("kernels"), rec.get("kernel_names") or {}
    if not kernels or not rec.get("kernel_ops") or part not in names:
        return None
    ops = seconds = 0.0
    for n in names[part]:
        k = kernels.get(n)
        if not k or not k["count"]:
            return None
        ops += k["count"] * rows_a_call * rec["kernel_ops"][n]
        seconds += k["total_s"]
    peak = lib.peaks(rec["device_kind"])["bf16_flops_per_s"]
    return lib.share_of_peak(ops / seconds, peak, part)


def flash_band_mfu_pct(rec: dict):
    return _kernel_share(rec, "flash_band")


def flash_full_mfu_pct(rec: dict):
    return _kernel_share(rec, "flash_full")


def experts_mfu_pct(rec: dict):
    """The grouped products, forward and backward, over their traced time.
    A call's rows are the choices that fell on the held experts in its group
    of sequences: the traced steps' ``moe.choices_held`` over the calls they
    made (``moe.expert_calls``: one a layer and sequence a step),
    the mean at which every traced call is counted."""
    held = _counter(rec, "moe.choices_held", "traced_counters")
    calls = _counter(rec, "moe.expert_calls", "traced_counters")
    if held is None or not calls:
        return None
    return _kernel_share(rec, "experts", held / calls)
