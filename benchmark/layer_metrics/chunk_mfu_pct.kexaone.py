"""``chunk_mfu_pct``: the traced prefill chunks' operations (the products of the
tokens counted, the routed work for the choices held here, attention over the
keys visible, 128 at most on a sliding layer) over their device time, as a
share of the chip's peak."""

from benchmark.kexaone_stats import chunk_mfu_pct as read  # noqa: F401
