"""``chunk_mfu_pct``: the traced prefill chunks' operations (the products of the
tokens counted, the recurrence at a multiply-add into the state and one out of
it a token, the routed work for the choices held here, attention over the keys
visible) over their device time, as a share of the chip's peak."""

from benchmark.granite_stats import chunk_mfu_pct as read  # noqa: F401
