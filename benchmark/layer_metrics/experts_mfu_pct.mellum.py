"""``experts_mfu_pct``: the grouped products of the expert layer (forward,
the rows' gradient, the weights' gradient) for the choices
``moe.choices_held`` counted, over their traced device time, as a share of
the chip's bf16 peak."""

from benchmark.mellum_stats import experts_mfu_pct as read  # noqa: F401
