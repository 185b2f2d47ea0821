"""``ssm_state_share_pct``: of the bytes the traced ticks had to move, the share
that was recurrent state (``ssm.state_bytes_moved`` over those ticks, the
chunks' part taken out, over the ticks' reckoned bytes): what a smaller state
or a kernel for the update would act on."""

from benchmark.granite_stats import ssm_state_share_pct as read  # noqa: F401
