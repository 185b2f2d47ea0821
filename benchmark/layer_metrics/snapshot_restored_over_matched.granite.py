"""``snapshot_restored_over_matched``: ``prefix.blocks_restored /
prefix.blocks_matched`` over the window: 1.0 where the snapshot budget lost
nothing; the rest was matched by the index, found without a snapshot and
computed again (the first wave and each prompt's second bearer)."""

from benchmark.granite_stats import snapshot_restored_over_matched as read  # noqa: F401,E501
