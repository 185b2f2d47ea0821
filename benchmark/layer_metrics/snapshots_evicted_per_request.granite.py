"""``snapshots_evicted_per_request``: ``ssm.snapshots_evicted`` over the window
a request it finished: how often the budget of 24 entries had to take one from
the least recently restored block."""

from benchmark.granite_stats import snapshots_evicted_per_request as read  # noqa: F401,E501
