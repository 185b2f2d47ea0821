"""``sched_host_ms``: per ticking engine step of the traced window, the wall
time less the device time of the programs it ran (128 slots to walk), from the
first tick's start to the last whole tick's end on the trace's clock."""

from benchmark.lfm2_stats import sched_host_ms as read  # noqa: F401
