"""``idle_sched_pct.tput``: ``idle_sched_pct`` in the cells judged by
``serve_tput``."""

from benchmark import lib

read = lib.load_module("layer_metrics", "idle_sched_pct").read
