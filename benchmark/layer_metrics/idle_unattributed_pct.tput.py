"""``idle_unattributed_pct.tput``: ``idle_unattributed_pct`` in the cells judged by
``serve_tput``."""

from benchmark import lib

read = lib.load_module("layer_metrics", "idle_unattributed_pct").read
