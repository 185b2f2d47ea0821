"""``idle_front_door_pct.tput``: ``idle_front_door_pct`` in the cells judged by
``serve_tput``."""

from benchmark import lib

read = lib.load_module("layer_metrics", "idle_front_door_pct").read
