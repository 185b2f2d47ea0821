"""``attn_walk_over_live.batch``: ``attn_walk_over_live`` in the cells judged by ``serve_tput``."""

from benchmark import lib

read = lib.load_module("layer_metrics", "attn_walk_over_live").read
