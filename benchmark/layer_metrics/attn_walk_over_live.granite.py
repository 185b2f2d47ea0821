"""``attn_walk_over_live.granite``: ``attn_walk_over_live`` in ``granite_toolcalls``."""

from benchmark import lib

read = lib.load_module("layer_metrics", "attn_walk_over_live").read
