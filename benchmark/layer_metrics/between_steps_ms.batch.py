"""``between_steps_ms.batch``: ``between_steps_ms`` in the cells judged by ``serve_tput``."""

from benchmark import lib

read = lib.load_module("layer_metrics", "between_steps_ms").read
