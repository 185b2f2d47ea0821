"""``between_steps_ms.granite``: ``between_steps_ms`` in ``granite_toolcalls``."""

from benchmark import lib

read = lib.load_module("layer_metrics", "between_steps_ms").read
